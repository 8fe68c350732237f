"""Serving integration: engine cache behavior + RAC-scored KV-block
manager (radix validity, prefix reuse, eviction under pressure)."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import EmbeddingSpace
from repro.models import smoke_variant
from repro.serving import EngineConfig, KVBlockManager, ServingEngine


@pytest.fixture(scope="module")
def engine():
    mcfg = smoke_variant(get_config("paper"))
    ecfg = EngineConfig(cache_capacity=16, max_new_tokens=4, max_batch=4,
                        max_seq=64)
    return ServingEngine(mcfg, ecfg)


def test_repeat_request_hits_and_matches(engine):
    space = EmbeddingSpace(dim=64, seed=11)
    e = space.content_embedding(0, 0).astype(np.float32)
    p = space.paraphrase(e, 0, 0, 1).astype(np.float32)
    prompt = [5, 6, 7]
    done1 = engine.run([(0, e, prompt)])
    assert not done1[0].cached
    out1 = done1[0].out_tokens
    done2 = engine.run([(0, p, prompt)])      # paraphrase of the same query
    assert done2[0].cached
    assert done2[0].out_tokens == out1        # served from cache verbatim
    assert engine.stats["hits"] == 1


def test_engine_batches_multiple_misses(engine):
    space = EmbeddingSpace(dim=64, seed=12)
    reqs = [(100 + i, space.content_embedding(3, 100 + i).astype(np.float32),
             [2, 3, 4]) for i in range(6)]
    done = engine.run(reqs)
    assert len(done) == 6
    assert all(len(r.out_tokens) == 4 for r in done)


def test_engine_async_admit_matches_sync():
    """The acceptance criterion: with async_admit the engine returns
    identical request outputs (tokens, hit flags) to the blocking path,
    while generation slots no longer pay the admit cost inline."""
    from repro.core import SynthConfig, synthetic_trace

    mcfg = smoke_variant(get_config("paper"))
    trace = synthetic_trace(SynthConfig(trace_len=60, n_topics=8, seed=4))
    rng = np.random.default_rng(4)
    reqs = [(r.cid, r.emb, list(rng.integers(2, mcfg.vocab_size, size=3)))
            for r in trace.requests]

    def run(async_admit):
        eng = ServingEngine(mcfg, EngineConfig(
            cache_capacity=16, max_new_tokens=3, max_batch=4, max_seq=64,
            async_admit=async_admit))
        done = eng.run([(c, e, list(t)) for c, e, t in reqs])
        out = [(r.rid, r.cid, r.cached, tuple(r.out_tokens)) for r in done]
        stats = eng.stats
        eng.close()
        return out, stats

    out_sync, s_sync = run(False)
    out_async, s_async = run(True)
    assert out_sync == out_async
    for k in ("hits", "misses", "evictions", "generated_tokens", "batches"):
        assert s_sync[k] == s_async[k], k


def test_decode_step_updates_the_cache_in_place():
    """The engine's decode jit donates the stacked KV cache and writes each
    layer's row into it: the cache leaves are aliased input to output, a
    call deletes the cache it was handed, and the optimized program makes
    no cache-sized buffer of its own (no copy of the stacked cache or of a
    layer's slice, no fresh stacked buffer broadcast for the layer scan's
    output).  float32: XLA:CPU rewrites bfloat16 with converts of the whole
    cache that the TPU does not have."""
    mcfg = dataclasses.replace(smoke_variant(get_config("smollm_360m")),
                               n_layers=3, scan_layers=True)
    eng = ServingEngine(mcfg, EngineConfig(max_batch=8, max_seq=256))
    cache = eng.model.init_cache(8, 256)
    batch = {"tokens": jnp.zeros((8, 1), jnp.int32),
             "pos": jnp.arange(8, dtype=jnp.int32)}
    text = eng.decode.lower(eng.params, cache, batch).compile().as_text()
    leaf = jax.tree.leaves(cache)[0]

    def made(shape, ops):
        return re.findall(rf"= {re.escape('f32[%s]' % ','.join(map(str, shape)))}"
                          rf"\{{[^}}]*\}} ({ops})\(", text)
    assert not made(leaf.shape, "copy|broadcast")
    assert not made(leaf.shape[1:], "copy")
    n_params = len(jax.tree.leaves(eng.params))
    aliased = re.findall(r"\{\d+\}: \((\d+), \{\}", text)
    assert {int(a) for a in aliased} == set(
        range(n_params, n_params + len(jax.tree.leaves(cache))))
    _, _, out = eng.decode(eng.params, cache, batch)
    assert all(a.is_deleted() for a in jax.tree.leaves(cache))
    assert not any(a.is_deleted() for a in jax.tree.leaves(out))
    eng.close()


# ------------------------------------------------------------ KV blocks
def test_kv_prefix_reuse():
    mgr = KVBlockManager(n_blocks=64, block_tokens=4)
    conv = list(range(20))
    r1 = mgr.on_request(conv)
    assert r1["hit_tokens"] == 0
    assert len(r1["new_blocks"]) == 5
    # same conversation extended: full prefix reuse
    r2 = mgr.on_request(conv + [99, 98, 97, 96])
    assert r2["hit_tokens"] == 20
    assert len(r2["new_blocks"]) == 1


def test_kv_eviction_respects_radix_validity():
    mgr = KVBlockManager(n_blocks=8, block_tokens=4)
    mgr.on_request(list(range(16)))           # 4 blocks, chain
    mgr.on_request(list(range(100, 116)))     # 4 more -> full
    mgr.on_request(list(range(200, 216)))     # needs evictions
    # invariant: no block with live children was evicted
    for bid, b in mgr.blocks.items():
        for ch in b.children:
            assert ch in mgr.blocks
        if b.parent >= 0 and b.parent not in mgr.blocks:
            pytest.fail(f"orphan block {bid}: parent evicted first")


def test_kv_hot_prefix_survives():
    mgr = KVBlockManager(n_blocks=8, block_tokens=4)
    hot = list(range(8))                      # 2 blocks, reused often
    for _ in range(5):
        mgr.on_request(hot)
    root_key = tuple(hot[:4])
    hot_root = mgr.root_index[root_key]
    # flood with one-off conversations to force evictions
    for i in range(10):
        mgr.on_request(list(range(1000 + 16 * i, 1000 + 16 * i + 12)))
    assert mgr.root_index.get(root_key) == hot_root   # anchor retained
    r = mgr.on_request(hot)
    assert r["hit_tokens"] == 8
