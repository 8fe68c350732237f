"""The unified SemanticCache facade: protocol parity with the historical
simulator loop, numpy-vs-kernel backend equivalence, sharded-vs-numpy
decision parity across shard counts, payload/eviction hooks,
checkpoint/restore, and the no-inline-cache-logic guarantee for the
serving engine."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.cache import (CacheConfig, CacheHit, CacheMiss, KernelBackend,
                         NumpyBackend, SemanticCache, ShardedKernelBackend,
                         ShardedStore, get_backend)
from repro.core import (EmbeddingSpace, SynthConfig, default_factories,
                        run_policy, synthetic_trace)
from repro.core.store import ResidentStore


# ------------------------------------------------------------------ parity
def _seed_loop(trace, capacity, factory, hit_mode="content", tau_hit=0.85):
    """The pre-facade simulator protocol, verbatim — the parity oracle."""
    dim = trace.requests[0].emb.shape[0]
    store = ResidentStore(capacity, dim)
    policy = factory(capacity, store)
    hits = misses = evictions = 0
    for req in trace.requests:
        if hit_mode == "content":
            hit_cid = req.cid if req.cid in store else -1
        else:
            cid, sim = store.nearest(req.emb)
            hit_cid = cid if sim >= tau_hit else -1
        if hit_cid >= 0:
            hits += 1
            policy.on_hit(hit_cid, req, req.t)
        else:
            misses += 1
            if capacity <= 0:
                continue
            if hit_mode == "content" or req.cid not in store:
                store.insert(req.cid, req.emb)
                policy.on_admit(req.cid, req, req.t)
                while len(store) > capacity:
                    v = policy.victim(req.t)
                    store.remove(v)
                    evictions += 1
    return hits, misses, evictions


@pytest.fixture(scope="module")
def trace_10k():
    return synthetic_trace(SynthConfig(trace_len=10_000, seed=0)).with_next_use()


@pytest.mark.parametrize("name", ["RAC", "LRU", "S3-FIFO", "Belady"])
def test_run_policy_reproduces_seed_counts_content(trace_10k, name):
    facs = default_factories(include_belady=True)
    cap = int(0.1 * trace_10k.meta["unique"])
    ref = _seed_loop(trace_10k, cap, facs[name], hit_mode="content")
    s = run_policy(trace_10k, cap, facs[name], hit_mode="content", name=name)
    assert (s.hits, s.misses, s.evictions) == ref


@pytest.mark.parametrize("name", ["RAC", "LRU"])
def test_run_policy_reproduces_seed_counts_semantic(trace_10k, name):
    facs = default_factories(include_belady=True)
    cap = int(0.1 * trace_10k.meta["unique"])
    ref = _seed_loop(trace_10k, cap, facs[name], hit_mode="semantic")
    s = run_policy(trace_10k, cap, facs[name], hit_mode="semantic",
                   name=name)
    assert (s.hits, s.misses, s.evictions) == ref


# ------------------------------------------------------- backend equivalence
def _filled_cache(backend, n=40, capacity=50, dim=64, policy="LRU"):
    space = EmbeddingSpace(dim=dim, seed=5)
    cache = SemanticCache(CacheConfig(capacity=capacity, dim=dim,
                                      backend=backend, policy=policy))
    embs = [space.content_embedding(i % 8, i).astype(np.float32)
            for i in range(n)]
    for i, e in enumerate(embs):
        cache.admit(i, e, payload=[i])
    return cache, space, embs


def test_lookup_batch_kernel_matches_numpy():
    cn, space, embs = _filled_cache("numpy")
    ck, _, _ = _filled_cache("kernel")
    queries = np.stack(
        [space.paraphrase(embs[i], i % 8, i, 1).astype(np.float32)
         for i in range(len(embs))]
        + [space.content_embedding(9, 1000 + j).astype(np.float32)
           for j in range(8)])
    n_cids, n_sims = cn.peek_batch(queries)
    k_cids, k_sims = ck.peek_batch(queries)
    np.testing.assert_array_equal(n_cids, k_cids)
    np.testing.assert_allclose(n_sims, k_sims, atol=1e-5)
    rn = cn.lookup_batch(queries, cids=list(range(len(queries))))
    rk = ck.lookup_batch(queries, cids=list(range(len(queries))))
    assert [r.hit for r in rn] == [r.hit for r in rk]
    assert [r.cid if r.hit else -1 for r in rn] == \
           [r.cid if r.hit else -1 for r in rk]
    assert sum(r.hit for r in rn) == len(embs)      # paraphrases all hit
    assert cn.metrics.hits == ck.metrics.hits


def test_lookup_batch_matches_sequential_lookups():
    cn, space, embs = _filled_cache("numpy")
    cs, _, _ = _filled_cache("numpy")
    queries = np.stack(
        [space.paraphrase(embs[i], i % 8, i, 1).astype(np.float32)
         for i in range(10)])
    batched = cn.lookup_batch(queries)
    single = [cs.lookup(q) for q in queries]
    for b, s in zip(batched, single):
        assert b.hit == s.hit and b.cid == s.cid
        np.testing.assert_allclose(b.sim, s.sim, atol=1e-6)


def test_kernel_rac_value_matches_numpy():
    rng = np.random.default_rng(0)
    nb, kb = NumpyBackend(), KernelBackend()
    tsi = rng.random(100)
    tids = rng.integers(0, 16, 100)
    tp_last = rng.random(16) * 5
    t_last = rng.integers(0, 500, 16)
    a = nb.rac_value(tsi, tids, tp_last, t_last, 0.001, 700)
    b = kb.rac_value(tsi, tids, tp_last, t_last, 0.001, 700)
    np.testing.assert_allclose(a, b, rtol=1e-5)


def test_get_backend_rejects_unknown():
    with pytest.raises(ValueError):
        get_backend("cuda")


# ------------------------------------------------------ facade semantics
def test_lookup_never_admits_and_admit_evicts():
    space = EmbeddingSpace(dim=32, seed=1)
    cache = SemanticCache(CacheConfig(capacity=2, dim=32, policy="FIFO"))
    e = [space.content_embedding(0, i).astype(np.float32) for i in range(3)]
    assert isinstance(cache.lookup(e[0], cid=0), CacheMiss)
    assert len(cache) == 0                      # miss did not admit
    cache.admit(0, e[0], payload="r0")
    cache.admit(1, e[1], payload="r1")
    evicted = cache.admit(2, e[2], payload="r2")
    assert evicted == [0] and len(cache) == 2   # FIFO over capacity 2
    assert 0 not in cache.payloads              # payload died with entry
    r = cache.lookup(e[1], cid=1)
    assert isinstance(r, CacheHit) and r.payload == "r1"


def test_event_hooks_fire():
    space = EmbeddingSpace(dim=32, seed=2)
    cache = SemanticCache(CacheConfig(capacity=1, dim=32, policy="LRU"))
    seen = []
    for kind in ("hit", "miss", "admit", "evict"):
        cache.subscribe(kind, lambda ev, k=kind: seen.append((k, ev.cid)))
    e0 = space.content_embedding(0, 0).astype(np.float32)
    e1 = space.content_embedding(1, 1).astype(np.float32)
    cache.lookup(e0, cid=0)                     # miss
    cache.admit(0, e0, payload="x")             # admit
    cache.lookup(e0, cid=0)                     # hit
    cache.admit(1, e1)                          # admit + evict 0
    kinds = [k for k, _ in seen]
    assert kinds == ["miss", "admit", "hit", "admit", "evict"]
    assert seen[-1] == ("evict", 0)
    m = cache.metrics
    assert (m.hits, m.misses, m.admissions, m.evictions) == (1, 1, 2, 1)


def test_checkpoint_restore_roundtrip():
    cache, space, embs = _filled_cache("numpy", n=30, capacity=32)
    cache.lookup(embs[3], cid=3)
    snap = cache.checkpoint()
    before = (cache.metrics.hits, cache.metrics.evictions, len(cache.store),
              sorted(cache.store.keys()), dict(cache.payloads))
    for j in range(50):                          # churn everything
        cache.admit(2000 + j,
                    space.content_embedding(11, 2000 + j).astype(np.float32))
    assert sorted(cache.store.keys()) != before[3]
    cache.restore(snap)
    after = (cache.metrics.hits, cache.metrics.evictions, len(cache.store),
             sorted(cache.store.keys()), dict(cache.payloads))
    assert after == before
    # restored cache still behaves: resident entry hits again
    assert cache.lookup(embs[3], cid=3).hit


def test_content_mode_lookup_batch():
    cache = SemanticCache(CacheConfig(capacity=8, dim=16, policy="LRU",
                                      hit_mode="content"))
    rng = np.random.default_rng(0)
    embs = rng.standard_normal((4, 16)).astype(np.float32)
    cache.admit_batch([0, 1], embs[:2])
    rs = cache.lookup_batch(embs, cids=[0, 1, 2, 3])
    assert [r.hit for r in rs] == [True, True, False, False]


# ------------------------------------------ exact lookups on the store mirror
def _answers(results):
    return [(r.cid, r.sim) if r.hit else (r.best_cid, r.best_sim)
            for r in results]


def _lookup_both(ck, cn, queries):
    """One ``lookup_batch`` on the kernel and the host facade.  Decisions
    match the host backend's; the kernel's (cid, sim) are bit-equal to the
    same scan of a freshly uploaded slab, so the mirror is never stale."""
    fresh = KernelBackend(use_pallas=False).top1_batch(ck.store, queries)
    got, want = (_answers(c.lookup_batch(queries)) for c in (ck, cn))
    assert [c for c, _ in got] == [c for c, _ in want]
    np.testing.assert_allclose([s for _, s in got], [s for _, s in want],
                               atol=1e-6)
    assert got == list(zip(fresh[0].tolist(), fresh[1].tolist()))
    return [c for c, _ in got]


@pytest.mark.parametrize("case", ["evict", "churn", "foreign", "arena",
                                  "restore"])
def test_exact_lookup_scans_the_fresh_mirror(case):
    """The kernel backend's exact lookup scans its journal-synced store
    mirror, interleaved with admissions that evict: a dirty-row scatter per
    lookup after one full sync, a full sync after churn past
    ``small_delta``, and fresh answers when a store of another lineage (a
    diverged copy, an arena view) or a restored snapshot passes through
    the same backend."""
    import copy

    from repro.cache.backends import small_delta
    from repro.core.arena import ArenaStore
    dim, cap = 32, 24 if case != "churn" else 100
    space = EmbeddingSpace(dim=dim, seed=7)
    kb = KernelBackend(use_pallas=False)
    ck, cn = (SemanticCache(CacheConfig(capacity=cap, dim=dim, backend=b,
                                        policy="LRU", tau_hit=0.85))
              for b in (kb, "numpy"))
    emb = [space.content_embedding(i % 9, i).astype(np.float32)
           for i in range(3 * cap)]

    def admit(i):
        out = [c.admit(i, emb[i]) for c in (ck, cn)]
        assert out[0] == out[1]
        return out[0]

    def queries(ids):
        return np.stack([space.paraphrase(emb[i], i % 9, i, 1)
                         .astype(np.float32) for i in ids] + [emb[0]])

    for i in range(cap):
        admit(i)
    _lookup_both(ck, cn, queries(range(0, cap, 3)))
    s0 = dict(kb.sync_stats)
    assert s0["full"] == 1                  # the first scan fills the mirror

    if case == "evict":
        for i in range(cap, cap + 8):
            (gone,) = admit(i)              # frees the victim's slot
            hits = _lookup_both(ck, cn, queries([gone, i, i - 1]))
            assert gone not in hits
        s1 = kb.sync_stats
        assert s1["full"] == s0["full"]
        assert s1["incremental"] == s0["incremental"] + 8
    elif case == "churn":
        v = ck.store.version
        for i in range(cap, 2 * cap):
            admit(i)
        assert not small_delta(len(ck.store.dirty_since(v)),
                               ck.store.emb.shape[0])
        _lookup_both(ck, cn, queries(range(cap, 2 * cap, 5)))
        assert kb.sync_stats["full"] == s0["full"] + 1
        assert kb.sync_stats["incremental"] == s0["incremental"]
    elif case in ("foreign", "arena"):
        if case == "foreign":               # same history, then diverges
            other = copy.deepcopy(ck.store)
            other.remove(next(iter(other.slot_of)))
            other.insert(10_000, emb[-1])
        else:
            arena = ArenaStore(2, cap, dim, track_rows=True)
            for i in range(cap):
                arena.views[i % 2].insert(i, emb[-1 - i])
            other = arena.views[1]
        q = queries([0, 3 * cap - 1, 3 * cap - 2])
        for i in range(cap, cap + 3):       # alternate the two lineages
            want = NumpyBackend().top1_batch(other, q)
            got = kb.top1_batch(other, q)
            np.testing.assert_array_equal(got[0], want[0])
            np.testing.assert_allclose(got[1], want[1], atol=1e-6)
            _lookup_both(ck, cn, q)         # never answered from `other`
            admit(i)
            other.remove(next(iter(other.slot_of)))
            other.insert(20_000 + i, emb[i])
    else:
        snaps = [c.checkpoint() for c in (ck, cn)]
        for i in range(cap, cap + 4):
            admit(i)
        _lookup_both(ck, cn, queries(range(cap, cap + 4)))
        for c, snap in zip((ck, cn), snaps):
            c.restore(snap)                 # back to rows the mirror left
        _lookup_both(ck, cn, queries(range(cap, cap + 4)))
        s1 = dict(kb.sync_stats)
        admit(cap + 5)                      # the mirror follows the restored
        _lookup_both(ck, cn, queries([cap + 5]))      # store from now on
        assert kb.sync_stats["full"] == s1["full"]
        assert kb.sync_stats["incremental"] == s1["incremental"] + 1


# ------------------------------------------------------ sharded store parity
def _replay_decisions(trace, capacity, backend, n_requests=2000, **bkw):
    """Replay a trace slice through the facade, recording every decision
    (hit cids, admissions, eviction victims) via the event hooks."""
    dim = trace.requests[0].emb.shape[0]
    cache = SemanticCache(CacheConfig(capacity=capacity, dim=dim,
                                      backend=backend, policy="RAC",
                                      use_pallas=False, backend_kwargs=bkw))
    events = []
    for kind in ("hit", "miss", "admit", "evict"):
        cache.subscribe(kind, lambda ev, k=kind: events.append((k, ev.cid)))
    for req in trace.requests[:n_requests]:
        r = cache.lookup(req.emb, cid=req.cid, t=req.t, req=req)
        if not r.hit:
            cache.admit(req.cid, req.emb, t=req.t, req=req)
    return events, cache


@pytest.fixture(scope="module")
def numpy_decisions(trace_10k):
    cap = int(0.1 * trace_10k.meta["unique"])
    return _replay_decisions(trace_10k, cap, "numpy")


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_sharded_matches_numpy_decisions(trace_10k, numpy_decisions, n_shards):
    """The acceptance criterion: identical hit/miss cids, admissions, and
    eviction victims across shard counts — RAC policy, semantic mode."""
    cap = int(0.1 * trace_10k.meta["unique"])
    ev_n, cache_n = numpy_decisions
    ev_s, cache_s = _replay_decisions(trace_10k, cap, "sharded",
                                      n_shards=n_shards)
    assert ev_s == ev_n
    assert isinstance(cache_s.store, ShardedStore)
    assert cache_s.store.n_shards == n_shards
    m_n, m_s = cache_n.metrics, cache_s.metrics
    assert (m_s.hits, m_s.misses, m_s.evictions) == \
           (m_n.hits, m_n.misses, m_n.evictions)
    # row partitioning really happened: per-shard load counters agree with
    # an exact recount of where every resident slot actually lives (strict
    # balance is NOT an invariant — evictions pick victims by value, not
    # by shard — so only the bookkeeping is asserted)
    store = cache_s.store
    recount = np.bincount([s // store.rows_per_shard
                           for s in store.slot_of.values()],
                          minlength=store.n_shards)
    np.testing.assert_array_equal(store.load, recount)
    assert store.load.sum() == len(store)


def test_sharded_lookup_batch_matches_numpy_pallas():
    """Small-batch parity with the Pallas kernel path active per shard."""
    cn, space, embs = _filled_cache("numpy")
    cs = SemanticCache(CacheConfig(capacity=50, dim=64, backend="sharded",
                                   policy="LRU",
                                   backend_kwargs={"n_shards": 4}))
    for i, e in enumerate(embs):
        cs.admit(i, e, payload=[i])
    queries = np.stack(
        [space.paraphrase(embs[i], i % 8, i, 1).astype(np.float32)
         for i in range(len(embs))]
        + [space.content_embedding(9, 1000 + j).astype(np.float32)
           for j in range(8)])
    n_cids, n_sims = cn.peek_batch(queries)
    s_cids, s_sims = cs.peek_batch(queries)
    np.testing.assert_array_equal(n_cids, s_cids)
    np.testing.assert_allclose(n_sims, s_sims, atol=1e-5)


def test_sharded_empty_and_all_slots_free():
    """Lookups against an empty sharded cache (all slots free) miss with
    best_cid -1; a store with occupied and empty shards still resolves."""
    space = EmbeddingSpace(dim=32, seed=3)
    cache = SemanticCache(CacheConfig(capacity=6, dim=32, policy="LRU",
                                      backend="sharded", use_pallas=False,
                                      backend_kwargs={"n_shards": 4}))
    e = [space.content_embedding(0, i).astype(np.float32) for i in range(3)]
    r = cache.lookup(e[0], cid=0)
    assert isinstance(r, CacheMiss) and r.best_cid == -1
    cache.admit(0, e[0])                        # 3 of 4 shards stay empty
    assert (cache.store.load > 0).sum() == 1
    assert cache.lookup(e[0], cid=0).hit
    r = cache.lookup(e[1], cid=1)
    assert not r.hit and r.best_cid == 0        # nearest resident reported


@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_capacity_boundary(n_shards):
    """Exactly capacity admissions → no eviction; one more → exactly one,
    with the same victim the numpy backend elects."""
    rng = np.random.default_rng(4)
    cap, dim = 8, 32
    embs = rng.standard_normal((cap + 1, dim)).astype(np.float32)
    embs /= np.linalg.norm(embs, axis=1, keepdims=True)

    def fill(backend, **bkw):
        c = SemanticCache(CacheConfig(capacity=cap, dim=dim, policy="RAC",
                                      backend=backend, use_pallas=False,
                                      backend_kwargs=bkw))
        evicted = []
        for i in range(cap):
            evicted += c.admit(i, embs[i])
        assert evicted == [] and len(c) == cap
        evicted = c.admit(cap, embs[cap])
        assert len(evicted) == 1 and len(c) == cap
        return evicted

    assert fill("sharded", n_shards=n_shards) == fill("numpy")


def test_sharded_checkpoint_restore_roundtrip():
    """All sharded state (slab, free lists, loads, hwm) survives the
    facade's checkpoint/restore with no backend cooperation."""
    space = EmbeddingSpace(dim=64, seed=5)
    cache = SemanticCache(CacheConfig(capacity=32, dim=64, policy="LRU",
                                      backend="sharded", use_pallas=False,
                                      backend_kwargs={"n_shards": 4}))
    embs = [space.content_embedding(i % 8, i).astype(np.float32)
            for i in range(30)]
    for i, e in enumerate(embs):
        cache.admit(i, e, payload=[i])
    cache.lookup(embs[3], cid=3)
    snap = cache.checkpoint()
    before = (sorted(cache.store.keys()), cache.store.load.tolist(),
              cache.store.local_hwm.tolist(), cache.metrics.hits)
    for j in range(50):
        cache.admit(2000 + j,
                    space.content_embedding(11, 2000 + j).astype(np.float32))
    assert sorted(cache.store.keys()) != before[0]
    cache.restore(snap)
    after = (sorted(cache.store.keys()), cache.store.load.tolist(),
             cache.store.local_hwm.tolist(), cache.metrics.hits)
    assert after == before
    assert cache.lookup(embs[3], cid=3).hit


@pytest.mark.slow_mesh
def test_sharded_shard_map_path_in_subprocess():
    """With enough devices the mesh path (shard_map + all_gather argmax
    merge) is exercised end-to-end and agrees with the numpy backend."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = """
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4").strip()
import numpy as np
from repro.cache import NumpyBackend, ShardedKernelBackend, ShardedStore
rng = np.random.default_rng(1)
store = ShardedStore(300, 64, n_shards=4)
embs = rng.standard_normal((200, 64)).astype(np.float32)
embs /= np.linalg.norm(embs, axis=1, keepdims=True)
for i in range(200):
    store.insert(i, embs[i])
store.remove(7); store.remove(90)
q = rng.standard_normal((64, 64)).astype(np.float32)
q /= np.linalg.norm(q, axis=1, keepdims=True)
sb = ShardedKernelBackend(n_shards=4, use_pallas=False)
assert sb.mesh() is not None, "mesh must be active with 4 devices"
nc, ns = NumpyBackend().top1_batch(store, q)
sc, ss = sb.top1_batch(store, q)
np.testing.assert_array_equal(nc, sc)
np.testing.assert_allclose(ns, ss, atol=1e-5)
print("OK")
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_get_backend_kwargs_uniform():
    """kwargs pass through to every backend; unexpected ones raise instead
    of being silently dropped."""
    b = get_backend("sharded", n_shards=2, use_pallas=False)
    assert isinstance(b, ShardedKernelBackend) and b.n_shards == 2
    with pytest.raises(TypeError):
        get_backend("numpy", use_pallas=True)
    with pytest.raises(TypeError):
        get_backend("kernel", n_shards=2)
    with pytest.raises(ValueError):
        get_backend(NumpyBackend(), use_pallas=True)
    assert isinstance(get_backend(NumpyBackend()), NumpyBackend)


# ----------------------------------------------------------- engine facade
def test_engine_has_no_inline_cache_logic():
    """The acceptance criterion: ServingEngine owns no cache protocol —
    lookup/admit/evict live behind SemanticCache only."""
    import inspect

    from repro.serving.engine import ServingEngine
    assert not hasattr(ServingEngine, "_lookup")
    assert not hasattr(ServingEngine, "_admit")
    src = inspect.getsource(ServingEngine)
    assert "ResidentStore(" not in src and "RACPolicy(" not in src
    # batched hot path: the whole queue is scored in one fused facade
    # launch, and rescans stay row-restricted through the backend
    assert "decide_batch" in src
    assert "peek_rows" in src
