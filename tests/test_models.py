"""Per-architecture smoke tests (reduced same-family configs) + decode/
forward parity (KV-cache correctness) + one train step (finite loss/grads).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import (build_model, make_train_step, smoke_variant)
from repro.optim import AdamWConfig, adamw_init

RNG = jax.random.PRNGKey(0)


def _batch(cfg, b=2, s=16):
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(2, cfg.vocab_size, (b, s)),
        jnp.int32)}
    if cfg.frontend == "audio":
        batch["audio_embeds"] = 0.1 * jnp.ones(
            (b, cfg.n_frontend_tokens, cfg.d_model), cfg.cdtype)
    if cfg.frontend == "vision":
        batch["image_embeds"] = 0.1 * jnp.ones(
            (b, cfg.n_frontend_tokens, cfg.d_model), cfg.cdtype)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = smoke_variant(get_config(arch))
    model = build_model(cfg)
    params = model.init(RNG)
    batch = _batch(cfg)
    logits = model.forward(params, batch)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step_finite(arch):
    cfg = smoke_variant(get_config(arch))
    model = build_model(cfg)
    params = model.init(RNG)
    batch = _batch(cfg)
    batch["labels"] = batch["tokens"]
    step = make_train_step(model, AdamWConfig(lr=1e-3))
    params2, opt2, metrics = step(params, adamw_init(params), batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually changed
    d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), params, params2)
    assert max(jax.tree.leaves(d)) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch):
    """Teacher-forcing parity: decoding token-by-token through the cache
    must reproduce the full-sequence forward logits (validates every cache
    layout: linear KV, MLA compressed, ring window, SSM/xLSTM states)."""
    cfg = smoke_variant(get_config(arch))
    model = build_model(cfg)
    params = model.init(RNG)
    b, s = 2, 12
    batch = _batch(cfg, b, s)
    if cfg.family == "vlm":
        # stand in the token embeddings as "image" embeds so the decode
        # stream (tokens only) is information-identical to the forward
        from repro.models.model import embed
        batch["image_embeds"] = embed(params["emb"], cfg,
                                      batch["tokens"][:, :cfg.n_frontend_tokens])
    full = model.forward(params, batch)      # (B,S,V)

    cache = model.init_cache(b, s + 4)
    errs = []
    for pos in range(s):
        dbatch = {"tokens": batch["tokens"][:, pos:pos + 1],
                  "pos": jnp.full((b,), pos, jnp.int32)}
        if cfg.frontend == "audio":
            # decode consumes the cached encoder output
            enc = model._encode(params, batch["audio_embeds"])
            dbatch["enc_out"] = enc
        logits, cache = model.decode_step(params, cache, dbatch)
        errs.append(float(jnp.abs(
            logits - full[:, pos]).max()))
    tail = errs
    assert max(tail) < (2e-1 if cfg.family in ("ssm", "hybrid") else 5e-2), \
        f"decode/forward divergence {max(tail)} (per-pos {tail})"


# slots of the engine's batch: each runs its own prompts back to back, so
# after the first restart the slots sit at different positions
SEGMENTS = [(12,), (5, 7), (3, 9)]


@pytest.mark.parametrize("arch,scan", [
    ("smollm_360m", True), ("smollm_360m", False),
    ("deepseek_v2_lite_16b", True), ("deepseek_v2_lite_16b", False),
    ("hymba_15b", True), ("xlstm_125m", True), ("xlstm_125m", False)])
def test_engine_decode_matches_forward(arch, scan):
    """The serving engine's decode jit, which donates the cache, stepped
    with its slots at different positions, reproduces each slot's
    teacher-forced forward logits; each call consumes the cache it is
    handed.  A slot whose cache holds a recurrent state carries it across
    prompts, so those slots run one prompt each (still a whole-state write
    per layer).  DeepSeek-V2 (a dense layer, then MoE) is held to the plain
    float32 reference's forward instead of the program's own."""
    from repro.serving import EngineConfig, ServingEngine
    mla = arch.startswith("deepseek")
    cfg = dataclasses.replace(_dsv2_small() if mla
                              else smoke_variant(get_config(arch)),
                              scan_layers=scan)
    model = build_model(cfg)
    params = model.init(RNG)
    if mla:
        from repro.models import deepseek_v2_ref as ref
        forward = lambda t: ref.forward(params, t, _hf(cfg))
    else:
        forward = lambda t: model.forward(params, {"tokens": t})
    eng = ServingEngine(cfg, EngineConfig(max_batch=len(SEGMENTS),
                                          max_seq=16), params=params)
    recurrent = cfg.family in ("ssm", "hybrid")
    segs = [(sum(s),) if recurrent else s for s in SEGMENTS]
    rng = np.random.default_rng(1)
    toks = [[rng.integers(2, cfg.vocab_size, n) for n in s] for s in segs]
    full = [[forward(jnp.asarray(t[None]))[0] for t in row] for row in toks]
    # (segment, position) of every slot at every step
    sched = [[(i, p) for i, n in enumerate(s) for p in range(n)]
             for s in segs]
    cache = model.init_cache(len(segs), 16)
    errs = []
    for step in range(len(sched[0])):
        at = [row[step] for row in sched]
        batch = {"tokens": jnp.asarray([[toks[b][i][p]]
                                        for b, (i, p) in enumerate(at)],
                                       jnp.int32),
                 "pos": jnp.asarray([p for _, p in at], jnp.int32)}
        given = cache
        _, logits, cache = eng.decode(params, given, batch)
        assert all(a.is_deleted() for a in jax.tree.leaves(given))
        errs += [float(jnp.abs(logits[b] - full[b][i][p]).max())
                 for b, (i, p) in enumerate(at)]
    assert max(errs) < (2e-1 if recurrent else 5e-2), max(errs)
    if mla:
        assert max(errs) < 1e-4, max(errs)
    eng.close()


def test_moe_routing_conserves_tokens():
    """Dropless dispatch: with renormalized top-k weights every token's
    routed output is its experts' outputs weighted to a sum of 1, whatever
    the batch; zero input gives zero output (no biases)."""
    from repro.models.layers import init_moe, moe_apply, moe_route
    cfg = dataclasses.replace(_dsv2_small(), norm_topk_prob=True,
                              n_shared_experts=0)
    p = init_moe(cfg, RNG)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          cfg.cdtype)
    y, counts = moe_apply(p, cfg, x)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())
    w, _ = moe_route(p, cfg, x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    assert int(counts[0]) == 16 * cfg.top_k
    y0, _ = moe_apply(p, cfg, jnp.zeros_like(x))
    np.testing.assert_allclose(np.asarray(y0, np.float32), 0.0, atol=1e-5)


# ------------------------------------------------- DeepSeek-V2 vs reference
def _dsv2_small(**kw):
    """DeepSeek-V2-shaped at a small size: d 128, 4 heads, kv_lora_rank
    64, rope 16, 8 experts top-2 beside 2 shared, 1 dense + 2 MoE layers,
    float32, with the published YaRN and routing options."""
    small = dict(n_layers=3, d_model=128, n_heads=4, n_kv_heads=4,
                 head_dim=32, d_ff=256, vocab_size=512, kv_lora_rank=64,
                 rope_head_dim=16, n_experts=8, top_k=2, expert_d_ff=64,
                 param_dtype="float32", compute_dtype="float32",
                 scan_layers=True, remat=False)
    return dataclasses.replace(get_config("deepseek_v2_lite_16b"),
                               **{**small, **kw})


def _hf(cfg) -> dict:
    """The HF ``config.json`` of a DeepSeek-V2 ModelConfig, for the
    reference (vocabulary at the program's padded size)."""
    y = cfg.yarn
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.hd,
            "qk_rope_head_dim": cfg.rope_head_dim, "v_head_dim": cfg.hd,
            "intermediate_size": cfg.d_ff,
            "moe_intermediate_size": cfg.expert_d_ff,
            "n_routed_experts": cfg.n_experts,
            "num_experts_per_tok": cfg.top_k,
            "n_shared_experts": cfg.n_shared_experts,
            "first_k_dense_replace": cfg.n_dense_layers,
            "num_hidden_layers": cfg.n_layers,
            "vocab_size": cfg.padded_vocab, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": 1.0,
            "rope_scaling": None if y is None else {
                "type": "yarn", "factor": y.factor,
                "original_max_position_embeddings": y.original_max_pos,
                "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim}}


DSV2_CASES = {"published": {},
              "renormalized": {"norm_topk_prob": True},
              "plain rope": {"yarn": None}}


@pytest.mark.parametrize("case", DSV2_CASES)
def test_dsv2_forward_matches_reference(case):
    from repro.models import deepseek_v2_ref as ref
    cfg = _dsv2_small(**DSV2_CASES[case])
    model = build_model(cfg)
    params = model.init(RNG)
    tokens = _batch(cfg, 2, 16)["tokens"]
    got = model.forward(params, {"tokens": tokens})
    want = ref.forward(params, tokens, _hf(cfg))
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_dsv2_dropless_under_a_skewed_router():
    """Every token routed to one expert (the router's column 0 aligned with
    a direction all inputs share): nothing is dropped, the routed output
    equals the reference's dense-mask sum, and the counters see the one
    expert given every row."""
    from repro.models import deepseek_v2_ref as ref
    from repro.models.layers import init_moe, moe_apply
    cfg = _dsv2_small()
    p = init_moe(cfg, RNG)
    u = jnp.ones((cfg.d_model,)) / cfg.d_model ** 0.5
    p["router"] = p["router"].at[:, 0].set(50.0 * u)
    x = (jax.random.normal(jax.random.PRNGKey(2), (4, 16, cfg.d_model))
         + 8.0 * u)
    y, counts = moe_apply(p, cfg, x)
    z = ref.dims(_hf(cfg))
    with jax.default_matmul_precision("highest"):
        w, topi = ref.route(x.reshape(-1, cfg.d_model), p["router"], z)
        want, _ = ref._moe(x, p, z, False)
    assert bool((topi == 0).any(-1).all())
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    rows = 4 * 16
    pairs, _, busiest, layers = np.asarray(counts).tolist()
    assert (pairs, busiest, layers) == (rows * cfg.top_k, rows, 1)


def test_dsv2_yarn_at_the_published_numbers():
    """YaRN's frequencies (factor 40, 4,096 positions, betas 32 and 1:
    HF's correction range (10, 23) over 32 rope pairs) and the softmax
    scale 1/sqrt(192) x mscale^2, mscale = 0.1 x 0.707 x ln 40 + 1."""
    from repro.models import deepseek_v2_ref as ref
    from repro.models.layers import yarn_inv_freq
    cfg = get_config("deepseek_v2_lite_16b")
    inv = np.asarray(yarn_inv_freq(64, 10_000.0, cfg.yarn))
    i = np.arange(32)
    extra = 10_000.0 ** (-2 * i / 64)
    keep = 1 - np.clip((i - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, extra * keep + extra / 40 * (1 - keep),
                               rtol=1e-6)
    z = ref.dims(_hf(cfg))
    np.testing.assert_allclose(ref.inv_freq_and_mscale(z)[0], inv,
                               rtol=1e-6)
    assert cfg.yarn.get_mscale(0.707) == pytest.approx(1.26080, abs=1e-5)
    assert cfg.yarn.cos_sin_scale == 1.0
    scale = 192 ** -0.5 * cfg.yarn.softmax_mscale
    assert scale == pytest.approx(0.114722, abs=1e-6)
    assert ref.softmax_scale(z) == pytest.approx(scale, rel=1e-12)


def test_dsv2_lite_parameter_count():
    """The published model, norms included: 15.7B."""
    assert get_config("deepseek_v2_lite_16b").n_params() == 15_706_484_224


def test_dsv2_moe_counters_match_a_host_count(monkeypatch):
    """The decode cache's MoE counters, summed over layers and steps,
    against a host count of the routing each MoE layer did (recorded
    eagerly); a dense model's cache carries none."""
    from repro.models import layers as L
    cfg = _dsv2_small(scan_layers=False)
    model = build_model(cfg)
    params = model.init(RNG)
    seen = []
    route = L.moe_route
    monkeypatch.setattr(L, "moe_route", lambda p, c, xt: seen.append(
        np.asarray(route(p, c, xt)[1])) or route(p, c, xt))
    b = 6
    cache = model.init_cache(b, 16)
    toks = np.random.default_rng(3).integers(2, cfg.vocab_size, (b, 5))
    for pos in range(5):
        _, cache = model.decode_step(params, cache, {
            "tokens": jnp.asarray(toks[:, pos:pos + 1], jnp.int32),
            "pos": jnp.full((b,), pos, jnp.int32)})
    assert len(seen) == 5 * (cfg.n_layers - cfg.n_dense_layers)
    sizes = [np.bincount(t.ravel(), minlength=cfg.n_experts) for t in seen]
    host = [sum(int(s.sum()) for s in sizes),
            sum(int((s > 0).sum()) for s in sizes),
            sum(int(s.max()) for s in sizes), len(sizes)]
    assert np.asarray(cache["moe_stats"]).tolist() == host
    dense = build_model(smoke_variant(get_config("smollm_360m")))
    assert "moe_stats" not in dense.init_cache(2, 8)


def test_sliding_window_masks_distant_tokens():
    from repro.models import layers as L
    b, h, s, d = 1, 2, 64, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d))
    out_w = L.sdpa(q, k, v, causal=True, window=8)
    # perturb a token far outside every later query's window
    k2 = k.at[:, 0].add(10.0)
    v2 = v.at[:, 0].add(10.0)
    out_w2 = L.sdpa(q, k2, v2, causal=True, window=8)
    np.testing.assert_allclose(np.asarray(out_w[:, 16:]),
                               np.asarray(out_w2[:, 16:]), atol=1e-5)


def test_scan_equals_unrolled_stack():
    cfg = smoke_variant(get_config("gemma-7b"))
    cfg_scan = dataclasses.replace(cfg, scan_layers=True)
    model_u = build_model(cfg)
    model_s = build_model(cfg_scan)
    params_u = model_u.init(RNG)
    # restack the unrolled params for the scanned model
    import jax.tree_util as jtu
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params_u["blocks"])
    params_s = {"emb": params_u["emb"], "blocks": stacked}
    batch = _batch(cfg)
    lu = model_u.forward(params_u, batch)
    ls = model_s.forward(params_s, batch)
    np.testing.assert_allclose(np.asarray(lu, np.float32),
                               np.asarray(ls, np.float32), atol=2e-4)
