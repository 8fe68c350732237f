"""Device-resident fused decision pipeline (``kernels/fused.py``).

Contracts under test:

- **Bit-parity within a backend**: the fused single-launch path must
  reproduce the staged multi-launch driver's (hit, cid, sim) event
  stream bit-for-bit — same kernel engine, same tie contract, same
  safety predicates — across semantic/content modes and the
  pruned/quantized/composed configs.
- **Decision parity across backends**: hit/miss + cid sequences match
  the numpy host oracle (sims may differ in the last ulp between the
  pallas gemm and host BLAS — a pre-existing exact-path property, so
  cross-backend assertions are decisions-only).
- **Compile stability**: steady-state replay reuses one executable per
  fused entry point; store growth only recompiles at static shape
  bucket boundaries.
- **Probe-cap accounting**: the adaptive scan budget truncates probes
  identically on the staged and fused paths and lands in the ``capped``
  ledger, with decisions still exact.
- **Dispatch ledger**: ``metrics_snapshot()['dispatch']`` is always
  present; kernel backends tick launches/host_syncs, host backends
  report zeros.
"""
import numpy as np
import pytest

from repro.cache import CacheConfig, SemanticCache
from repro.cache.pruned import PrunedLookupConfig
from repro.cache.quantized import QuantizedLookupConfig
from repro.kernels import fused


def _workload(n=240, dim=32, n_proto=48, jitter=0.05, seed=7):
    rng = np.random.default_rng(seed)
    protos = rng.standard_normal((n_proto, dim)).astype(np.float32)
    protos /= np.linalg.norm(protos, axis=1, keepdims=True)
    reqs = []
    for i in range(n):
        j = int(rng.integers(0, n_proto))
        p = protos[j] + jitter * rng.standard_normal(dim).astype(np.float32)
        p /= np.linalg.norm(p)
        reqs.append((j, p.astype(np.float32)))
    return reqs


def _events(backend, pruned, quant, *, mode="semantic", tau=0.80,
            capacity=40, use_pallas=False, reqs=None, **bk):
    cfg = CacheConfig(capacity=capacity, dim=32, tau_hit=tau,
                      hit_mode=mode, backend=backend,
                      pruned_lookup=pruned, quantized_lookup=quant,
                      use_pallas=use_pallas, backend_kwargs=bk)
    cache = SemanticCache(cfg)
    ev = []
    for cid, emb in (reqs or _workload()):
        r = cache.lookup(emb)
        ev.append((r.hit, getattr(r, "cid", -1),
                   float(getattr(r, "sim", float("-inf")))))
        if not r.hit:
            cache.admit(cid, emb)
    return ev, cache


def _decisions(ev):
    return [(h, c) for h, c, _ in ev]


# ------------------------------------------------------- config plumbing
def test_fused_is_the_default():
    assert PrunedLookupConfig().fused is True
    assert QuantizedLookupConfig().fused is True
    assert set(fused.fused_stats) == {"calls", "fallback_rows",
                                      "capped_rows"}
    assert set(fused.compile_counts()) == {"pruned", "quant"}


def test_shape_buckets():
    assert fused.pad_pow2(1, 8) == 8
    assert fused.pad_pow2(9, 8) == 16
    assert fused.pad_geo(1) == 64
    assert fused.pad_geo(65) == 96          # pow2 + 1.5x midpoints
    assert fused.pad_geo(97) == 128
    # tau_lo is the largest f32 strictly below tau: device `v <= tau_lo`
    # must decide exactly like host f64 `v < tau`
    tau = 0.85
    lo = float(fused.tau_lo_f32(tau))
    assert lo < tau
    assert np.nextafter(np.float32(lo), np.float32(np.inf)) >= \
        np.float32(tau)


# --------------------------------------------------- bit-parity contracts
@pytest.mark.parametrize("mode", ["semantic", "content"])
@pytest.mark.parametrize("pruned,quant", [
    (True, False), (False, True), (True, True)])
def test_fused_matches_staged_bit_for_bit(mode, pruned, quant):
    """Same backend, fused vs staged: the full (hit, cid, sim) stream is
    bit-equal — the fused union rescore runs the same kernel engine over
    the same candidate rows with the same lowest-slot tie contract."""
    ev_f, cache = _events("kernel", pruned and {"fused": True},
                          quant and {"fused": True}, mode=mode)
    ev_s, _ = _events("kernel", pruned and {"fused": False},
                      quant and {"fused": False}, mode=mode)
    assert ev_f == ev_s
    if mode == "semantic":
        # the fused path actually ran (its ledgers moved)
        snap = cache.metrics_snapshot()
        ledger = snap["prune"] if pruned else snap["quant"]
        assert ledger["scans"] > 0


@pytest.mark.parametrize("pruned,quant", [
    (True, False), (False, True), (True, True)])
def test_fused_decisions_match_numpy(pruned, quant):
    ev_f, _ = _events("kernel", pruned and {"fused": True},
                      quant and {"fused": True})
    ev_n, _ = _events("numpy", pruned, quant)
    assert _decisions(ev_f) == _decisions(ev_n)


def test_fused_pallas_kernel_parity():
    """One pallas-engine combo (interpret mode on CPU): fused == staged
    with the real kernel bodies, not just the jnp oracles."""
    ev_f, _ = _events("kernel", {"fused": True}, {"fused": True},
                      use_pallas=True)
    ev_s, _ = _events("kernel", {"fused": False}, {"fused": False},
                      use_pallas=True)
    assert ev_f == ev_s


@pytest.mark.parametrize("n_shards", [1, 2])
def test_sharded_fused_decision_parity(n_shards):
    """The sharded backend's unbound delegation reaches the fused path
    (same mirrors, sharded exact fallback) and keeps decision parity
    with its own staged driver and the numpy oracle."""
    ev_f, cache = _events("sharded", {"fused": True}, False,
                          n_shards=n_shards)
    ev_s, _ = _events("sharded", {"fused": False}, False,
                      n_shards=n_shards)
    ev_n, _ = _events("numpy", True, False)
    assert _decisions(ev_f) == _decisions(ev_s)
    assert _decisions(ev_f) == _decisions(ev_n)
    assert cache.metrics_snapshot()["prune"]["scans"] > 0


def test_arena_fused_parity():
    from repro.core import default_factories
    from repro.core.arena import run_arena
    from repro.core.types import Request, Trace
    reqs = [Request(t=i, cid=cid, emb=emb)
            for i, (cid, emb) in enumerate(_workload(n=200))]
    trace = Trace(requests=reqs)
    allf = default_factories()
    facs = {"LRU": allf["LRU"], "RAC": allf["RAC"]}
    kw = dict(hit_mode="semantic", tau_hit=0.80, backend="kernel",
              use_pallas=False, seed=0)
    key = lambda st: [(s.policy, s.hits, s.misses, s.evictions)
                      for s in st]
    st_f = run_arena(trace, 24, facs, pruned={"fused": True}, **kw)
    st_s = run_arena(trace, 24, facs, pruned={"fused": False}, **kw)
    assert key(st_f) == key(st_s)


# ------------------------------------------------------ compile stability
def test_fused_compile_stability():
    """Steady-state replay (full store, fixed batch bucket) reuses ONE
    executable per fused entry point — no per-chunk recompiles."""
    reqs = _workload(n=260)
    cfg = CacheConfig(capacity=40, dim=32, tau_hit=0.80,
                      hit_mode="semantic", backend="kernel",
                      pruned_lookup={"fused": True},
                      quantized_lookup={"fused": True},
                      use_pallas=False)
    cache = SemanticCache(cfg)
    for cid, emb in reqs[:60]:               # warm: fill + first buckets
        if not cache.lookup(emb).hit:
            cache.admit(cid, emb)
    before = fused.compile_counts()
    for cid, emb in reqs[60:]:
        if not cache.lookup(emb).hit:
            cache.admit(cid, emb)
    assert fused.compile_counts() == before


# ------------------------------------------------------ probe-cap account
def test_probe_cap_fused_staged_parity():
    """A tight scan budget truncates the probe list identically on both
    drivers (device cumulative-count prefix == host searchsorted), shows
    up in the ``capped`` ledger, and decisions stay exact."""
    tight = {"probes": 8, "max_scan_frac": 0.05, "min_scan_rows": 1}
    ev_f, cache_f = _events("kernel", dict(tight, fused=True), False)
    ev_s, cache_s = _events("kernel", dict(tight, fused=False), False)
    ev_x, _ = _events("kernel", False, False)
    assert ev_f == ev_s
    assert _decisions(ev_f) == _decisions(ev_x)
    capped_f = cache_f.backend.prune_stats["capped"]
    capped_s = cache_s.backend.prune_stats["capped"]
    assert capped_f > 0
    assert capped_f == capped_s


def test_uncapped_budget_keeps_small_stores_whole():
    """The min_scan_rows floor keeps the default budget above small
    stores, so the cap never truncates them (no behavior drift for the
    existing test workloads)."""
    ev_f, cache = _events("kernel", {"fused": True}, False)
    assert cache.backend.prune_stats["capped"] == 0
    assert fused.candidate_cap(np.array([4, 2, 3]), 2, 2, 256) >= 9


# ------------------------------------------------------- dispatch ledger
def test_dispatch_ledger_in_snapshot():
    ev, cache = _events("kernel", {"fused": True}, False, reqs=_workload(n=24))
    snap = cache.metrics_snapshot()
    assert set(snap["dispatch"]) == {"launches", "host_syncs", "kernel_s",
                                     "upload_s", "uploads"}
    assert snap["dispatch"]["launches"] > 0
    assert snap["dispatch"]["host_syncs"] > 0
    assert snap["dispatch"]["kernel_s"] >= 0.0
    # host backend: the ledger is present and inert
    _, host = _events("numpy", True, False, reqs=_workload(n=8))
    host_snap = host.metrics_snapshot()
    assert set(host_snap["dispatch"]) == {"launches", "host_syncs",
                                          "kernel_s", "upload_s", "uploads"}


def test_fused_launch_count_per_lookup():
    """Steady-state fused pruned lookups cost ONE fused launch each (the
    decide path adds one aux launch; this test drives lookup() directly)."""
    from repro.kernels import ops
    reqs = _workload(n=120)
    cfg = CacheConfig(capacity=40, dim=32, tau_hit=0.80,
                      hit_mode="semantic", backend="kernel",
                      pruned_lookup={"fused": True}, use_pallas=False)
    cache = SemanticCache(cfg)
    for cid, emb in reqs[:80]:
        if not cache.lookup(emb).hit:
            cache.admit(cid, emb)
    fb0 = fused.fused_stats["fallback_rows"]
    base = ops.dispatch_stats["launches"]
    hits = 0
    for cid, emb in reqs[80:]:
        if cache.lookup(emb).hit:
            hits += 1
    n, fb = 40, fused.fused_stats["fallback_rows"] - fb0
    assert hits > 0
    # one fused launch per lookup; each uncertified row may add exact-
    # fallback launches, so bound with the observed fallback count
    assert ops.dispatch_stats["launches"] - base <= n + 2 * fb
