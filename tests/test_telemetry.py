"""Telemetry subsystem: metric primitives, tracker sinks/scoping, the
observation-only (bit-exact decision parity) contract across backends and
hit modes, hook-failure containment, and the consolidated
``metrics_snapshot`` surface."""
import copy
import json
import math

import numpy as np
import pytest

from repro.cache import CacheConfig, SemanticCache
from repro.core import EmbeddingSpace, SynthConfig, synthetic_trace
from repro.telemetry import (NOOP, CompositeTracker, Histogram,
                             InMemoryTracker, JsonlTracker, MetricsRegistry,
                             NoopTracker, WindowedSeries, make_tracker,
                             render_text, summarize)


# ------------------------------------------------------- metric primitives
def test_histogram_quantiles_within_bucket_error():
    h = Histogram()
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-7, sigma=1.0, size=5000)
    for v in vals:
        h.observe(float(v))
    for q, true in ((0.5, np.quantile(vals, 0.5)),
                    (0.95, np.quantile(vals, 0.95)),
                    (0.99, np.quantile(vals, 0.99))):
        est = h.quantile(q)
        # log-bucket growth 2**0.25 -> <= ~9% relative bucket error
        assert abs(est - true) / true < 0.12, (q, est, true)
    assert h.count == 5000
    assert math.isclose(h.mean, float(np.mean(vals)), rel_tol=1e-9)
    p = h.percentiles()
    assert set(p) == {"p50", "p95", "p99"} and p["p50"] <= p["p99"]


def test_histogram_merge_equals_single_pass():
    a, b, both = Histogram(), Histogram(), Histogram()
    rng = np.random.default_rng(1)
    for i, v in enumerate(rng.exponential(size=400)):
        (a if i % 2 else b).observe(float(v))
        both.observe(float(v))
    a.merge(b)
    assert a.count == both.count
    assert a.buckets == both.buckets
    assert a.quantile(0.5) == both.quantile(0.5)
    assert a.vmin == both.vmin and a.vmax == both.vmax


def test_histogram_zero_and_bounds():
    h = Histogram()
    for v in (0.0, 0.0, 4.0):
        h.observe(v)
    assert h.quantile(0.0) == 0.0          # zero bucket sorts first
    assert h.quantile(1.0) <= h.vmax       # clamped to observed range


def test_windowed_series_means():
    s = WindowedSeries(window=10)
    for t, v in ((0, 1.0), (3, 0.0), (9, 1.0), (10, 1.0), (25, 0.0)):
        s.add(t, v)
    pts = s.series()
    assert [p["t"] for p in pts] == [0, 10, 20]
    assert pts[0]["mean"] == pytest.approx(2 / 3)
    assert pts[0]["count"] == 3
    assert pts[1]["mean"] == 1.0 and pts[2]["mean"] == 0.0


def test_registry_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("n", 2)
    b.inc("n", 3)
    b.inc("only_b")
    a.observe("lat", 1.0)
    b.observe("lat", 3.0)
    b.record("hit", 5, 1.0)
    a.merge(b)
    assert a.counters["n"] == 5 and a.counters["only_b"] == 1
    assert a.histograms["lat"].count == 2
    assert a.series["hit"].series()[0]["count"] == 1
    snap = a.snapshot()
    assert snap["counters"]["n"] == 5
    assert "lat" in snap["histograms"]


# ------------------------------------------------------------ tracker sinks
def test_child_scoping_prefixes_names():
    trk = InMemoryTracker()
    trk.child("backend").count("sync.rows", 5)
    trk.child("tier").child("host").count("hits")
    assert trk.counter("backend.sync.rows") == 5
    assert trk.counter("tier.host.hits") == 1


def test_tags_fold_into_metric_name():
    trk = InMemoryTracker()
    trk.count("cache.evictions", tags={"tier": "host"})
    trk.count("cache.evictions", tags={"tier": "device"})
    assert trk.counter("cache.evictions{tier=host}") == 1
    assert trk.counter("cache.evictions{tier=device}") == 1


def test_make_tracker_specs(tmp_path):
    assert make_tracker(None) is None
    assert make_tracker("") is None
    trk = InMemoryTracker()
    assert make_tracker(trk) is trk
    assert isinstance(make_tracker("noop"), NoopTracker)
    assert isinstance(make_tracker("memory"), InMemoryTracker)
    jl = make_tracker(f"jsonl:{tmp_path / 't.jsonl'}")
    assert isinstance(jl, JsonlTracker)
    combo = make_tracker(f"memory+jsonl:{tmp_path / 'u.jsonl'}")
    assert isinstance(combo, CompositeTracker) and len(combo.parts) == 2
    with pytest.raises(ValueError):
        make_tracker("wandb")
    with pytest.raises(ValueError):
        make_tracker(123)


def test_tracker_shared_not_cloned_by_deepcopy():
    trk = InMemoryTracker()
    assert copy.deepcopy(trk) is trk
    assert copy.deepcopy(NOOP) is NOOP


def test_jsonl_round_trip(tmp_path):
    path = tmp_path / "t.jsonl"
    trk = JsonlTracker(str(path), buffer=2)
    trk.count("a", 2, tags={"x": 1})
    trk.gauge("g", 0.5)
    trk.observe("h", 1e-3, t=7)
    with trk.span("s"):
        pass
    trk.close()
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    kinds = [r["kind"] for r in recs]
    assert kinds == ["count", "gauge", "observe", "span"]
    assert recs[0]["tags"] == {"x": 1}
    assert recs[2]["t"] == 7
    assert all("wall" in r for r in recs)


def test_chrome_export_is_valid(tmp_path):
    trk = InMemoryTracker()
    with trk.span("cache.decide_batch", tags={"b": 4}):
        pass
    trk.add_span("serve.request", 1.0, 1.5, track=3,
                 tags={"outcome": "hit"})
    path = tmp_path / "trace.json"
    trk.export_chrome(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for e in evs:
        assert e["ph"] == "X" and e["dur"] >= 0 and "ts" in e
    names = {e["name"] for e in evs}
    assert names == {"cache.decide_batch", "serve.request"}


def test_report_render(tmp_path):
    trk = InMemoryTracker()
    trk.count("cache.evictions", 3)
    trk.gauge("cache.queue_depth", 2)
    trk.observe("cache.lookup_s", 1e-4)
    trk.observe("cache.hit", 1.0, t=10)
    txt = render_text(summarize(trk), title="t")
    assert "cache.evictions" in txt and "cache.lookup_s" in txt
    from repro.telemetry import write_report
    out = write_report(trk, str(tmp_path / "r.json"), title="t")
    doc = json.loads((tmp_path / "r.json").read_text())
    assert doc["counters"]["cache.evictions"] == 3
    assert "cache.lookup_s" in doc["histograms"]
    assert out


# --------------------------------------------- observation-only bit parity
def _replay_events(backend, hit_mode, tracker, trace, capacity,
                   use_pallas=False, n_shards=2):
    kw = {"n_shards": n_shards} if backend == "sharded" else {}
    cache = SemanticCache(CacheConfig(
        capacity=capacity, dim=trace.requests[0].emb.shape[0],
        tau_hit=0.85, hit_mode=hit_mode, backend=backend,
        use_pallas=use_pallas, backend_kwargs=kw, tracker=tracker))
    events = []
    for kind in ("hit", "miss", "admit", "evict"):
        cache.subscribe(kind, lambda ev: events.append(
            (ev.kind, ev.cid, ev.t, ev.tier)))
    for r in trace.requests:
        res = cache.lookup(r.emb, cid=r.cid, t=r.t)
        if not res.hit:
            cache.admit(r.cid, r.emb, payload=(r.cid,), t=r.t)
    counters = (cache.metrics.hits, cache.metrics.misses,
                cache.metrics.evictions, cache.metrics.admissions)
    cache.close()
    return events, counters


@pytest.fixture(scope="module")
def parity_trace():
    return synthetic_trace(SynthConfig(trace_len=300, n_topics=8,
                                       dim=16, seed=4))


@pytest.mark.parametrize("backend", ["numpy", "kernel", "sharded"])
@pytest.mark.parametrize("hit_mode", ["content", "semantic"])
def test_decisions_bit_identical_across_trackers(parity_trace, backend,
                                                 hit_mode, tmp_path):
    trackers = [None, NOOP, InMemoryTracker(),
                JsonlTracker(str(tmp_path / f"{backend}-{hit_mode}.jsonl"))]
    runs = [_replay_events(backend, hit_mode, trk, parity_trace, 24)
            for trk in trackers]
    ref_events, ref_counters = runs[0]
    assert ref_counters[2] > 0          # workload actually evicts
    for events, counters in runs[1:]:
        assert events == ref_events
        assert counters == ref_counters


def test_backend_sync_counters_flow_to_tracker(parity_trace):
    trk = InMemoryTracker()
    cache = SemanticCache(CacheConfig(
        capacity=24, dim=16, hit_mode="semantic", backend="kernel",
        use_pallas=False, tracker=trk))
    for r in parity_trace.requests[:50]:
        res = cache.lookup(r.emb, cid=r.cid, t=r.t)
        if not res.hit:
            cache.admit(r.cid, r.emb, t=r.t)
    cache.decide_batch(np.stack([r.emb for r in parity_trace.requests[:8]]))
    assert trk.counter("backend.sync.full") >= 1
    assert trk.counter("backend.sync.bytes") > 0
    snap = cache.metrics_snapshot()
    assert snap["sync"]["full"] >= 1 and snap["sync"]["bytes"] > 0


# -------------------------------------------------- hook-failure containment
def test_poisoned_hook_is_contained_and_counted():
    trk = InMemoryTracker()
    cache = SemanticCache(CacheConfig(capacity=4, dim=8,
                                      hit_mode="content", tracker=trk))

    def _boom(ev):
        raise RuntimeError("poisoned subscriber")

    cache.subscribe("admit", _boom)

    seen = []
    cache.subscribe("admit", lambda ev: seen.append(ev.cid))
    emb = np.ones(8, dtype=np.float32)
    evicted = cache.admit(1, emb)          # must not raise
    assert evicted == [] and 1 in cache
    assert seen == [1]                     # later hooks still ran
    assert cache.metrics.hook_errors == 1
    assert trk.counter("cache.hook_errors{kind=admit}") == 1
    assert cache.metrics_snapshot()["hook_errors"] == 1


def test_debug_hooks_reraises():
    cache = SemanticCache(CacheConfig(capacity=4, dim=8,
                                      hit_mode="content", debug_hooks=True))
    cache.subscribe("admit", lambda ev: (_ for _ in ()).throw(
        RuntimeError("boom")))
    with pytest.raises(RuntimeError, match="boom"):
        cache.admit(1, np.ones(8, dtype=np.float32))
    assert cache.metrics.hook_errors == 1


# ------------------------------------------------- consolidated snapshot
def test_metrics_snapshot_merges_all_surfaces():
    from repro.cache import TierConfig
    space = EmbeddingSpace(dim=16, seed=9)
    cache = SemanticCache(CacheConfig(
        capacity=4, dim=16, hit_mode="content", async_admit="sync",
        tiers=TierConfig(host_capacity=8, ghost_capacity=8),
        tracker=InMemoryTracker()))
    for i in range(10):
        emb = space.content_embedding(i % 3, i).astype(np.float32)
        if not cache.lookup(emb, cid=i).hit:
            cache.admit(i, emb, payload=[i])
    cache.flush()
    snap = cache.metrics_snapshot()
    for key in ("hits", "misses", "evictions", "hit_ratio", "hook_errors",
                "pending_admits", "admit_stall_s", "enqueue_s", "flush_s",
                "tiers"):
        assert key in snap, key
    assert snap["pending_admits"] == 0
    assert snap["tiers"]["demotions"] > 0
    cache.close()


def test_checkpoint_restore_shares_tracker():
    trk = InMemoryTracker()
    cache = SemanticCache(CacheConfig(capacity=4, dim=8,
                                      hit_mode="content", tracker=trk))
    emb = np.ones(8, dtype=np.float32)
    cache.admit(1, emb)
    snap = cache.checkpoint()
    cache.admit(2, emb)
    cache.restore(snap)
    assert cache.tracker is trk            # never cloned by the deep copy
    cache.lookup(emb, cid=1)
    assert trk.percentiles("cache.lookup_s") is not None


# ------------------------------------- spans on the profiler's clock
SPAN_PREFIXES = ("rac/", "op/")


def _profiled(tmp_path, fn):
    """Run ``fn`` under a CPU ``jax.profiler`` trace and read the host
    spans back as ``chipbench/trace.load`` does: (start_ns, end_ns, name)
    of the ``/host:CPU`` plane's events, in start order."""
    import glob

    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0           # host: the annotations only
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIXES)]
    return sorted(spans, key=lambda s: (s[0], -s[1]))


def _named(spans, name):
    return [(a, b) for a, b, n in spans if n == name]


def _each_inside(spans, inner, outer):
    """Every ``inner`` span lies inside some ``outer`` span (and there is
    at least one ``inner``)."""
    outs = _named(spans, outer)
    ins = _named(spans, inner)
    return bool(ins) and all(any(oa <= a and b <= ob for oa, ob in outs)
                             for a, b in ins)


def test_cache_spans_nest_on_the_profiler_clock(tmp_path):
    rng = np.random.default_rng(3)
    cache = SemanticCache(CacheConfig(capacity=8, dim=16, tau_hit=0.85,
                                      backend="kernel",
                                      backend_kwargs={"interpret": True}))
    rows = rng.normal(size=(12, 16)).astype(np.float32)
    queries = rng.normal(size=(3, 16)).astype(np.float32)

    def work():
        for i in range(12):                # 4 admissions past capacity
            cache.admit(i, rows[i])
        cache.lookup_batch(queries)

    spans = _profiled(tmp_path, work)
    names = {n for _, _, n in spans}
    assert {"rac/admit", "rac/arrive", "rac/victim", "rac/rac_value",
            "rac/lookup_batch", "rac/upload", "rac/sim_top1"} <= names
    assert len(_named(spans, "rac/admit")) == 12
    assert len(_named(spans, "rac/victim")) == 4
    assert len(_named(spans, "rac/lookup_batch")) == 1     # not per query
    assert _each_inside(spans, "rac/arrive", "rac/admit")
    assert _each_inside(spans, "rac/victim", "rac/admit")
    assert _each_inside(spans, "rac/rac_value", "rac/victim")
    assert _each_inside(spans, "rac/upload", "rac/lookup_batch")
    assert _each_inside(spans, "rac/sim_top1", "rac/lookup_batch")
    # the upload is the scan's sibling, done before the scan starts
    (ua, ub), = _named(spans, "rac/upload")
    (sa, sb), = _named(spans, "rac/sim_top1")
    assert ub <= sa
    assert [n for a, _, n in spans if ua <= a < sb] == ["rac/upload",
                                                        "rac/sim_top1"]


def test_engine_spans_per_decode_step_are_bounded(tmp_path):
    from repro.configs import get_config
    from repro.core import EmbeddingSpace
    from repro.models import smoke_variant
    from repro.serving import EngineConfig, ServingEngine
    slots = 4
    eng = ServingEngine(smoke_variant(get_config("paper")), EngineConfig(
        cache_capacity=4, max_new_tokens=3, max_batch=slots, max_seq=64))
    space = EmbeddingSpace(dim=64, seed=21)
    reqs = [(i, space.content_embedding(i, i).astype(np.float32), [2, 3, 4])
            for i in range(9)]
    eng.run(reqs[:1])                      # compile outside the trace
    steps0 = eng.stats["batches"]

    spans = _profiled(tmp_path, lambda: eng.run(reqs[1:]))
    steps = eng.stats["batches"] - steps0
    assert steps > 0 and eng.stats["evictions"] > 0
    kinds = {n for _, _, n in spans if n.startswith("rac/engine/")}
    assert kinds == {"rac/engine/init_kv", "rac/engine/dispatch",
                     "rac/engine/sync", "rac/engine/slots",
                     "rac/engine/fill"}
    # one span per phase of a step, none per slot
    assert len(_named(spans, "rac/engine/init_kv")) == 1
    for phase in ("dispatch", "sync", "slots"):
        assert len(_named(spans, f"rac/engine/{phase}")) == steps
    assert len(_named(spans, "rac/engine/fill")) == steps + 1
    # finished requests are admitted (and evict) inside the slot loop
    assert _each_inside(spans, "rac/admit", "rac/engine/slots")
    assert _each_inside(spans, "rac/victim", "rac/admit")


def test_tracker_spans_show_in_the_profiler_trace(tmp_path):
    mem = InMemoryTracker()
    jl = JsonlTracker(str(tmp_path / "t.jsonl"))

    def work():
        with mem.span("op/memory"):
            pass
        with jl.span("op/jsonl"):
            pass
        mem.add_span("op/after_the_fact", 0.0, 1.0)     # host-only

    spans = _profiled(tmp_path / "prof", work)
    assert {n for _, _, n in spans} == {"op/memory", "op/jsonl"}
    assert [e[0] for e in mem.trace.events] == ["op/memory",
                                                "op/after_the_fact"]
    jl.close()


def test_victim_and_upload_counters(parity_trace):
    """``victim_s`` advances on both backends.  The kernel backend's exact
    lookup scans the journal-synced store mirror: one full sync on the
    first ``lookup_batch`` after the fill, a dirty-row scatter after one
    more admission, and no explicit slab upload either time.  The host
    backend counts no upload.  Decisions agree."""
    from repro.kernels import ops
    reqs = parity_trace.requests[:81]
    queries = np.stack([r.emb for r in reqs[:5]])
    out = {}
    for backend in ("numpy", "kernel"):
        cache = SemanticCache(CacheConfig(
            capacity=24, dim=16, tau_hit=0.85, backend=backend,
            use_pallas=False))
        evicted = []
        for r in reqs[:80]:
            evicted += cache.admit(r.cid, r.emb, t=r.t)
        before = cache.metrics_snapshot()
        up0 = dict(ops.dispatch_stats)
        res = cache.lookup_batch(queries)
        snap = cache.metrics_snapshot()
        assert snap["evictions"] == len(evicted) > 0
        assert 0.0 < snap["victim_s"] <= snap["admit_s"]
        assert {"upload_s", "uploads"} <= set(snap["dispatch"])
        r = reqs[80]
        evicted += cache.admit(r.cid, r.emb, t=r.t)
        res += cache.lookup_batch(queries)
        again = cache.metrics_snapshot()
        if backend == "kernel":
            assert ops.dispatch_stats["uploads"] == up0["uploads"]
            for a, b in ((before, snap), (snap, again)):
                assert b["dispatch"]["uploads"] == a["dispatch"]["uploads"]
            # the first scan after the fill uploads the mirror once ...
            assert snap["sync"]["full"] == before["sync"]["full"] + 1
            # ... and after one more admission only its rows are scattered
            assert again["sync"]["full"] == snap["sync"]["full"]
            assert (again["sync"]["incremental"]
                    == snap["sync"]["incremental"] + 1)
        else:
            assert (again["dispatch"]["uploads"],
                    again["dispatch"]["upload_s"]) == (0, 0.0)
        out[backend] = (evicted, [(r.hit, getattr(r, "cid", None))
                                  for r in res])
    assert out["numpy"] == out["kernel"]
