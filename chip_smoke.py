#!/usr/bin/env python3
"""Smoke run of the served RAC path on a TPU.

Runs, in one process and in order:

1. a device check: the first JAX device must be a TPU — there is no CPU
   fallback;
2. the served path through ``repro.launch.serve.main``: the ``paper``
   model at its published widths (32 layers, d_model 960, bf16, random
   weights from the seed) behind the semantic cache with
   ``--backend kernel``, then the same requests with ``--backend numpy``;
   per-request hit flags, generated tokens and the admit/evict sequence
   must be identical, and no event hook may have failed;
3. the lookup hot path: a ``SemanticCache`` on the kernel backend with
   the int8 and topic-pruned lookups on (the fused one-launch pipeline)
   over a 65,536 x 384 store, fed the same lookups, admissions and
   decision passes as a ``NumpyBackend`` twin; decisions must be equal
   and the device kernels must have launched.

With ``--four-chips`` it runs only the sharded store over a 4-device
mesh against a ``NumpyBackend`` twin: 65,536 rows per shard under LRU,
then a small store under RAC for its sharded eviction and decision
programs.

Any failure exits non-zero.  The last line of standard output is one JSON
object naming the device, printed only when every phase passed.

Usage (from the repository root):
    python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

EMB_DIM = 384                 # sentence-encoder width (all-MiniLM-L6-v2)
# Topic geometry: items of one topic sit ~0.80 apart (below tau_hit 0.85),
# paraphrases ~0.93 from their item.  Looser topics (the 0.70 of
# repro.core.embeddings) leave the pruned lookup's spread bound above
# tau_hit, and every query then falls back to the exact scan.
COS_TOPIC = 0.80 ** 0.5
COS_PARA = 0.93


def fail(msg: str):
    print(f"[smoke] FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def check(cond, msg: str):
    if not cond:
        fail(msg)


def device_check(n_chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[smoke] devices: platform={dev['platform']} "
          f"kind={dev['kind']} count={dev['count']}")
    check(dev["platform"] == "tpu",
          f"no TPU: JAX found {dev['platform']!r}; this smoke run does not "
          "fall back to the CPU")
    check(dev["count"] >= n_chips, f"needs {n_chips} chips, found "
          f"{dev['count']}")
    return dev


class CompileClock:
    """Sums JAX's backend-compile durations and persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, h0, t0 = self.seconds, self.cache_hits, time.perf_counter()
        yield
        print(f"[smoke] phase {name}: {time.perf_counter() - t0:.1f}s wall, "
              f"{self.seconds - c0:.1f}s compiling, "
              f"{self.cache_hits - h0} compile-cache hits")


# ---------------------------------------------------------------- served

def served_phase(seed: int, requests: int = 300, capacity: int = 64):
    from repro.launch import serve
    argv = ["--arch", "paper", "--requests", str(requests), "--capacity",
            str(capacity), "--seed", str(seed)]
    dev = serve.main(argv + ["--backend", "kernel"])
    ref = serve.main(argv + ["--backend", "numpy"])
    for name, run in (("kernel", dev), ("numpy", ref)):
        check(run["metrics"]["hook_errors"] == 0,
              f"{name} run: {run['metrics']['hook_errors']} hook errors")
    check([r.cached for r in dev["done"]] == [r.cached for r in ref["done"]],
          "per-request hit flags differ between kernel and numpy backends")
    check(dev["events"] == ref["events"],
          "admit/evict sequences differ between kernel and numpy backends")
    check([r.out_tokens for r in dev["done"]]
          == [r.out_tokens for r in ref["done"]],
          "generated tokens differ between kernel and numpy backends")
    s = dev["stats"]
    check(s["hits"] > 0 and s["misses"] > 0 and s["evictions"] > 0,
          f"served run lacks hits, misses or evictions: {s}")
    check(dev["metrics"]["dispatch"]["launches"] > 0,
          "kernel backend launched no device program")
    print(f"[smoke] served: {len(dev['done'])} requests, hits {s['hits']}, "
          f"misses {s['misses']}, evictions {s['evictions']}, generated "
          f"{s['generated_tokens']} tokens; kernel == numpy on hit flags, "
          f"tokens and {len(dev['events'])} admit/evict events")


# ---------------------------------------------------------------- lookups

def _unit(x):
    return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype(np.float32)


def _around(rng, base, cos):
    """Unit rows at cosine ``cos`` from each (unit) row of ``base``."""
    g = rng.standard_normal(base.shape, dtype=np.float32)
    g -= np.sum(g * base, axis=1, keepdims=True) * base
    return _unit(cos * base + np.sqrt(1.0 - cos * cos) * _unit(g))


class Workload:
    """Seeded clustered embeddings: ``n_topics`` centroids, stored rows
    around them, and query chunks mixing paraphrases of stored rows
    (cosine ~0.93, above ``tau_hit``) with fresh in-topic items."""

    def __init__(self, rng, n_rows: int, n_topics: int):
        self.rng = rng
        self.cent = _unit(rng.standard_normal((n_topics, EMB_DIM),
                                              dtype=np.float32))
        self.topic = rng.integers(0, n_topics, n_rows)
        self.rows = _around(rng, self.cent[self.topic], COS_TOPIC)
        self.next_cid = n_rows

    def queries(self, b: int):
        src = self.rng.integers(0, self.rows.shape[0], b)
        para = (self.rng.random(b) < 0.6)[:, None]
        q = np.where(para, _around(self.rng, self.rows[src], COS_PARA),
                     _around(self.rng, self.cent[self.topic[src]],
                             COS_TOPIC))
        cids = np.arange(self.next_cid, self.next_cid + b)
        self.next_cid += b
        return q, cids


def fill(caches, rows):
    for cache in caches:
        for i in range(rows.shape[0]):
            cache.admit(i, rows[i])


def drive(dev, ref, work: Workload, chunks, decide_every: int = 8):
    """Feed both caches the same operations and require equal decisions:
    per-query hit flags and hit cids, evicted cids per admission, and on
    every ``decide_every``-th chunk a decision pass (hit, route and victim
    columns).  Misses are admitted on alternate queries, so a full store
    evicts.  Returns (hits, misses, evictions, max |sim| difference)."""
    hits = misses = evictions = 0
    sim_diff = 0.0
    for n, b in enumerate(chunks):
        q, cids = work.queries(b)
        out = []
        for cache in (dev, ref):
            if b == 1:
                out.append([cache.lookup(q[0], cid=int(cids[0]))])
            else:
                out.append(cache.lookup_batch(q, cids=cids))
        for i, (a, r) in enumerate(zip(*out)):
            check(a.hit == r.hit and (not a.hit or a.cid == r.cid),
                  f"chunk {n} query {i}: device {a} != reference {r}")
            if a.hit:
                hits += 1
                sim_diff = max(sim_diff, abs(a.sim - r.sim))
            else:
                misses += 1
                if i % 2 == 0:
                    ev = [c.admit(int(cids[i]), q[i]) for c in (dev, ref)]
                    check(ev[0] == ev[1], f"chunk {n}: admitting "
                          f"{cids[i]} evicted {ev[0]} != {ev[1]}")
                    evictions += len(ev[0])
        if n % decide_every == 0:
            da, dr = (c.decide_batch(q) for c in (dev, ref))
            # a certified miss may report an approximate best row, so only
            # hits name a cid that must agree
            hit = dr.hit_sim >= ref.cfg.tau_hit
            check(np.array_equal(da.hit_sim >= dev.cfg.tau_hit, hit)
                  and np.array_equal(da.hit_cid[hit], dr.hit_cid[hit]),
                  f"chunk {n}: decide_batch hit columns differ")
            check(np.array_equal(da.route_tid, dr.route_tid),
                  f"chunk {n}: decide_batch route columns differ")
            if dr.victim_value is not None:
                # slot-indexed, and the stores may place a cid in
                # different slots: compare per resident cid
                res = list(ref.store.slot_of)
                va = da.victim_value[[dev.store.slot_of[c] for c in res]]
                vr = dr.victim_value[[ref.store.slot_of[c] for c in res]]
                check(np.allclose(va, vr, rtol=1e-5, atol=0)
                      and np.isinf(da.victim_value[~dev.store.occ]).all(),
                      f"chunk {n}: decide_batch victim values differ")
    check(sim_diff < 1e-5, f"hit similarities differ by {sim_diff}")
    return hits, misses, evictions, sim_diff


def lookup_phase(seed: int, n_rows: int = 65536, n_topics: int = 256,
                 chunks=(1,) * 1024 + (16,) * 64):
    from repro.cache import CacheConfig, SemanticCache
    from repro.kernels import fused
    cfg = dict(capacity=n_rows, dim=EMB_DIM, tau_hit=0.85, policy="RAC")
    dev = SemanticCache(CacheConfig(**cfg, backend="kernel",
                                    quantized_lookup=True,
                                    pruned_lookup=True))
    ref = SemanticCache(CacheConfig(**cfg, backend="numpy"))
    work = Workload(np.random.default_rng(seed), n_rows, n_topics)
    t0 = time.perf_counter()
    fill((dev, ref), work.rows)
    print(f"[smoke] lookup store: {n_rows} x {EMB_DIM} rows admitted to "
          f"both caches in {time.perf_counter() - t0:.1f}s")
    launches0 = dev.metrics_snapshot()["dispatch"]["launches"]
    calls0 = fused.fused_stats["calls"]
    hits, misses, evictions, sim_diff = drive(dev, ref, work, chunks)
    snap = dev.metrics_snapshot()
    launches = snap["dispatch"]["launches"] - launches0
    calls = fused.fused_stats["calls"] - calls0
    check(launches > 0, "no device program launched in the lookup phase")
    check(calls > 0, "the fused lookup pipeline never ran")
    check(hits > 0 and misses > 0 and evictions > 0,
          f"lookup phase lacks hits, misses or evictions "
          f"({hits}, {misses}, {evictions})")
    print(f"[smoke] lookups: {sum(chunks)} queries in {len(chunks)} chunks "
          f"(widths {sorted(set(chunks))}): hits {hits}, misses {misses}, "
          f"evictions {evictions}; device == numpy decisions; "
          f"{launches} device launches, {calls} fused-pipeline calls, "
          f"{snap['prune']['fallbacks']} exact fallbacks; max hit-sim "
          f"difference {sim_diff:.3g}")


# ---------------------------------------------------------------- sharded

def sharded_phase(seed: int, n_shards: int = 4, rows_per_shard: int = 65536,
                  chunks=(1,) * 256 + (16,) * 32):
    """The store sharded over the mesh, at scale under LRU (cheap host
    admission keeps the 262,144-row fill short), then at a small scale
    under RAC so its sharded eviction and decision programs run too."""
    from repro.cache import CacheConfig, SemanticCache
    rng = np.random.default_rng(seed)
    for policy, rows, n_topics, ch in (
            ("LRU", rows_per_shard, 256, chunks),
            ("RAC", 1024, 32, (1,) * 64 + (16,) * 16)):
        cap = rows * n_shards - 1          # +1 spare slot fills the shard
        cfg = dict(capacity=cap, dim=EMB_DIM, tau_hit=0.85, policy=policy)
        dev = SemanticCache(CacheConfig(**cfg, backend="sharded",
                                        backend_kwargs={"n_shards":
                                                        n_shards}))
        ref = SemanticCache(CacheConfig(**cfg, backend="numpy"))
        check(dev.backend.mesh() is not None,
              f"no {n_shards}-device cache mesh: the sharded backend "
              "would loop the shards on one device")
        work = Workload(rng, cap, n_topics)
        t0 = time.perf_counter()
        fill((dev, ref), work.rows)
        fill_s = time.perf_counter() - t0
        hits, misses, evictions, sim_diff = drive(dev, ref, work, ch)
        slab, _ = dev.backend._slab(dev.store)
        devices = slab.sharding.device_set
        shapes = sorted({s.data.shape for s in slab.addressable_shards})
        check(len(devices) == n_shards,
              f"slab spans {len(devices)} devices, not {n_shards}")
        check(shapes == [(1, rows, EMB_DIM)],
              f"slab shards have shapes {shapes}")
        print(f"[smoke] sharded {policy}: {cap + 1} slots over "
              f"{len(devices)} devices, shard {shapes[0]}, filled in "
              f"{fill_s:.1f}s; {sum(ch)} queries: hits {hits}, misses "
              f"{misses}, evictions {evictions}; sharded == numpy "
              f"decisions; max hit-sim difference {sim_diff:.3g}")


# ---------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded store over a 4-chip mesh")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    dev = device_check(n_chips)

    from repro.launch.compile_cache import enable_compile_cache
    print(f"[smoke] compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    if args.four_chips:
        with clock.phase("sharded"):
            sharded_phase(args.seed, n_shards=n_chips)
    else:
        with clock.phase("served"):
            served_phase(args.seed)
        with clock.phase("lookup"):
            lookup_phase(args.seed)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
