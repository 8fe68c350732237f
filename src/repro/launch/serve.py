"""Serving driver: RAC-fronted engine over a trace of requests.

Replays a dialogue trace (synthetic or OASST-style) against the serving
engine: semantic-cache hits skip generation entirely; misses run batched
decode and admit their responses under RAC eviction.  Reports hit ratio +
generation savings — the end-to-end instantiation of the paper's claim
(hit ratio ∝ saved compute/latency).

The named model config runs at its published widths; ``--smoke`` swaps in
the reduced same-family model for CPU runs.  Request embeddings have the
width of a sentence encoder (``ENCODER_DIM``).

Usage:
    PYTHONPATH=src python -m repro.launch.serve --requests 200 \
        --capacity 64 --arch paper --backend kernel
    PYTHONPATH=src python -m repro.launch.serve --smoke --requests 30
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs import get_config
from repro.core import SynthConfig, synthetic_trace
from repro.launch.compile_cache import enable_compile_cache
from repro.models import smoke_variant
from repro.serving import EngineConfig, ServingEngine

#: Request-embedding width: that of all-MiniLM-L6-v2, a common encoder in
#: front of semantic caches.
ENCODER_DIM = 384


def main(argv=None) -> dict:
    """Serve one seeded trace; returns the run's record: the engine
    ``stats``, the completed requests (``done``, in request order), the
    cache's admit/evict event sequence (``events``), the cache's
    ``metrics`` snapshot, and the wall seconds of the run."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="numpy",
                    choices=("numpy", "kernel", "sharded"),
                    help="cache lookup/scoring backend")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family model, for CPU runs")
    args = ap.parse_args(argv)
    enable_compile_cache()

    mcfg = get_config(args.arch)
    if args.smoke:
        mcfg = smoke_variant(mcfg)
    ecfg = EngineConfig(cache_capacity=args.capacity,
                        max_new_tokens=args.max_new, emb_dim=ENCODER_DIM,
                        cache_backend=args.backend)
    engine = ServingEngine(mcfg, ecfg)
    events: list[tuple[str, int]] = []
    for kind in ("admit", "evict"):
        engine.cache.subscribe(kind,
                               lambda ev: events.append((ev.kind, ev.cid)))

    trace = synthetic_trace(SynthConfig(trace_len=args.requests,
                                        n_topics=24, dim=ENCODER_DIM,
                                        seed=args.seed))
    rng = np.random.default_rng(args.seed)
    reqs = []
    for r in trace.requests:
        prompt = list(rng.integers(2, mcfg.vocab_size,
                                   size=int(rng.integers(4, 12))))
        reqs.append((r.cid, r.emb, prompt))

    t0 = time.perf_counter()
    done = engine.run(reqs)
    dt = time.perf_counter() - t0
    s = engine.stats
    hr = s["hits"] / max(1, s["hits"] + s["misses"])
    print(f"[serve] {mcfg.name} ({mcfg.n_layers}L x {mcfg.d_model}) "
          f"backend={args.backend}: {len(done)} requests in {dt:.1f}s | "
          f"hit_ratio {hr:.3f} | generated {s['generated_tokens']} tokens "
          f"in {s['batches']} batched steps | hits {s['hits']} misses "
          f"{s['misses']} evictions {s['evictions']}")
    saved = s["hits"] * ecfg.max_new_tokens
    print(f"[serve] generation saved by cache ≈ {saved} tokens "
          f"({saved / max(1, saved + s['generated_tokens']):.1%} of total)")
    metrics = engine.cache.metrics_snapshot()
    engine.close()
    return {"stats": s, "done": done, "events": events, "metrics": metrics,
            "seconds": dt}


if __name__ == "__main__":
    main()
