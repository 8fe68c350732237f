#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate its backlog does not grow at.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 20,40,60

One process, one set-up: the cell is filled and warmed once, then driven
for ``--seconds`` at each rate in turn, on fresh messages of the same
stream.  For each rate it prints the latency percentiles, the share of the
window's requests answered inside the window, and the median lag (answer
minus due time) of the first and the last quarter of the window's
requests; a last quarter that lags far behind the first is a growing
backlog.  The rate in a traffic file is written by hand from this table
(0.8 x the knee); a run never searches for one.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np  # noqa: E402

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    bench, cell, cfg, traffic = run.load_cell(args.workload)
    run.device_info(int(cell["chips"]))
    run.enable_compile_cache()
    driver = run.load_module(run.HERE / "drivers" / f"{cfg['driver']}.py")
    c = driver.Cell(cfg, traffic, seed=args.seed, seconds=args.seconds,
                    rate=rates[0])
    c.drain_s = 5.0                    # a sweep point stops 5 s past its window
    c.setup(extra=int(sum(rates) * (args.seconds + c.lead) * 1.1) + 64)
    gc.collect()
    gc.freeze()
    rows = []
    for i, rate in enumerate(rates):
        if i:
            c.reschedule(rate)
        rec = c.window()
        lag = np.asarray(rec["done"]) - np.asarray(rec["due"])
        q = max(1, len(lag) // 4)
        row = {"rate": rate, "requests": int(rec["attempted"]),
               "p50_ms": float(np.percentile(lag, 50) * 1e3),
               "p95_ms": float(np.percentile(lag, 95) * 1e3),
               "in_window": float(np.mean(np.asarray(rec["done"])
                                          <= args.seconds)),
               "lag_first_q_ms": float(np.median(lag[:q]) * 1e3),
               "lag_last_q_ms": float(np.median(lag[-q:]) * 1e3)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print("| rate (req/s) | requests | p50 ms | p95 ms | answered in window "
          "| lag first/last quarter ms |")
    print("| --- | --- | --- | --- | --- | --- |")
    for r in rows:
        print(f"| {r['rate']:g} | {r['requests']} | {r['p50_ms']:.1f} | "
              f"{r['p95_ms']:.1f} | {r['in_window']:.3f} | "
              f"{r['lag_first_q_ms']:.1f} / {r['lag_last_q_ms']:.1f} |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
