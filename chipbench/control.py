#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` for the program and for the
lower-precision control, on several seeds of one cell, in one process.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Each seed is a full set-up and a window at the cell's own load, as in a
run; then the reference reads the program's numbers and the control's: the
same reference computed one precision step lower in the program's place
(the lookup scan at precision HIGH, three bfloat16 passes, instead of
HIGHEST; the eviction values rounded to bfloat16; the model's weights in
float8).
A limit is set between the program's largest reading and the control's
smallest; the benchmark's own runs never compute the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench, cell, cfg, traffic = run.load_cell(args.workload)
    run.device_info(int(cell["chips"]))
    run.enable_compile_cache()
    driver = run.load_module(run.HERE / "drivers" / f"{cfg['driver']}.py")
    for seed in (int(s) for s in args.seeds.split(",")):
        c = driver.Cell(cfg, traffic, seed=seed, seconds=args.seconds)
        c.setup()
        rec = c.window()
        c.release()
        gc.collect()
        checks = c.check(control=True)
        print(json.dumps({"seed": seed, "attempted": rec["attempted"],
                          "failed": rec["failed"], "checks": checks}),
              flush=True)
        del c
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
