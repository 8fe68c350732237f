#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration
(``chipbench/configs/<config>.json``), traffic mix
(``chipbench/traffic/<traffic>.json``), driver (``chipbench/drivers/``)
and per-layer metric readers (``chipbench/metrics/<metric>.py``) are found
by the names there, so a new cell, mix or metric is new files and entries
only.

Order of a run: the device check (a TPU with as many chips as the cell
asks for, no fallback), set-up (the program built, its store filled, every
shape of the window warmed; timed as ``setup_s``), the measured window,
the peak memory, then the comparison with the plain reference, which
decides ``correct``.  With ``--trace 1`` the window runs under the JAX
profiler and the per-layer metrics are printed instead of the end-to-end
ones.  The last line of standard output is one JSON object.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import resource
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import e2e                                     # noqa: E402  (harness files)
import trace as trace_reduce                   # noqa: E402
from timing import annotate                    # noqa: E402

#: Where a traced run writes the profiler's files (removed after reading).
TRACE_DIR = ROOT / ".chipbench_trace"
#: The persistent compilation cache, when the environment names none.
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(Exception):
    """A run that cannot produce a result (no chip, a bad cell)."""


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None:
        raise BenchError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str):
    """(benchmark, cell, configuration, traffic) for the cell ``name``."""
    bench_path = ROOT / "BENCHMARK.json"
    bench = json.loads(bench_path.read_text())
    cells = [w for w in bench["workloads"] if w["name"] == name]
    if not cells:
        raise BenchError(f"no workload named {name!r} in {bench_path}")
    cell = cells[0]
    cfg = json.loads((HERE / "configs" / f"{cell['config']}.json").read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, cfg, traffic


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"devices: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if require_tpu and dev["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX found {dev['platform']!r}; the "
                         "benchmark does not fall back to another device")
    if dev["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{dev['count']}")
    dev["count"] = chips
    return dev


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed directory: the one
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache`` in the
    checkout.  Every program is cached, however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compiles and persistent-cache reads (JAX monitoring
    events), so a window that compiles or loads a program shows it."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.cache_hits


def memory_peak(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def per_layer_metrics(bench: dict, cell: dict, ctx: dict) -> dict:
    """Each per-layer metric that lists this cell (or lists none), read by
    its own reader ``chipbench/metrics/<name>.py``; a reader that finds
    nothing returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        reader = load_module(HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def merge(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys replaced, nested dicts merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def run(argv=None, *, require_tpu: bool = True, overrides=None,
        driver_hook=None) -> dict:
    """One run; returns the result object (also printed as the last line).

    ``require_tpu=False``, ``overrides`` (``{"config": {...}, "traffic":
    {...}}`` merged into the cell's files) and ``driver_hook`` (called with
    the driver's cell object after set-up) are for the CPU rehearsal tests
    only."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic = load_cell(args.workload)
    if overrides:
        cfg = merge(cfg, overrides.get("config", {}))
        traffic = merge(traffic, overrides.get("traffic", {}))
    dev = device_info(int(cell["chips"]), require_tpu)
    log(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    driver = load_module(HERE / "drivers" / f"{cfg['driver']}.py")
    c = driver.Cell(cfg, traffic, seed=args.seed, seconds=args.seconds)

    c.setup()
    if driver_hook is not None:
        driver_hook(c)
    # what set-up built lives to the end of the run: keep it out of the
    # collector's passes, so none of them stalls the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s ({counter.compiles} compiles, "
        f"{counter.compile_s:.1f} s compiling, {counter.cache_hits} "
        "compile-cache reads)")

    import jax
    tdir = TRACE_DIR / f"{args.workload}.{args.seed}"
    if args.trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host: the annotations only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    before = counter.snapshot()
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    with annotate(trace_reduce.WINDOW_SPAN):
        rec = c.window()
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    in_window = (counter.compiles - before[0], counter.cache_hits - before[1])
    if args.trace:
        jax.profiler.stop_trace()
    log(f"window: {in_window[0]} compiles, {in_window[1]} compile-cache "
        f"reads inside it; generator late by at most "
        f"{rec['late_max_s'] * 1e3:.3f} ms")
    late = ", ".join(f"{t:.2f} s +{d * 1e3:.1f} ms" for t, d in rec["late"])
    log(f"wakes later than 5 ms: {len(rec['late'])} [{late}]; process CPU "
        f"over the window: {ru1.ru_utime - ru0.ru_utime:.2f} s user, "
        f"{ru1.ru_stime - ru0.ru_stime:.2f} s system")
    dev["memory_peak_bytes"] = memory_peak(int(cell["chips"]))

    result = {"correct": False, "attempted": int(rec["attempted"]),
              "failed": int(rec["failed"]), "metrics": {}, "device": dev}
    if args.trace:
        red = trace_reduce.reduce_dir(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        ctx = {"cell": cell, "cfg": cfg, "traffic": traffic, "rec": rec,
               "trace": red,
               # the rehearsal on the CPU has no peaks and no device ops
               "peaks": (trace_reduce.peaks_for(dev["kind"])
                         if dev["platform"] == "tpu" else None)}
        result["metrics"] = per_layer_metrics(bench, cell, ctx)
        result["breakdown"] = {"device_ops": red["device_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
    else:
        result["metrics"] = e2e.metrics(bench, cell, rec, setup_s)

    c.release()
    gc.collect()
    t_ref = time.perf_counter()
    checks = c.check()
    log(f"reference comparison {time.perf_counter() - t_ref:.3f} s")
    ok = all(ch["value"] <= ch["limit"] for ch in checks)
    if rec["failed"]:
        log(f"{rec['failed']} requests due in the window were never answered")
    result["correct"] = bool(ok and rec["failed"] == 0)
    # JSON has no infinity: an answer the reference could not even place
    # (a row that is not resident, a missing victim) reads as 1e308
    for ch in checks:
        if not math.isfinite(ch["value"]):
            ch["value"] = 1e308
    result["checks"] = {ch["name"]: {"value": ch["value"],
                                     "limit": ch["limit"]} for ch in checks}
    print(json.dumps(result, allow_nan=False), flush=True)
    for ch in checks:
        verdict = "ok" if ch["value"] <= ch["limit"] else "FAIL"
        print(f"check {ch['name']} {ch['value']!r} limit {ch['limit']!r} "
              f"{verdict}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    try:
        run(argv)
    except BenchError as e:
        log(f"FAIL: {e}")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
