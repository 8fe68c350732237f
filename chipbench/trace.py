"""Reduce a profiler trace (``.xplane.pb``) to what the window did on the chip.

What a TPU trace holds, as JAX 0.9 writes it: a plane per chip
(``/device:TPU:<n>``) whose ``XLA Ops`` line has one event per HLO
instruction executed, named by the instruction's own text (``%name =
<shape> <opcode>(<operands>) ...``), and whose ``XLA Modules`` line has one
event per program run; and a ``/host:CPU`` plane whose thread lines carry
the host spans, among them the harness's ``chipbench/...`` annotations
and the program's ``rac/...`` ones.  All events share one clock.

The reduction, inside the ``chipbench/window`` span:

* busy time: the union of the chip's op intervals (averaged over chips);
* device ops: seconds per program and instruction (numeric suffixes
  dropped), most first; an op that holds others (a ``while`` loop around
  its body's ops) is left out, its body's ops are listed;
* programs: runs and device seconds per program (module), its every op
  and the gaps between them included;
* idle gaps: the complement of busy time, each gap charged to the
  innermost harness or program span open at its midpoint, summed per span;
* kernels: every Pallas call (``tpu_custom_call``) with its duration and
  its operand and result shapes, labelled by its signature: ``sim_top1``
  (results ``f32[Q,1], s32[Q,1]`` from ``s32[1], f32[Q,D], f32[N,D]``) and
  ``rac_value`` (``f32[R,128]`` from three ``f32[R,128]``); others keep
  their instruction name.
"""
from __future__ import annotations

import glob
import json
import re
from collections import defaultdict
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
WINDOW_SPAN = "chipbench/window"
HOST_PREFIXES = ("chipbench/", "rac/")

_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_BITS = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
         "u8": 1, "pred": 1, "f64": 8, "s64": 8}


def peaks_for(kind: str) -> dict:
    """The chip's published peaks; an unknown device kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    return table[kind]


def shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    """(dtype, dims) of every array shape written in ``text``."""
    return [(t, tuple(int(x) for x in d.split(",") if x))
            for t, d in _SHAPE.findall(text)]


def nbytes(shape: tuple[str, tuple[int, ...]]) -> int:
    n = _BITS.get(shape[0], 4)
    for d in shape[1]:
        n *= d
    return n


def _base(name: str) -> str:
    """An HLO or module name without its numeric suffix or hash."""
    name = name.lstrip("%").split("(")[0]
    return re.sub(r"\.\d+$", "", name)


def kernel_call(op: str):
    """(label, result shapes, operand shapes) of a Pallas call, else None."""
    if 'custom_call_target="tpu_custom_call"' not in op:
        return None
    lhs, rhs = op.split(" custom-call(", 1)
    results = shapes(lhs.split(" = ", 1)[1])
    operands = shapes(rhs.split("), custom_call_target", 1)[0])
    label = _base(lhs.split(" = ", 1)[0])
    dt = [s[0] for s in operands]
    if (len(results) == 2 and [r[0] for r in results] == ["f32", "s32"]
            and dt == ["s32", "f32", "f32"]):
        label = "sim_top1"
    elif (len(results) == 1 and len(operands) == 3 and dt == ["f32"] * 3
          and all(s[1] == results[0][1] for s in operands)
          and results[0][1][-1:] == (128,)):
        label = "rac_value"
    return label, results, operands


def _union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def load(path) -> dict:
    """The trace's device ops, program runs and host spans, in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops = defaultdict(list)        # plane -> [(start, end, text)]
    mods = defaultdict(list)       # plane -> [(start, end, name)]
    host = []                      # [(start, end, name)]
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": mods}.get(line.name)
                if dst is not None:
                    dst[plane.name] += [(e.start_ns, e.start_ns + e.duration_ns,
                                         e.name) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events
                         if e.name.startswith(HOST_PREFIXES)]
    return {"ops": dict(ops), "mods": dict(mods), "host": host}


def reduce(tr: dict) -> dict:
    """Busy and window seconds, device ops, idle gaps and kernel calls of
    the ``chipbench/window`` span (see the module docstring)."""
    wins = [h for h in tr["host"] if h[2] == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = wins[0][0], wins[0][1]
    busy_by_chip = {}
    by_op: dict[str, float] = defaultdict(float)
    kernels: dict[str, list] = defaultdict(list)
    programs: dict[str, list] = defaultdict(lambda: [0, 0.0])
    first_busy = None
    for plane in sorted(tr["ops"]):
        ops = [(max(a, w0), min(b, w1), t) for a, b, t in tr["ops"][plane]
               if b > w0 and a < w1]
        mods = sorted((a, b, n) for a, b, n in tr["mods"].get(plane, []))
        for a, b, name in mods:
            if b > w0 and a < w1:
                programs[_base(name)][0] += 1
                programs[_base(name)][1] += (min(b, w1) - max(a, w0)) * 1e-9
        busy = _union([(a, b) for a, b, _ in ops if b > a])
        busy_by_chip[plane] = sum(b - a for a, b in busy)
        if first_busy is None:
            first_busy = busy
        mi = 0
        ops.sort(key=lambda o: (o[0], -o[1]))      # a loop before its body
        for i, (a, b, text) in enumerate(ops):
            while mi < len(mods) and mods[mi][1] < a:
                mi += 1
            mod = (_base(mods[mi][2]) if mi < len(mods) and mods[mi][0] <= a
                   else "?")
            if i + 1 < len(ops) and ops[i + 1][0] < b and ops[i + 1][1] <= b:
                continue          # a loop around the ops listed inside it
            k = kernel_call(text)
            inst = k[0] if k else _base(text.split(" = ", 1)[0])
            by_op[f"{mod}/{inst}"] += (b - a) * 1e-9
            if k:
                kernels[k[0]].append(((b - a) * 1e-9, k[1], k[2]))
    busy = first_busy or []
    gaps, prev = [], w0
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if w1 > prev:
        gaps.append((prev, w1))
    # the harness's and the program's spans nest on one thread: the span
    # open at a gap's midpoint that started last is the innermost
    spans = sorted(h for h in tr["host"] if h[2] != WINDOW_SPAN)
    idle: dict[str, float] = defaultdict(float)
    active: list = []
    si = 0
    for a, b in gaps:
        mid = 0.5 * (a + b)
        while si < len(spans) and spans[si][0] <= mid:
            active.append(spans[si])
            si += 1
        active = [h for h in active if h[1] >= mid]
        name = max(active)[2] if active else "host: no span"
        idle[name] += (b - a) * 1e-9
    n = max(1, len(busy_by_chip))
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy_by_chip.values()) * 1e-9 / n,
            "device_ops": sorted(([k, v] for k, v in by_op.items()),
                                 key=lambda x: -x[1]),
            "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                                key=lambda x: -x[1]),
            "kernels": dict(kernels),
            "programs": {k: [r / n, t / n] for k, (r, t) in programs.items()}}


def reduce_dir(path) -> dict:
    """Reduce the one ``.xplane.pb`` the profiler wrote under ``path``."""
    files = glob.glob(str(Path(path) / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {path}: {files}")
    return reduce(load(files[0]))
