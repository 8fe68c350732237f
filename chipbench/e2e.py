"""End-to-end arithmetic: what a user of the system sees, from the
harness's own clock.

Every request due inside the window enters: its latency runs from its due
time (not from when the harness got to it, so a stall delays every
request behind it) to its answer.  A request still unanswered when the
harness gave up enters with the time it had waited by then.
"""
from __future__ import annotations

import numpy as np


def _ms(x) -> float:
    return float(x) * 1e3


def latency_s(rec: dict) -> np.ndarray:
    return np.asarray(rec["done"]) - np.asarray(rec["due"])


def latency_p50_ms(rec: dict) -> float:
    return _ms(np.percentile(latency_s(rec), 50))


def latency_p95_ms(rec: dict) -> float:
    return _ms(np.percentile(latency_s(rec), 95))


def ttft_p95_ms(rec: dict) -> float:
    """Due time to the first token, or to the cached answer of a hit."""
    first = np.asarray(rec["first"])
    return _ms(np.percentile(first - np.asarray(rec["due"]), 95))


FUNCTIONS = {"latency_p50_ms": latency_p50_ms,
             "latency_p95_ms": latency_p95_ms,
             "ttft_p95_ms": ttft_p95_ms}


def metrics(bench: dict, cell: dict, rec: dict, setup_s: float) -> dict:
    """The cell's end-to-end metrics, named and united as BENCHMARK.json
    names them."""
    out = {}
    for m in bench["end_to_end"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = setup_s if m["name"] == "setup_s" else \
            FUNCTIONS[m["name"]](rec)
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
