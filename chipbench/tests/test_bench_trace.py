"""The trace reduction, on a trace recorded on a TPU v5e (one chip: the
semantic cache at 4,096 rows, 57 lookup batches in a 0.6 s window) and on
hand-made events."""
from pathlib import Path

import pytest

import trace as xtrace

DATA = Path(__file__).resolve().parent / "data" / "semcache_probe.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return xtrace.reduce(xtrace.load(DATA))


def test_window_busy_and_idle(reduced):
    assert reduced["window_s"] == pytest.approx(0.603212366)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    idle = sum(v for _, v in reduced["idle_gaps"])
    assert idle + reduced["busy_s"] == pytest.approx(reduced["window_s"])
    names = [n for n, _ in reduced["idle_gaps"]]
    assert names[0] == "chipbench/idle_wait"        # an open loop, lightly
    assert "rac/sim_top1" in names                  # loaded: mostly waiting


def test_kernels_and_device_ops(reduced):
    calls = reduced["kernels"]["sim_top1"]
    assert len(calls) == 57
    _, res, ops = calls[0]
    assert ops == [("s32", (1,)), ("f32", (128, 384)), ("f32", (4608, 384))]
    ops_by_name = dict(reduced["device_ops"])
    assert ops_by_name["jit__sim_top1_jit/sim_top1"] == pytest.approx(
        sum(c[0] for c in calls))
    assert reduced["device_ops"][0][0] == "jit__sim_top1_jit/sim_top1"
    assert sum(ops_by_name.values()) >= reduced["busy_s"] - 1e-9
    runs, seconds = reduced["programs"]["jit__sim_top1_jit"]
    assert runs == 57 and seconds >= sum(c[0] for c in calls)


def test_union_and_attribution_by_hand():
    tr = {"host": [(0, 1000, "chipbench/window"),
                   (100, 400, "chipbench/lookup_batch"),
                   (150, 350, "rac/sim_top1"),
                   (500, 1000, "chipbench/idle_wait")],
          "ops": {"/device:TPU:0": [(200, 250, "%a.1 = f32[1]{0} add()"),
                                    (240, 300, "%b.2 = f32[1]{0} mul()"),
                                    (900, 1100, "%c = f32[1]{0} neg()")]},
          "mods": {"/device:TPU:0": [(190, 310, "jit_f(123)"),
                                     (890, 1200, "jit_g(9)")]}}
    r = xtrace.reduce(tr)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(200e-9)          # 200-300, 900-1000
    idle = dict(r["idle_gaps"])
    # gaps: 0-200 (mid 100: lookup_batch), 300-900 (mid 600: idle_wait)
    assert idle == pytest.approx({"chipbench/lookup_batch": 200e-9,
                                  "chipbench/idle_wait": 600e-9})
    ops = dict(r["device_ops"])
    assert ops["jit_f/a"] == pytest.approx(50e-9)
    assert ops["jit_g/c"] == pytest.approx(100e-9)       # clipped to window
    assert r["programs"]["jit_f"] == [1, pytest.approx(120e-9)]
    assert r["programs"]["jit_g"] == [1, pytest.approx(110e-9)]


def test_a_loop_is_not_counted_beside_its_body():
    tr = {"host": [(0, 100, "chipbench/window")],
          "ops": {"/device:TPU:0": [(10, 60, "%while.3 = f32[1]{0} while()"),
                                    (10, 30, "%fusion.1 = f32[1]{0} fusion()"),
                                    (30, 60, "%copy.2 = f32[1]{0} copy()")]},
          "mods": {"/device:TPU:0": [(5, 70, "jit_step(1)")]}}
    r = xtrace.reduce(tr)
    assert r["busy_s"] == pytest.approx(50e-9)
    assert dict(r["device_ops"]) == pytest.approx(
        {"jit_step/fusion": 20e-9, "jit_step/copy": 30e-9})
