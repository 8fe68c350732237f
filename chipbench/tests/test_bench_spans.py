"""The readers of the program's spans and counters, on hand-made idle gaps
and snapshots: the values worked out by hand, and None wherever what they
read is absent (a CPU rehearsal, a program without the spans or counters,
a window with no evictions or uploads)."""
import importlib.util
from pathlib import Path

import pytest

import trace as xtrace

ROOT = Path(__file__).resolve().parents[1]


def metric(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), ROOT / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ctx(gaps=(), busy=1.0, steps=0, snap0=None, snap1=None):
    return {"trace": {"idle_gaps": [list(g) for g in gaps], "busy_s": busy,
                      "window_s": 10.0, "programs": {}},
            "rec": {"steps": steps, "snap0": snap0 or {},
                    "snap1": snap1 or {}}}


SEMCACHE = [("chipbench/idle_wait", 18.0), ("rac/upload", 16.0),
            ("rac/victim", 6.0), ("rac/lookup_batch", 2.0),
            ("chipbench/admit", 0.5), ("host: no span", 0.5)]
SERVED = [("rac/engine/slots", 9.0), ("rac/engine/dispatch", 6.0),
          ("chipbench/engine_run", 0.4), ("rac/engine/sync", 0.5),
          ("rac/fused_decide", 0.1), ("chipbench/idle_wait", 3.0)]
# what a program without phase spans leaves (the served cell's idle)
NO_PHASES = [("chipbench/engine_run", 21.1), ("rac/fused_decide", 0.025),
             ("chipbench/idle_wait", 0.02)]


def test_unattributed_idle_share():
    m = metric("device.unattributed_idle_share")
    # 1.0 s of the 25.0 s not spent waiting is under no program span
    assert m.read(ctx(SEMCACHE)) == pytest.approx(1.0 / 25.0)
    assert m.read(ctx(SERVED)) == pytest.approx(0.4 / 16.0)
    assert m.read(ctx(NO_PHASES)) is None
    assert m.read(ctx(SEMCACHE, busy=0.0)) is None          # no device op
    assert m.read(ctx([("chipbench/idle_wait", 1.0)])) is None


def test_engine_host_gap_ms():
    m = metric("engine.host_gap_ms")
    # 15.6 s under rac/ spans over 4,000 steps
    assert m.read(ctx(SERVED, steps=4000)) == pytest.approx(3.9)
    assert m.read(ctx(SERVED, steps=0)) is None
    assert m.read(ctx(SERVED, steps=4000, busy=0.0)) is None
    assert m.read(ctx(NO_PHASES, steps=4000)) is None
    assert m.read(ctx(SEMCACHE, steps=4000)) is None          # no engine


def disp(upload_s, uploads):
    return {"dispatch": {"launches": 9, "host_syncs": 9, "kernel_s": 1.0,
                         "upload_s": upload_s, "uploads": uploads}}


def test_backend_upload_ms():
    m = metric("backend.upload_ms")
    assert m.read(ctx(snap0=disp(1.0, 10), snap1=disp(9.0, 810))) == \
        pytest.approx(10.0)
    assert m.read(ctx(snap0=disp(1.0, 10), snap1=disp(1.0, 10))) is None
    old = {"dispatch": {"launches": 9, "host_syncs": 9, "kernel_s": 1.0}}
    assert m.read(ctx(snap0=old, snap1=old)) is None
    assert m.read(ctx()) is None


def test_policy_victim_ms():
    m = metric("policy.victim_ms")
    a = {"evictions": 100, "victim_s": 1.0}
    assert m.read(ctx(snap0=a, snap1={"evictions": 600, "victim_s": 6.5})) \
        == pytest.approx(11.0)
    assert m.read(ctx(snap0=a, snap1=a)) is None
    assert m.read(ctx(snap0={"evictions": 1},
                      snap1={"evictions": 9})) is None        # no victim_s


def test_program_spans_take_the_gaps_they_hold():
    # the harness's admit span holds the program's admit, which holds the
    # victim choice, which holds the rac_value round trip
    tr = {"host": [(0, 1000, "chipbench/window"),
                   (0, 300, "chipbench/admit"),
                   (10, 290, "rac/admit"),
                   (20, 60, "rac/arrive"),
                   (100, 280, "rac/victim"),
                   (150, 250, "rac/rac_value"),
                   (300, 700, "chipbench/lookup_batch"),
                   (310, 690, "rac/lookup_batch"),
                   (320, 500, "rac/upload"),
                   (500, 600, "rac/sim_top1"),
                   (700, 1000, "chipbench/idle_wait")],
          "ops": {"/device:TPU:0": [(200, 220, "%rac_value.1 = f32[8]{0} "
                                              "custom-call()"),
                                    (520, 560, "%sim_top1.1 = f32[8]{0} "
                                               "custom-call()")]},
          "mods": {"/device:TPU:0": [(200, 220, "jit_rac_value(1)"),
                                     (520, 560, "jit__sim_top1_jit(2)")]}}
    r = xtrace.reduce(tr)
    idle = dict(r["idle_gaps"])
    # gaps 0-200 (mid 100: rac/victim), 220-520 (mid 370: rac/upload),
    # 560-1000 (mid 780: chipbench/idle_wait)
    assert idle == pytest.approx({"rac/victim": 200e-9,
                                  "rac/upload": 300e-9,
                                  "chipbench/idle_wait": 440e-9})
    c = {"trace": r, "rec": {"steps": 0}}
    assert metric("device.unattributed_idle_share").read(c) == 0.0
