"""The yardstick's arithmetic against numbers worked out by hand."""
import importlib.util
import json
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


V5E = {"bf16_flops": 1.97e14, "hbm_bytes_per_s": 8.19e11}


def test_peaks_table_has_v5e_and_refuses_unknown_kinds():
    p = xtrace.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 1.97e14 and p["int8_ops"] == 3.93e14
    assert p["hbm_bytes_per_s"] == 8.19e11 and p["hbm_bytes"] == 1.6e10
    with pytest.raises(KeyError):
        xtrace.peaks_for("TPU v9 imaginary")


def test_sim_top1_bytes_flops_and_bound_at_the_cell_shape():
    m = metric("sim_top1_roofline")
    # a 65,536-row store (+1 spare slot) padded to 512-row tiles: 66,048 rows
    results = [("f32", (128, 1)), ("s32", (128, 1))]
    operands = [("s32", (1,)), ("f32", (128, 384)), ("f32", (66048, 384))]
    # 66048*384*4 + 128*384*4 + 4 + 2*128*4
    assert m.call_bytes(results, operands) == 101_449_728 + 196_608 + 4 + 1024
    assert m.call_flops(results, operands) == 2 * 128 * 66048 * 384
    least = m.least_s(results, operands, V5E)
    assert least == pytest.approx(101_647_364 / 8.19e11)     # bytes bind
    assert least > 2 * 128 * 66048 * 384 / 1.97e14


def test_rac_value_bytes_and_share():
    m = metric("rac_value_roofline")
    # 65,537 slots (tsi f32, topic id s32 in; value f32 out) and a topic
    # table of 4,096 rows (TP f32, last arrival s32)
    assert m.least_bytes(65537, 4096) == 65537 * 12 + 4096 * 8 == 819_212
    ctx = {"rec": {"rac_value_calls": [(65537, 4096)] * 10},
           "trace": {"programs": {"jit_rac_value": [10, 0.011]}},
           "peaks": V5E}
    # ten calls of 819,212 bytes at 819 GB/s in 11 ms of device time
    assert m.read(ctx) == pytest.approx(100 * 8_192_120 / 8.19e11 / 0.011)
    ctx["trace"]["programs"] = {}
    assert m.read(ctx) is None
    ctx["trace"]["programs"] = {"jit_rac_value": [9, 0.011]}
    with pytest.raises(ValueError):
        m.read(ctx)


def test_smollm_360m_decode_flops_per_token():
    m = metric("decode_mfu")
    cfg = json.loads((ROOT / "configs" / "served-smollm360m.json").read_text())
    # per layer: q 960*960, k/v 2*960*320, o 960*960, MLP 3*960*2560
    per_layer = 921_600 + 614_400 + 921_600 + 7_372_800
    assert m.matmul_params(cfg["model"]) == 32 * per_layer + 49152 * 960
    assert m.matmul_params(cfg["model"]) == 361_758_720
    assert m.flops(cfg["model"], 1, 0) == pytest.approx(0.7235e9, rel=1e-4)
    # attention: 4 * 15 heads * 64 * 32 layers per context position
    assert m.flops(cfg["model"], 0, 10) == 4 * 15 * 64 * 32 * 10


def test_kernel_signatures():
    sim = ('%_sim_top1_jit.1 = (f32[128,1]{1,0:T(8,128)S(1)}, s32[128,1]'
           '{1,0:T(8,128)S(1)}) custom-call(s32[1]{0:T(128)} %bitcast.1, '
           'f32[128,384]{1,0:T(8,128)S(1)} %pad.0, f32[4608,384]{1,0:T(8,128)'
           'S(1)} %pad.2), custom_call_target="tpu_custom_call"')
    label, res, ops = xtrace.kernel_call(sim)
    assert label == "sim_top1"
    assert ops[2] == ("f32", (4608, 384)) and res[1] == ("s32", (128, 1))
    rv = ('%rac_value.3 = f32[520,128]{1,0} custom-call(f32[520,128]{1,0} '
          '%a, f32[520,128]{1,0} %b, f32[520,128]{1,0} %c), '
          'custom_call_target="tpu_custom_call"')
    assert xtrace.kernel_call(rv)[0] == "rac_value"
    assert xtrace.kernel_call("%pad.2 = f32[8]{0} pad(f32[4]{0} %x)") is None
