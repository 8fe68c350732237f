"""The DeepSeek-V2-Lite served cell at a tiny size on the CPU, its metric
readers against numbers worked out by hand, and its traffic files."""
import importlib.util
import json
from pathlib import Path

import jax
import pytest

import run
from small import ENGINE, args

ROOT = Path(__file__).resolve().parents[1]
SEED = 2 ** 31 + 321
V5E = {"bf16_flops": 1.97e14, "hbm_bytes_per_s": 8.19e11}

#: DeepSeek-V2-shaped at a tiny size: 3 layers (1 dense + 2 MoE), 8
#: experts top-2 with 2 shared, the published YaRN and routing options
DSV2 = {"config": {"hidden_size": 128, "num_attention_heads": 4,
                   "num_key_value_heads": 4, "kv_lora_rank": 64,
                   "qk_rope_head_dim": 16, "qk_nope_head_dim": 32,
                   "v_head_dim": 32, "intermediate_size": 256,
                   "moe_intermediate_size": 64, "n_routed_experts": 8,
                   "num_experts_per_tok": 2, "num_hidden_layers": 3,
                   "vocab_size": 512, "torch_dtype": "float32",
                   "cache": ENGINE["config"]["cache"],
                   "engine": ENGINE["config"]["engine"],
                   "check": ENGINE["config"]["check"]},
        "traffic": ENGINE["traffic"]}


def metric(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), ROOT / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published():
    return json.loads((ROOT / "configs" / "served-dsv2lite.json").read_text())


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_reaches_correct(trace, capsys):
    cells = []
    res = run.run(args("served-dsv2lite.chat", SEED, trace=trace),
                  require_tpu=False, overrides=DSV2,
                  driver_hook=lambda c: cells.append(
                      jax.tree.leaves(c.params)))
    # the program's weights were freed before the reference made its own
    assert all(a.is_deleted() for a in cells[0])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line == json.loads(json.dumps(res))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["checks"]) == {"lookup_err", "victim_err", "logit_gap"}
    bench = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
    if not trace:
        want = {m["name"] for m in bench["end_to_end"]
                if "served-dsv2lite.chat" in m.get("workloads",
                                                    ["served-dsv2lite.chat"])}
        assert set(line["metrics"]) == want
    else:
        # the CPU has no peaks: the counter's metric is there, the trace's
        # shares are not
        assert line["metrics"]["moe.load_imbalance"]["value"] >= 1.0


def test_decode_flops_per_token_at_the_published_widths():
    m = metric("dsv2lite.decode_mfu")
    cfg = published()
    # per layer: q 2048*16*192, kv_a 2048*576, absorbed kv_b 2*16*128*512,
    # o 16*128*2048; dense 3*2048*10944 once; 8 MoE layers of 8 experts'
    # 3*2048*1408 (6 routed + 2 shared) and the router 2048*64; the head
    attn = 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304
    moe = 8 * 3 * 2048 * 1408 + 2048 * 64
    assert m.macs_per_token(cfg) == (9 * attn + 67_239_936 + 8 * moe
                                     + 2048 * 102_400) == 955_514_880
    # attention: 2 * 16 heads * (2 * 512 + 64) per position, 9 layers
    assert m.flops(cfg, 0, 10) == 2 * 16 * 1088 * 9 * 10
    ctx = {"cfg": cfg, "rec": {"slot_steps": 1000, "ctx_tokens": 50_000},
           "trace": {"window_s": 2.0}, "peaks": V5E}
    assert m.read(ctx) == pytest.approx(
        100 * m.flops(cfg, 1000, 50_000) / (2.0 * 1.97e14))
    assert m.read({**ctx, "rec": {}}) is None


def test_step_roofline_bytes():
    m = metric("dsv2lite.step_roofline")
    cfg = published()
    assert m.kv_bytes_per_position(cfg) == 1152
    assert m.expert_bytes(cfg) == 3 * 2048 * 1408 * 2
    # everything but the routed experts and the token table, in bf16: the
    # 9 layers' attention and norms, the dense MLP, 8 x (shared experts +
    # router), the head and final norm, and 128 token rows
    attn = 13_763_072 + 4096
    other = 9 * attn + 67_239_936 + 8 * (17_301_504 + 131_072) \
        + 209_715_200 + 2048 + 128 * 2048
    assert m.fixed_bytes(cfg, 128) == 2 * other
    rec = {"steps": 10, "max_batch": 128, "moe_experts_touched": 5000,
           "ctx_tokens": 20_000}
    least = 10 * 2 * other + 5000 * 17_301_504 + 20_000 * 9 * 1152
    assert m.least_bytes(cfg, rec) == least
    ctx = {"cfg": cfg, "rec": rec, "peaks": V5E,
           "trace": {"programs": {"jit_decode_step": [10, 0.2]}}}
    assert m.read(ctx) == pytest.approx(100 * least / 8.19e11 / 0.2)
    ctx["trace"]["programs"]["jit_decode_step"] = [9, 0.2]
    with pytest.raises(ValueError):
        m.read(ctx)
    # the parent's program has no MoE counters: nothing to read
    assert m.read({**ctx, "rec": {"steps": 10, "max_batch": 128,
                                  "ctx_tokens": 1}}) is None


def test_load_imbalance():
    m = metric("moe.load_imbalance")
    cfg = published()
    # 2 layer-steps of 768 pairs over 64 experts (12 a mean expert), the
    # busiest given 20 and 22 rows
    ctx = {"cfg": cfg, "rec": {"moe_rows": 1536, "moe_max_load": 42}}
    assert m.read(ctx) == pytest.approx(42 / 24)
    assert m.read({"cfg": cfg, "rec": {}}) is None


@pytest.mark.parametrize("child,parent", [
    ("chat-burst", "chat"), ("chat-served-dsv2lite", "chat-served")])
def test_traffic_differs_from_its_parent_only_in_arrivals(child, parent):
    """Every value but the arrivals (and the prose that describes them) is
    the parent mix's."""
    def load(name):
        return json.loads((ROOT / "traffic" / f"{name}.json").read_text())
    a, b = load(child), load(parent)
    assert set(a) == set(b)
    for k in a:
        if k in ("sources", "assumed"):
            assert {j: v for j, v in a[k].items() if j != "arrivals"} == \
                {j: v for j, v in b[k].items() if j != "arrivals"}
        elif k not in ("arrivals", "about"):
            assert a[k] == b[k], k
    assert a["arrivals"] != b["arrivals"]


def test_reference_copy_is_the_repository_reference():
    here = ROOT / "reference" / "deepseek_v2.py"
    src = ROOT.parent / "src" / "repro" / "models" / "deepseek_v2_ref.py"
    assert here.read_text() == src.read_text()


def test_configuration_holds_the_published_config():
    cfg = published()
    assert cfg["num_hidden_layers"] == 9 and cfg["reduced"] == [
        "num_hidden_layers"] and cfg["published"]["num_hidden_layers"] == 27
    pub = {"hidden_size": 2048, "intermediate_size": 10944,
           "moe_intermediate_size": 1408, "n_routed_experts": 64,
           "n_shared_experts": 2, "num_experts_per_tok": 6,
           "first_k_dense_replace": 1, "kv_lora_rank": 512,
           "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
           "v_head_dim": 128, "vocab_size": 102400, "norm_topk_prob": False,
           "routed_scaling_factor": 1, "q_lora_rank": None}
    assert {k: cfg[k] for k in pub} == pub
    assert cfg["rope_scaling"]["factor"] == 40
