"""Each driver end to end at a tiny size on the CPU: the shape of the last
line, and that the seed alone fixes the operation sequence."""
import json

import numpy as np
import pytest

import run
from small import ENGINE, FACADE, args

SEED = 2 ** 31 + 123


def last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell,small,trace", [
    ("semcache.chat", FACADE, 0), ("semcache.chat", FACADE, 1),
    ("served.chat", ENGINE, 0), ("served.chat", ENGINE, 1)])
def test_last_line(cell, small, trace, capsys):
    res = run.run(args(cell, SEED, trace=trace), require_tpu=False,
                  overrides=small)
    line = last_line(capsys)
    assert line == json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    bench, c, _, _ = run.load_cell(cell)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        layer = {m["name"] for m in bench["per_layer"]}
        assert set(line["metrics"]) <= layer
    else:
        want = {m["name"] for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])}
        assert set(line["metrics"]) == want
        assert all(m["value"] > 0 for m in line["metrics"].values())
    for ch in line["checks"].values():
        assert ch["value"] <= ch["limit"]


def test_no_tpu_is_an_error(capsys):
    with pytest.raises(run.BenchError, match="no TPU"):
        run.run(args("semcache.chat", SEED), overrides=FACADE)
    assert capsys.readouterr().out == ""


def _ops(max_b: int, seed: int):
    _, _, cfg, traffic = run.load_cell("semcache.chat")
    cfg = run.merge(cfg, FACADE["config"])
    traffic = run.merge(traffic, {"arrivals": {"rate": 1500.0},
                                  "max_lookup_batch": max_b})
    drv = run.load_module(run.HERE / "drivers" / "facade.py")
    c = drv.Cell(cfg, traffic, seed=seed, seconds=1.0)
    c.setup()
    rec = c.window()
    # kind, message, then the hit flag and row or the cid and evictions
    return [op[:4] for op in c.ops], rec


def test_grouping_does_not_change_the_operations():
    one, rec1 = _ops(1, SEED)
    many, rec32 = _ops(32, SEED)
    # the harness fell behind, so lookups did go in groups
    assert len(np.unique(rec32["done"])) < 0.9 * rec32["attempted"]
    assert len(np.unique(rec1["done"])) == rec1["attempted"]
    assert one == many
    assert rec1["hits"].sum() == rec32["hits"].sum() > 0
