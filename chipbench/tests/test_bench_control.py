"""The lower-precision control, put in the program's place, fails the
comparison that decides ``correct`` (at a tiny size on the CPU; the
readings at the cells' own sizes on the chip are in PERF.md)."""
import gc

import pytest

import run
from small import ENGINE, FACADE


@pytest.mark.parametrize("cell,small", [("semcache.chat", FACADE),
                                        ("served.chat", ENGINE)])
def test_control_fails_where_the_program_passes(cell, small):
    _, _, cfg, traffic = run.load_cell(cell)
    cfg = run.merge(cfg, small["config"])
    traffic = run.merge(traffic, small["traffic"])
    drv = run.load_module(run.HERE / "drivers" / f"{cfg['driver']}.py")
    c = drv.Cell(cfg, traffic, seed=2 ** 31 + 77, seconds=4.0)
    c.setup()
    c.window()
    c.release()
    gc.collect()
    checks = c.check(control=True)
    assert all(ch["value"] <= ch["limit"] for ch in checks)
    assert any(ch["control"] > ch["limit"] for ch in checks)
