"""engine.host_gap_ms: the chip's idle time charged to the program's
``rac/...`` spans (``trace.py``: the innermost span open at a gap's
midpoint) per decode step of the serving engine (``stats["batches"]``),
ms: the host work of one step of ``ServingEngine.run`` that the device
waits for (dispatch, the slot loop with its admissions, the queue fill).

None where the trace holds no device op (a CPU rehearsal), no step ran,
or the engine marks no ``rac/engine/`` phase."""

ENGINE = "rac/engine/"


def read(ctx):
    tr, steps = ctx["trace"], ctx["rec"].get("steps", 0)
    gaps = tr["idle_gaps"]
    if (not steps or tr["busy_s"] <= 0
            or not any(n.startswith(ENGINE) for n, _ in gaps)):
        return None
    return sum(s for n, s in gaps if n.startswith("rac/")) / steps * 1e3
