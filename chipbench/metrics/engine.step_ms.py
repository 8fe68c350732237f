"""engine.step_ms: the traced window's wall time per decode step of the
serving engine (``stats["batches"]``), ms: the engine loop's pace, host
and device together."""


def read(ctx):
    steps = ctx["rec"].get("steps", 0)
    return None if not steps else ctx["trace"]["window_s"] / steps * 1e3
