"""facade.hit_ratio: hits over lookups decided in the traced window, from
the program's own counters."""


def read(ctx):
    a, b = ctx["rec"]["snap0"], ctx["rec"]["snap1"]
    n = b["lookups"] - a["lookups"]
    return None if n <= 0 else (b["hits"] - a["hits"]) / n
