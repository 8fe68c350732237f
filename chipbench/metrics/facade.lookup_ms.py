"""facade.lookup_ms: host time per lookup inside ``SemanticCache``, ms:
the program's own ``lookup_s`` clock over its ``lookups`` count, across the
traced window."""


def read(ctx):
    a, b = ctx["rec"]["snap0"], ctx["rec"]["snap1"]
    n = b["lookups"] - a["lookups"]
    return None if n <= 0 else (b["lookup_s"] - a["lookup_s"]) / n * 1e3
