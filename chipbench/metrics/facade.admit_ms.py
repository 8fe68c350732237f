"""facade.admit_ms: host time per admission inside ``SemanticCache``
(insert, RAC bookkeeping, eviction scoring and the evictions), ms: the
program's ``admit_s`` clock over its ``admissions`` count, across the
traced window."""


def read(ctx):
    a, b = ctx["rec"]["snap0"], ctx["rec"]["snap1"]
    n = b["admissions"] - a["admissions"]
    return None if n <= 0 else (b["admit_s"] - a["admit_s"]) / n * 1e3
