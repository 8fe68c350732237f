"""policy.victim_ms: host time per eviction inside the policy's victim
choice (RAC: Eq. 1 values of every resident, the ``rac_value`` round trip
included, and the ordering), ms: the facade's ``victim_s`` clock (the
``rac/victim`` span) over its ``evictions`` count, across the traced
window.  None where the window evicts nothing or the program keeps no
``victim_s``."""


def read(ctx):
    a, b = ctx["rec"]["snap0"], ctx["rec"]["snap1"]
    n = b["evictions"] - a["evictions"]
    if n <= 0 or "victim_s" not in b:
        return None
    return (b["victim_s"] - a["victim_s"]) / n * 1e3
