"""device.unattributed_idle_share: the share of the chip's idle time, not
spent waiting for arrivals, that no program span explains.

``trace.py`` charges each idle gap of the window to the innermost host
span open at its midpoint.  Idle under ``chipbench/idle_wait`` is the
open loop waiting for the next due request; the rest is host work.  Of
that rest, the share charged to a harness span other than
``chipbench/idle_wait`` (the harness's own span around a call into the
program) or to no span at all: host time the program's ``rac/...`` spans
leave unnamed.

None where the trace holds no device op (a CPU rehearsal) or no gap falls
in one of the program's host-phase spans (``PHASES``; its older
``rac/...`` spans mark kernel launches alone): a program without them
leaves its host work to the harness's spans, and the share then says
nothing of the program."""

WAIT = "chipbench/idle_wait"
PHASES = ("rac/engine/", "rac/lookup_batch", "rac/admit", "rac/arrive",
          "rac/victim", "rac/rac_value", "rac/upload")


def read(ctx):
    tr = ctx["trace"]
    gaps = [(n, s) for n, s in tr["idle_gaps"] if n != WAIT]
    if tr["busy_s"] <= 0 or not any(n.startswith(PHASES) for n, _ in gaps):
        return None
    idle = sum(s for _, s in gaps)
    if idle <= 0:
        return None
    return sum(s for n, s in gaps if not n.startswith("rac/")) / idle
