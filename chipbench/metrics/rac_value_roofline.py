"""rac_value_roofline: the RAC Eq. 1 victim-scoring program's share of its
roofline, %.

The program (``jit_rac_value``) takes the slots' TSI (f32[N]) and topic
ids (s32[N]) and the topic tables' TP and last-arrival time (f32[T],
s32[T]); it gathers each slot's topic entries in XLA and scores the slots
in a Pallas call (f32[N] out).  Its least bytes are what it must read and
write once, 12 N + 8 T; a handful of flops per slot, so the bytes bind.
N and T of every call come from the harness's count of the policy's
scoring calls in the window; the time is the device time of the
program's runs in the trace, the gather included.  The share is the
calls' least time over that device time."""

PROGRAM = "jit_rac_value"


def least_bytes(slots: int, topics: int) -> int:
    return 12 * slots + 8 * topics


def read(ctx):
    calls = ctx["rec"].get("rac_value_calls") or []
    runs, spent = ctx["trace"]["programs"].get(PROGRAM, (0, 0.0))
    if not calls or spent <= 0:
        return None
    if runs != len(calls):
        raise ValueError(f"{PROGRAM}: {runs} runs in the trace, "
                         f"{len(calls)} scoring calls counted")
    bw = ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * sum(least_bytes(n, t) for n, t in calls) / bw / spent
