"""Counts the harness keeps of the program's calls, for per-layer metrics
that need each call's sizes."""
from __future__ import annotations


def count_rac_value(policy, sink: list) -> None:
    """Append (slots, topic-table rows) of every RAC Eq. 1 scoring call
    the policy makes through its device scorer to ``sink``."""
    score = policy.value_backend
    if score is None:
        return

    def counted(tsi, tids, tp_last, t_last, alpha, t_now):
        sink.append((len(tsi), len(tp_last)))
        return score(tsi, tids, tp_last, t_last, alpha, t_now)

    policy.value_backend = counted
