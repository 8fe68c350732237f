"""Driver for a standalone semantic cache: ``SemanticCache`` as the entry.

Open loop.  Request ``i`` of the window is due at ``due[i]``; its lookup
runs then (or as soon as the harness is free), and a miss's response is
admitted ``gen_delay_s`` after the due time, as a chat service admits the
answer its model produced.  The order of lookups and admissions is the
schedule's, never the clock's: lookups that are due, consecutive in the
schedule and not separated by an admission go to the program as one
``lookup_batch`` (at most ``max_lookup_batch``), which decides exactly as
the same lookups one by one, since lookups never admit.  The loop starts
``lead_s`` before the window, so that admissions are already flowing when
it opens; requests due in that lead-in are served but not counted.

Set-up fills the store by admitting the stream's first messages in order
until it holds ``capacity`` entries, so that RAC's topics, anchors and
dependency links are those of real traffic, then warms every lookup width
the window can send and the eviction scorer at the topic-table sizes the
window can reach.
"""
from __future__ import annotations

import heapq
import importlib
import sys
import time

import numpy as np

from counters import count_rac_value
from reference import cache as cache_ref
from reference.cache import cache_checks
from timing import DRAIN_S, Clock, Phases, annotate


class Cell:
    def __init__(self, cfg: dict, traffic: dict, *, seed: int,
                 seconds: float, rate: float | None = None):
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed
        self.seconds = float(seconds)
        self.rate = float(traffic["arrivals"]["rate"] if rate is None
                          else rate)
        self.cache = None
        self.drain_s = DRAIN_S
        self.lead = float(traffic["lead_s"])
        self.gen = importlib.import_module(
            f"generators.{traffic['generator']}")

    # ------------------------------------------------------------ set-up
    def setup(self, extra: int = 0):
        """Build, fill and warm; ``extra`` more messages are generated for
        further windows (the knee sweep)."""
        from repro.cache import CacheConfig, SemanticCache
        c = self.cfg["cache"]
        cap = int(c["capacity"])
        ph = Phases()
        self.due = self.gen.arrivals(self.traffic["arrivals"],
                                     self.gen.rng_for(self.seed, 3),
                                     self.seconds, self.rate, self.lead)
        self.msgs, self.n_fill = self.gen.stream(
            self.traffic, self.seed, cap, len(self.due) + extra)
        self.win = np.arange(self.n_fill, self.n_fill + len(self.due))
        ph.mark("stream")
        self.cache = SemanticCache(CacheConfig(
            capacity=cap, dim=int(c["dim"]), tau_hit=float(c["tau_hit"]),
            backend=c["backend"], policy=c["policy"],
            policy_kwargs=dict(c["policy_kwargs"])))
        ph.mark("cache")
        emb, cid = self.msgs["emb"], self.msgs["cid"]
        self.ops: list = []
        for i in range(self.n_fill):
            self.cache.admit(int(cid[i]), emb[i], payload=i)
            self.ops.append(("A", i, int(cid[i]), (), i))
        assert len(self.cache) == cap, (len(self.cache), cap)
        ph.mark(f"fill of {self.n_fill} admissions")
        self._warm()
        self.rac_calls: list = []
        count_rac_value(self.cache.policy, self.rac_calls)
        ph.mark("warm-up")
        print(f"[chipbench] set-up phases: {ph}", file=sys.stderr, flush=True)

    def _warm(self):
        """Every lookup width of the window, and the eviction scorer at the
        topic-table sizes the window can reach (the table doubles)."""
        emb = self.msgs["emb"]
        for b in range(1, int(self.traffic["max_lookup_batch"]) + 1):
            self.cache.peek_batch(emb[:b])
        pol = self.cache.policy
        n = len(self.cache) + 1                # residents while evicting
        t_rows = len(pol.table.tp_last)
        for rows in (t_rows, 2 * t_rows):
            self.cache.backend.rac_value(
                np.zeros(n), np.zeros(n, np.int64), np.zeros(rows),
                np.zeros(rows, np.int64), pol.alpha, 0)

    def reschedule(self, rate: float):
        """A new window at ``rate`` on the next messages (the knee sweep)."""
        self.due = self.gen.arrivals(self.traffic["arrivals"],
                                     self.gen.rng_for(self.seed, 4 + int(rate)),
                                     self.seconds, rate, self.lead)
        self.win = np.arange(self.next_msg, self.next_msg + len(self.due))
        assert self.win[-1] < len(self.msgs["cid"]), "sweep ran out of messages"

    # ------------------------------------------------------------ window
    def window(self) -> dict:
        cache, emb, cid = self.cache, self.msgs["emb"], self.msgs["cid"]
        gen_delay = float(self.traffic["gen_delay_s"])
        max_b = int(self.traffic["max_lookup_batch"])
        n = len(self.win)
        done = np.full(n, np.nan)
        hits = np.zeros(n, bool)
        admits: list[tuple[float, int]] = []   # (due, window index)
        self.rac_calls.clear()
        self.snap0 = cache.metrics_snapshot()
        clock = Clock(self.seconds, self.drain_s, self.lead)
        t0, end = clock.t0, clock.end
        li = 0
        while True:
            d_l = t0 + self.due[li] if li < n else np.inf
            d_a = t0 + admits[0][0] if admits else np.inf
            nxt = min(d_l, d_a)
            if nxt >= end:
                break
            now = clock.wait_until(nxt)
            if now > end + clock.drain_s:
                break
            if d_a <= d_l:
                _, k = heapq.heappop(admits)
                i = int(self.win[k])
                with annotate("chipbench/admit"):
                    ev = cache.admit(int(cid[i]), emb[i], payload=i)
                self.ops.append(("A", i, int(cid[i]), tuple(ev), i))
                continue
            j = li + 1
            while (j < n and j - li < max_b and t0 + self.due[j] <= now
                   and t0 + self.due[j] < d_a):
                j += 1
            idx = self.win[li:j]
            with annotate("chipbench/lookup_batch"):
                res = cache.lookup_batch(emb[idx], cids=cid[idx].tolist())
            t_done = time.perf_counter()
            for k, (i, r) in enumerate(zip(idx, res)):
                done[li + k] = t_done - t0
                hits[li + k] = r.hit
                if r.hit:
                    self.ops.append(("L", int(i), True, int(r.cid),
                                     float(r.sim), r.payload))
                else:
                    self.ops.append(("L", int(i), False, int(r.best_cid),
                                     float(r.best_sim), None))
                    heapq.heappush(admits,
                                   (self.due[li + k] + gen_delay, li + k))
            li = j
        t1 = time.perf_counter()
        unanswered = np.isnan(done)
        self.snap1 = cache.metrics_snapshot()
        self.next_msg = int(self.win[li - 1]) + 1 if li else int(self.win[0])
        done[np.isnan(done)] = t1 - t0
        # the lead-in's requests brought the loop to its steady state;
        # the window's are those due inside it
        w = self.due >= 0
        failed = int(np.sum(w & unanswered))
        return {"due": self.due[w], "done": done[w], "first": done[w],
                "hits": hits[w], "attempted": int(w.sum()), "failed": failed,
                "late_max_s": clock.late_max_s,
                "late": clock.late, "t0": t0,
                "t1": max(end, t1), "snap0": self.snap0,
                "snap1": self.snap1,
                "rac_value_calls": list(self.rac_calls)}

    # ------------------------------------------------------------ check
    def release(self):
        """Free the program's state before the reference runs."""
        self.cache = None

    def check(self, control: bool = False) -> list[dict]:
        """The numbers compared, each with its limit; with ``control`` each
        also carries the lower-precision control's reading."""
        c = self.cfg["cache"]
        rac = cache_ref.RAC.from_config(c)
        rep = cache_ref.replay(rac, self.ops, self.msgs["emb"], control)
        look = rep["look"]
        print(f"[chipbench] replayed {len(self.ops)} operations: "
              f"{len(look['op'])} lookups ({int(look['hit'].sum())} hits), "
              f"{rep['evictions']} evictions", file=sys.stderr, flush=True)
        out = cache_checks(rep, self.msgs["emb"], c, self.cfg["limits"],
                           control)
        return out
