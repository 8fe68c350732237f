"""Plain reference of a RAC-managed semantic cache.

Written from the paper (arXiv:2602.21547, Alg. 1-5, Def. 1-2, §4.2) and
independent of the program: dictionaries and float64 arrays, one entry at
a time, nothing vectorised beyond numpy expressions.  It replays the
operations a run performed, in their order, and at every step judges the
program's answer against its own state:

* a lookup names a resident row and its cosine: the reference scores the
  query against every row resident at that step (float32 at
  ``precision=HIGHEST`` on the device, after the run) and reads how far
  the program's answer is off: the error of its cosine, how much better
  the best resident row is, and how far a hit lies below ``tau_hit`` (or a
  miss's best row above it).  A wrong payload counts 1.
* an admission that evicts names its victim: the reference computes the
  Eq. 1 value of every resident entry in float64 and reads the victim's
  value above the smallest, relative to the smallest.

Then it applies the program's decision (its hit, its victim), as a model
reference is run over the served tokens, so one early difference does not
re-colour every later step.

Semantics that the paper leaves open are fixed as the repository's policy
documents them: a topic's representative is its TSI-max member (ties: the
older last access, then the smaller cid), refreshed lazily after its
anchor leaves; routing takes the best representative of the K-shortlist,
ties to the smaller topic id; DetectParent scores sim/(t-k) over resident
same-topic members inside the look-back window, ties to the smaller cid;
an emptied topic's TP state is remembered (FIFO-bounded) and revived by a
later arrival routed to its representative; lifetime freq/dep of evicted
entries are remembered (FIFO-bounded, dropped in batches of a sixteenth);
the value is TP x TSI normalised by the topic's resident TSI mass.
"""
from __future__ import annotations

from collections import OrderedDict, deque

import numpy as np

try:                                       # numpy's bfloat16 ships with jax
    from ml_dtypes import bfloat16 as _BF16
except ImportError:                        # pragma: no cover
    import jax.numpy as _jnp
    _BF16 = _jnp.bfloat16


class RAC:
    """Reference RAC state over a store of at most ``capacity`` entries."""

    def __init__(self, capacity: int, dim: int, *, tau_route: float,
                 tau_edge: float, alpha: float, lam: float, lookback: int,
                 shortlist_k: int, ghost_limit: int = 1 << 18,
                 ghost_topic_limit: int = 4096):
        self.capacity = capacity
        self.tau_route = tau_route
        self.tau_edge = tau_edge
        self.alpha = alpha
        self.lam = lam
        self.lookback = lookback
        self.k = shortlist_k
        self.ghost_limit = ghost_limit
        self.ghost_topic_limit = ghost_topic_limit
        n = capacity + 1
        # per-slot entry state (slots are this reference's own)
        self.emb = np.zeros((n, dim), np.float32)
        self.cid = np.full(n, -1, np.int64)
        self.freq = np.zeros(n)
        self.dep = np.zeros(n)
        self.tsi = np.zeros(n)
        self.topic = np.zeros(n, np.int64)
        self.last_t = np.zeros(n, np.int64)
        self.slot_of: dict[int, int] = {}
        self.free = list(range(n - 1, -1, -1))
        # topics: tid -> [anchor cid, member cids, anchor left]; per topic
        # id its representative row and its TP state (kept after the topic
        # empties: they are what a revival restores)
        self.topics: dict[int, list] = {}
        self.rep = np.zeros((1024, dim), np.float32)
        self.tp = np.zeros(1024)
        self.tl = np.zeros(1024, np.int64)
        self.stale: set[int] = set()           # topics whose anchor left
        self.next_tid = 0
        self.ghosts: OrderedDict[int, tuple] = OrderedDict()
        self.ghost_topics: OrderedDict[int, None] = OrderedDict()
        self.par: dict[int, int] = {}
        # the last arrivals: every entry touched inside the look-back window
        # is among them
        self.recent: deque[int] = deque(maxlen=lookback)

    @classmethod
    def from_config(cls, c: dict) -> "RAC":
        """From a configuration's ``cache`` group."""
        kw = c["policy_kwargs"]
        return cls(int(c["capacity"]), int(c["dim"]),
                   tau_route=kw["tau_route"], tau_edge=kw["tau_edge"],
                   alpha=kw["alpha"], lam=kw["lam"], lookback=kw["lookback"],
                   shortlist_k=kw["shortlist_k"])

    # ------------------------------------------------------------ topics
    def _refresh(self, tid: int):
        """A topic whose anchor left takes its TSI-max member (ties: the
        older last access, then the smaller cid)."""
        top = self.topics[tid]
        if not top[2]:
            return
        best, key = -1, None
        for c in top[1]:
            s = self.slot_of[c]
            k = (self.tsi[s], -self.last_t[s], -c)
            if key is None or k > key:
                best, key = c, k
        if best >= 0:
            self.rep[tid] = self.emb[self.slot_of[best]]
            top[0] = best
        top[2] = False
        self.stale.discard(tid)

    def _route(self, e: np.ndarray) -> int:
        if self.topics:
            for tid in list(self.stale):
                self._refresh(tid)
            tids = np.fromiter(self.topics, np.int64, len(self.topics))
            sims = self.rep[tids] @ e
            k = min(self.k, len(tids))
            short = np.argsort(-sims, kind="stable")[:k]
            best = max(short, key=lambda i: (sims[i], -tids[i]))
            if sims[best] >= self.tau_route:
                return int(tids[best])
        if self.ghost_topics:
            gids = np.fromiter(self.ghost_topics, np.int64,
                               len(self.ghost_topics))
            sims = self.rep[gids] @ e
            gi = int(np.argmax(sims))
            if sims[gi] >= self.tau_route:
                tid = int(gids[gi])
                del self.ghost_topics[tid]
                self.topics[tid] = [-1, set(), False]
                return tid
        return -1

    def _anchor_tsi(self, tid: int) -> float:
        src = self.topics[tid][0]
        if src < 0 or src not in self.slot_of:
            return -np.inf
        return float(self.tsi[self.slot_of[src]])

    def _set_anchor(self, tid: int, c: int):
        top = self.topics[tid]
        self.rep[tid] = self.emb[self.slot_of[c]]
        top[0] = c
        top[2] = False
        self.stale.discard(tid)

    def _new_topic(self, c: int, e: np.ndarray, t: int) -> int:
        tid = self.next_tid
        self.next_tid += 1
        if tid >= len(self.tp):
            self.rep = np.concatenate([self.rep, np.zeros_like(self.rep)])
            self.tp = np.concatenate([self.tp, np.zeros_like(self.tp)])
            self.tl = np.concatenate([self.tl, np.zeros_like(self.tl)])
        self.topics[tid] = [c, set(), False]
        self.rep[tid] = e
        self.tp[tid], self.tl[tid] = 0.0, t
        return tid

    # ------------------------------------------------------------ arrival
    def _parent(self, c: int, e: np.ndarray, tid: int, t: int) -> int:
        """DetectParent: the resident same-topic entry touched inside the
        look-back window with the best sim/(t-k), gated by tau_edge."""
        cands = []
        for o in set(self.recent):
            s = self.slot_of.get(o)
            if o != c and s is not None and self.topic[s] == tid \
                    and 0 < t - self.last_t[s] <= self.lookback:
                cands.append(o)
        if not cands:
            return -1
        cands = np.asarray(cands, np.int64)
        slots = np.asarray([self.slot_of[o] for o in cands], np.int64)
        dt = t - self.last_t[slots]
        sims = self.emb[slots] @ e
        gate = sims >= self.tau_edge
        if not gate.any():
            return -1
        score = sims[gate] / dt[gate].astype(np.float32)
        top = score.max()
        return int(cands[gate][score == top].min())

    def _arrive(self, c: int, e: np.ndarray, t: int, admit: bool):
        s = self.slot_of[c]
        if admit:
            g = self.ghosts.pop(c, None)
            self.freq[s] = g[0] if g is not None else 0.0
            self.dep[s] = g[1] if g is not None else 0.0
            self.tsi[s] = self.freq[s] + self.lam * self.dep[s]
            tid = self._route(e)
            if tid < 0:
                tid = self._new_topic(c, e, t)
            self.topic[s] = tid
            self.topics[tid][1].add(c)
        tid = int(self.topic[s])
        # Def. 1: TP decays by half every 1/alpha ticks, +1 per arrival
        self.tp[tid] = 0.5 ** (self.alpha * (t - self.tl[tid])) * self.tp[tid] + 1.0
        self.tl[tid] = t
        # Alg. 3: freq, then the one-parent dependency cascade
        self.freq[s] += 1.0
        self.tsi[s] = self.freq[s] + self.lam * self.dep[s]
        if c in self.par:
            p, new = self.par[c], False
        else:
            p, new = self._parent(c, e, tid, t), True
            self.par[c] = p
        if p >= 0 and p in self.slot_of:
            sp = self.slot_of[p]
            self.dep[sp] += self.freq[s] if new else 1.0
            self.tsi[sp] = self.freq[sp] + self.lam * self.dep[sp]
            pt = int(self.topic[sp])
            if pt in self.topics and self.topics[pt][0] != p \
                    and self.tsi[sp] > self._anchor_tsi(pt):
                self._set_anchor(pt, p)
        self.last_t[s] = t
        self.recent.append(c)
        if admit:
            self._refresh(tid)
            if self.topics[tid][0] < 0 or self.tsi[s] > self._anchor_tsi(tid):
                self._set_anchor(tid, c)

    # ------------------------------------------------------------ protocol
    def admit(self, c: int, e: np.ndarray, t: int) -> bool:
        """Insert ``c`` (False when it is already resident)."""
        if c in self.slot_of:
            return False
        s = self.free.pop()
        self.slot_of[c] = s
        self.emb[s] = e
        self.cid[s] = c
        self._arrive(c, e, t, admit=True)
        return True

    def hit(self, c: int, e: np.ndarray, t: int):
        self._arrive(c, e, t, admit=False)

    def over(self) -> bool:
        return len(self.slot_of) > self.capacity

    def values(self, t: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(cids, Eq. 1 values, slots) of every resident entry, float64."""
        slots = np.fromiter(self.slot_of.values(), np.int64,
                            len(self.slot_of))
        tid = self.topic[slots]
        mass = np.bincount(tid, weights=self.tsi[slots])
        tsi = self.tsi[slots] / np.maximum(mass[tid], 1e-9)
        decay = 0.5 ** (self.alpha * (t - self.tl[tid]))
        return self.cid[slots], decay * self.tp[tid] * tsi, slots

    def evict(self, c: int):
        s = self.slot_of[c]
        tid = int(self.topic[s])
        top = self.topics.get(tid)
        if top is not None:
            top[1].discard(c)
            if not top[1]:
                # an emptied topic is remembered (FIFO-bounded) with its
                # representative and TP state
                self.ghost_topics[tid] = None
                while len(self.ghost_topics) > self.ghost_topic_limit:
                    self.ghost_topics.popitem(last=False)
                del self.topics[tid]
                self.stale.discard(tid)
            elif top[0] == c:
                top[0] = -1
                top[2] = True
                self.stale.add(tid)
        self.ghosts[c] = (float(self.freq[s]), float(self.dep[s]), tid)
        if len(self.ghosts) > self.ghost_limit:
            drop = max(1, self.ghost_limit // 16,
                       len(self.ghosts) - self.ghost_limit)
            for _ in range(min(drop, len(self.ghosts))):
                old, _ = self.ghosts.popitem(last=False)
                self.par.pop(old, None)
        del self.slot_of[c]
        self.cid[s] = -1
        self.freq[s] = self.dep[s] = self.tsi[s] = 0.0
        self.free.append(s)


def replay(rac: RAC, ops: list, emb: np.ndarray,
           control: bool = False) -> dict:
    """Replay a run's operations through ``rac``.

    ``ops`` holds, in the order the program performed them,
    ``("A", msg, cid, evicted, payload)`` for an admission of message
    ``msg`` (its cid, the cids the program evicted, what it stored) and
    ``("L", msg, hit, cid, sim, payload)`` for a lookup (the row the
    program named, its cosine, and the payload a hit returned).

    Returns the residency intervals of every admitted row, the lookups to
    score on the device, and the worst relative victim gap.  With
    ``control`` it also reads the gap of the victim that values rounded to
    bfloat16 would pick (the lower-precision control)."""
    rows_msg, rows_start, rows_end = [], [], []
    live: dict[int, int] = {}              # cid -> row index
    payload: dict = {}                     # cid -> its latest payload
    look = {"op": [], "msg": [], "row": [], "hit": [], "sim": [],
            "bad": []}
    victim_err = ctrl_err = 0.0
    evictions = 0
    for t, op in enumerate(ops, start=1):
        if op[0] == "A":
            _, msg, c, evicted, stored = op
            payload[c] = stored
            if rac.admit(c, emb[msg], t):
                live[c] = len(rows_msg)
                rows_msg.append(msg)
                rows_start.append(t)
                rows_end.append(np.iinfo(np.int32).max)   # still resident
            evicted = list(evicted)
            while rac.over():
                evictions += 1
                cids, vals, slots = rac.values(t)
                if not evicted or evicted[0] not in rac.slot_of:
                    # no victim, or a victim that is not resident: the
                    # reference keeps to capacity with its own choice
                    victim_err = float("inf")
                    evicted = [int(cids[np.argmin(vals)])]
                v = evicted.pop(0)
                vmin = vals.min()
                scale = max(abs(vmin), 1e-300)
                mine = vals[np.flatnonzero(cids == v)[0]]
                victim_err = max(victim_err, float((mine - vmin) / scale))
                if control:
                    low = vals.astype(np.float32).astype(_BF16)
                    order = np.lexsort((cids, rac.last_t[slots], low))
                    ctrl_err = max(ctrl_err,
                                   float((vals[order[0]] - vmin) / scale))
                rac.evict(v)
                rows_end[live.pop(v)] = t
            if evicted:                    # evicted more than it had to
                victim_err = float("inf")
        else:
            _, msg, hit, c, sim, got = op
            row = live.get(c, -1)
            look["op"].append(t)
            look["msg"].append(msg)
            look["hit"].append(bool(hit))
            look["sim"].append(float(sim))
            look["row"].append(row)
            # a named row that is not resident, no row named over a
            # non-empty store, or a hit with another row's payload
            look["bad"].append(row < 0 and (c >= 0 or bool(rac.slot_of))
                               or (hit and payload.get(c) != got))
            if hit and c in rac.slot_of:
                rac.hit(c, emb[msg], t)
    return {"rows_msg": np.asarray(rows_msg, np.int64),
            "rows_start": np.asarray(rows_start, np.int64),
            "rows_end": np.asarray(rows_end, np.int64),
            "look": {k: np.asarray(v) for k, v in look.items()},
            "victim_err": victim_err, "victim_err_control": ctrl_err,
            "evictions": evictions}


def lookup_err(rep: dict, emb: np.ndarray, tau_hit: float,
               control: bool = False, chunk: int = 512) -> float:
    """The worst lookup of the replay, in cosine units (see the module
    docstring), scoring each query on the device against the rows
    resident at its step, in float32 at ``precision=HIGHEST``.

    ``control=True`` judges instead the answers of the same scan computed
    one precision step lower, ``precision=HIGH`` (three bfloat16 passes on
    a TPU; the CPU computes both alike): its best resident row and that
    row's cosine, in the program's place."""
    import jax
    import jax.numpy as jnp

    look = rep["look"]
    if len(look["op"]) == 0:
        return 0.0
    rows = jnp.asarray(emb[rep["rows_msg"]])
    start = jnp.asarray(rep["rows_start"], jnp.int32)
    end = jnp.asarray(rep["rows_end"], jnp.int32)

    @jax.jit
    def score(rows, start, end, q, op, row):
        s = jnp.dot(q, rows.T, precision=jax.lax.Precision.HIGHEST)
        live = (start[None, :] < op[:, None]) & (op[:, None] < end[None, :])
        best = jnp.max(jnp.where(live, s, -jnp.inf), axis=1)
        if control:
            low = jnp.where(live, jnp.dot(q, rows.T,
                                          precision=jax.lax.Precision.HIGH),
                            -jnp.inf)
            row = jnp.argmax(low, axis=1)
            sim = jnp.max(low, axis=1)
        else:
            sim = jnp.zeros(q.shape[0])
        mine = jnp.take_along_axis(s, jnp.maximum(row, 0)[:, None], 1)[:, 0]
        return best, mine, sim

    n = len(look["op"])
    best, mine, csim = np.empty(n), np.empty(n), np.empty(n)
    for i in range(0, n, chunk):
        sl = slice(i, min(n, i + chunk))
        k = sl.stop - sl.start
        pad = chunk - k
        q = np.pad(emb[look["msg"][sl]], ((0, pad), (0, 0)))
        op = np.pad(look["op"][sl], (0, pad)).astype(np.int32)
        row = np.pad(look["row"][sl], (0, pad)).astype(np.int32)
        b, m, cs = score(rows, start, end, jnp.asarray(q), jnp.asarray(op),
                         jnp.asarray(row))
        best[sl], mine[sl], csim[sl] = (np.asarray(x)[:k] for x in (b, m, cs))
    if control:
        sim, hit = csim, csim >= tau_hit
        has, bad = np.ones(n, bool), np.zeros(n, bool)
    else:
        sim, hit = look["sim"], look["hit"]
        has, bad = look["row"] >= 0, look["bad"].astype(bool)
    err = np.zeros(n)
    err[has] = np.maximum(np.abs(sim[has] - mine[has]),
                          best[has] - mine[has])
    err[hit & has] = np.maximum(err[hit & has], tau_hit - mine[hit & has])
    err[~hit] = np.maximum(err[~hit], best[~hit] - tau_hit)
    err[bad] = np.maximum(err[bad], 1.0)
    err[np.isnan(err)] = np.inf            # a cosine that is not a number
    return float(max(0.0, err.max()))


def cache_checks(rep: dict, emb: np.ndarray, c: dict, limits: dict,
                 control: bool = False) -> list[dict]:
    """``lookup_err`` and ``victim_err`` of a replay, with their limits
    (and the control's readings when ``control``)."""
    tau = float(c["tau_hit"])
    out = [{"name": "lookup_err", "value": lookup_err(rep, emb, tau),
            "limit": limits["lookup_err"]},
           {"name": "victim_err", "value": rep["victim_err"],
            "limit": limits["victim_err"]}]
    if control:
        out[0]["control"] = lookup_err(rep, emb, tau, control=True)
        out[1]["control"] = rep["victim_err_control"]
    return out
