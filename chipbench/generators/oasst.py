"""Chat traffic in conversation threads: threads, embeddings, prompts
and arrivals.

Threads follow the shape of the repository's ``oasst_style_trace``: each
thread opens in a Zipf-drawn topic, its root either repeats a popular
earlier root of that topic (a cross-user repeat, weighted by prior use) or
is new, and it grows a tree of follow-up turns.  Threads interleave by
start time.  The traffic file states what users do, and the generator
derives its draws from that: ``turns_mean`` user turns per conversation
(one, plus a Poisson draw), and ``repeat_share``, the share of all
messages that re-ask an earlier root, so a new thread repeats a root with
probability ``repeat_share x turns_mean``.  The embedding geometry is the
repository's calibrated one: two items of one topic sit ~0.70 apart, a
child ~0.79 from its parent, a re-ask (paraphrase) ~0.93 from the first
ask, so only re-asks clear a hit threshold of 0.85.

Everything is drawn from one ``numpy`` generator seeded by the run's seed,
in bulk where the structure allows, so a 70,000-message trace takes a
second or two of host time.
"""
from __future__ import annotations

import numpy as np

_SEED_MOD = 2 ** 63


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % _SEED_MOD, stream]))


def _unit(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def threads(p: dict, rng: np.random.Generator, n_messages: int):
    """Message order of ``n_messages`` interleaved threads.

    Returns (cid, topic, parent) int64 arrays in arrival order."""
    n_topics = int(p["n_topics"])
    w = np.arange(1, n_topics + 1, dtype=np.float64) ** -float(p["zipf_gamma"])
    topic_cdf = np.cumsum(w / w.sum())
    topic_ids = rng.permutation(n_topics)
    turns = float(p["turns_mean"])
    p_repeat = float(p["repeat_share"]) * turns
    if not 0.0 <= p_repeat <= 1.0:
        raise ValueError("repeat_share x turns_mean must lie in [0, 1]")
    # a topic's urn holds each of its roots once, plus once per thread that
    # used it, so a uniform draw picks a root in proportion to 1 + its uses
    urns: dict[int, list[int]] = {}
    ts, tp, cid, par = [], [], [], []
    clock, next_cid = 0.0, 0
    while len(ts) < int(n_messages * 1.2) + 16:
        clock += rng.exponential(1.0 / float(p["thread_rate"]))
        tix = min(int(np.searchsorted(topic_cdf, rng.random())), n_topics - 1)
        topic = int(topic_ids[tix])
        urn = urns.setdefault(topic, [])
        if urn and rng.random() < p_repeat:
            root = urn[int(rng.integers(0, len(urn)))]
            urn.append(root)
        else:
            root = next_cid
            next_cid += 1
            urn += (root, root)
        n = 1 + int(rng.poisson(turns - 1.0))
        frontier = [root]
        ts.append(clock), tp.append(topic), cid.append(root), par.append(-1)
        t = clock
        for _ in range(n - 1):
            t += rng.exponential(float(p["turn_gap"]))
            parent = (frontier[-1] if rng.random() > float(p["branch_prob"])
                      else frontier[int(rng.integers(0, len(frontier)))])
            ts.append(t), tp.append(topic), cid.append(next_cid)
            par.append(parent)
            frontier.append(next_cid)
            next_cid += 1
    order = np.argsort(np.asarray(ts), kind="stable")[:n_messages]
    return (np.asarray(cid, np.int64)[order], np.asarray(tp, np.int64)[order],
            np.asarray(par, np.int64)[order])


def embeddings(p: dict, rng: np.random.Generator, cid: np.ndarray,
               topic: np.ndarray, parent: np.ndarray) -> np.ndarray:
    """(n, dim) float32 unit rows: content embedding at a cid's first ask,
    a fresh paraphrase of it at every later ask."""
    dim = int(p["dim"])
    cos_t = float(np.sqrt(p["cos_topic"]))
    sin_t = float(np.sqrt(1.0 - cos_t * cos_t))
    cos_p = float(p["cos_para"])
    sin_p = float(np.sqrt(1.0 - cos_p * cos_p))
    share = float(p["dep_share"])
    f32 = np.float32
    cent = _unit(rng.standard_normal((int(p["n_topics"]), dim), dtype=f32))
    n_cid = int(cid.max()) + 1
    ctopic = np.zeros(n_cid, np.int64)
    cparent = np.full(n_cid, -1, np.int64)
    ctopic[cid] = topic
    cparent[cid] = parent            # a cid keeps one parent (its first ask)
    c = cent[ctopic]
    g = rng.standard_normal((n_cid, dim), dtype=f32)
    u = _unit(g - np.sum(g * c, axis=1, keepdims=True) * c)
    # a child shares part of its parent's tangent direction: one level of
    # the thread trees at a time, so every parent is finished first
    # (parents have smaller cids)
    depth = np.zeros(n_cid, np.int64)
    for k in np.flatnonzero(cparent >= 0).tolist():
        depth[k] = depth[cparent[k]] + 1
    for d in range(1, int(depth.max()) + 1 if n_cid else 1):
        k = np.flatnonzero(depth == d)
        v = _unit(f32(share) * u[cparent[k]] + f32(1.0 - share) * u[k])
        v -= np.sum(v * c[k], axis=1, keepdims=True) * c[k]
        u[k] = _unit(v)
    base = _unit(f32(cos_t) * c + f32(sin_t) * u)
    emb = base[cid]
    # occurrence index of each message's cid (0 at the first ask)
    order = np.argsort(cid, kind="stable")
    sc = cid[order]
    first = np.r_[0, np.flatnonzero(np.diff(sc)) + 1]
    occ_sorted = np.arange(len(sc)) - np.repeat(first, np.diff(np.r_[first, len(sc)]))
    occ = np.empty_like(occ_sorted)
    occ[order] = occ_sorted
    re = np.flatnonzero(occ > 0)
    if len(re):
        b = emb[re]
        g = rng.standard_normal((len(re), dim), dtype=f32)
        noise = _unit(g - np.sum(g * b, axis=1, keepdims=True) * b)
        emb[re] = _unit(f32(cos_p) * b + f32(sin_p) * noise)
    return emb.astype(np.float32)


def prompts(p: dict, rng: np.random.Generator, cid: np.ndarray,
            vocab: int) -> list[list[int]]:
    """Prompt tokens per message, fixed by the content id: one length
    (log-normal around the median, clipped) and one token row per cid."""
    q = p["prompt"]
    n_cid = int(cid.max()) + 1
    ln = np.exp(np.log(q["median"]) + q["sigma"] * rng.standard_normal(n_cid))
    ln = np.clip(np.rint(ln), q["min"], q["max"]).astype(np.int64)
    tok = rng.integers(2, vocab, size=(n_cid, int(q["max"])))
    return [tok[c, :ln[c]].tolist() for c in cid]


def arrivals(a: dict, rng: np.random.Generator, seconds: float,
             rate: float | None = None, lead: float = 0.0) -> np.ndarray:
    """Due times in [-lead, seconds), sorted: the window and the lead-in
    before it.  The count is fixed by the rate (rate x (lead + seconds)),
    so every seed offers the same amount of work.

    ``poisson``: the points of a Poisson process given their count
    (uniform order statistics).  ``gamma``: renewal gaps of the stated
    coefficient of variation, scaled to fill the span."""
    rate = float(a["rate"] if rate is None else rate)
    span = seconds + lead
    n = max(1, int(round(rate * span)))
    if a["process"] == "poisson":
        return np.sort(rng.uniform(-lead, seconds, n))
    if a["process"] == "gamma":
        k = 1.0 / float(a["cv"]) ** 2
        gaps = rng.gamma(k, 1.0, n + 1)
        return np.cumsum(gaps)[:n] / gaps.sum() * span - lead
    raise ValueError(f"unknown arrival process {a['process']!r}")


def build(p: dict, seed: int, n_messages: int) -> dict:
    """The message stream of one run: cid, topic, parent, emb."""
    cid, topic, parent = threads(p, rng_for(seed, 1), n_messages)
    emb = embeddings(p, rng_for(seed, 2), cid, topic, parent)
    return {"cid": cid, "topic": topic, "parent": parent, "emb": emb}


def stream(p: dict, seed: int, capacity: int, after: int) -> tuple[dict, int]:
    """The messages of one run: as many as it takes to hold ``capacity``
    distinct contents (the store's fill), then ``after`` more.  Returns
    the messages and the length of the fill."""
    n = int(1.02 * capacity / (1.0 - float(p["repeat_share"]))) + after + 64
    while True:
        msgs = build(p, seed, n)
        first = np.zeros(n, bool)
        first[np.unique(msgs["cid"], return_index=True)[1]] = True
        fill = int(np.searchsorted(np.cumsum(first), capacity)) + 1
        if n - fill >= after:
            return msgs, fill
        n *= 2
