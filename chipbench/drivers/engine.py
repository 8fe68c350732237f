"""Driver for the served path: ``ServingEngine.run`` as the entry.

Open loop.  Request ``i`` of the window is due at ``due[i]``.  Each time the
engine's previous ``run`` call returns, every request that has fallen due
since (at most ``max_call``) goes to the next call; the engine answers
hits from its semantic cache and decodes misses under continuous batching
inside the call, then admits their responses.  A request's latency runs
from its due time to its answer: the cached response for a hit, the last
generated token for a miss; its time to first token ends at the first
generated token, or at the cached answer.  The loop starts ``lead_s``
before the window, so that the engine's waves have grown to their steady
size when it opens; requests due in that lead-in are served but not
counted.

Set-up makes the model's weights on the device from the seed, builds the
engine, fills its cache by admitting the stream's first messages (with a
seeded stand-in response each) until it holds ``capacity`` entries, and
warms every shape the window can use: the decode step at the slot batch,
the decision pass and the rescan at every call width up to ``max_call``,
the single lookup, the eviction scorer and the mirrors' row scatters.

The program's cache answers are recorded at the facade (its ``lookup`` and
``admit`` return values, in order) and replayed through the plain cache
reference; a seeded sample of the generated responses, the longest among
them, is run through the plain float32 model reference.
"""
from __future__ import annotations

import importlib
import sys
import time

import numpy as np

from counters import count_rac_value
from reference import cache as cache_ref
from reference.cache import cache_checks
from reference import llama
from timing import DRAIN_S, Clock, Phases, annotate


def jax_block(tree):
    import jax
    jax.block_until_ready(tree)


class Cell:
    def __init__(self, cfg: dict, traffic: dict, *, seed: int,
                 seconds: float, rate: float | None = None):
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed
        self.seconds = float(seconds)
        self.rate = float(traffic["arrivals"]["rate"] if rate is None
                          else rate)
        self.drain_s = DRAIN_S
        self.lead = float(traffic["lead_s"])
        self.engine = None
        self.gen = importlib.import_module(
            f"generators.{traffic['generator']}")

    # ------------------------------------------------------------ set-up
    def model_config(self):
        from repro.models.config import ModelConfig
        z = llama.dims(self.cfg["model"])
        return ModelConfig(
            name=self.cfg["name"], family="dense", n_layers=z["L"],
            d_model=z["d"], n_heads=z["h"], n_kv_heads=z["hkv"],
            head_dim=z["hd"], d_ff=z["f"], vocab_size=z["V"], mlp="swiglu",
            rope_theta=z["theta"], norm_eps=z["eps"],
            tie_embeddings=bool(self.cfg["model"]["tie_word_embeddings"]),
            param_dtype=self.cfg["model"]["torch_dtype"],
            compute_dtype=self.cfg["model"]["torch_dtype"])

    def setup(self, extra: int = 0):
        from repro.serving import EngineConfig, ServingEngine
        e, c = self.cfg["engine"], self.cfg["cache"]
        cap = int(c["capacity"])
        ph = Phases()
        self.due = self.gen.arrivals(self.traffic["arrivals"],
                                     self.gen.rng_for(self.seed, 3),
                                     self.seconds, self.rate, self.lead)
        self.msgs, self.n_fill = self.gen.stream(
            self.traffic, self.seed, cap, len(self.due) + extra)
        msgs = self.msgs
        self.win = np.arange(self.n_fill, self.n_fill + len(self.due))
        vocab = int(self.cfg["model"]["vocab_size"])
        self.prompts = self.gen.prompts(self.traffic,
                                        self.gen.rng_for(self.seed, 5),
                                        msgs["cid"], vocab)
        ph.mark("stream")
        self.params = llama.weights(self.cfg["model"], self.seed)
        jax_block(self.params)
        ph.mark("weights")
        self.engine = ServingEngine(
            self.model_config(),
            EngineConfig(cache_capacity=cap, tau_hit=float(c["tau_hit"]),
                         max_new_tokens=int(e["max_new_tokens"]),
                         max_batch=int(e["max_batch"]),
                         max_seq=int(e["max_seq"]), emb_dim=int(c["dim"]),
                         cache_backend=c["backend"]),
            params=self.params, policy_kwargs=dict(c["policy_kwargs"]))
        self._record()
        ph.mark("engine")
        cache, cid = self.engine.cache, msgs["cid"]
        rng = self.gen.rng_for(self.seed, 6)
        out_n = int(e["max_new_tokens"])
        for i in range(self.n_fill):
            cache.admit(int(cid[i]), self.row(i),
                        payload=rng.integers(2, vocab, out_n).tolist())
        assert len(cache) == cap, (len(cache), cap)
        ph.mark(f"fill of {self.n_fill} admissions")
        self._warm()
        self.rac_calls: list = []
        count_rac_value(self.engine.cache.policy, self.rac_calls)
        ph.mark("warm-up")
        print(f"[chipbench] set-up phases: {ph}", file=sys.stderr, flush=True)

    def _record(self):
        """Record every cache answer at the facade, in order: the engine's
        own lookups and admissions, with the message each belongs to."""
        cache = self.engine.cache
        self.ops: list = []
        self.msg_of: dict[int, int] = {}      # id(query row) -> message
        lookup, admit = cache.lookup, cache.admit

        def rec_lookup(emb, **kw):
            r = lookup(emb, **kw)
            msg = self.msg_of[id(emb)]
            if r.hit:
                self.ops.append(("L", msg, True, int(r.cid), float(r.sim),
                                 r.payload))
            else:
                self.ops.append(("L", msg, False, int(r.best_cid),
                                 float(r.best_sim), None))
            return r

        def rec_admit(cid, emb, payload=None, **kw):
            ev = admit(cid, emb, payload=payload, **kw)
            self.ops.append(("A", self.msg_of[id(emb)], int(cid), tuple(ev),
                             payload))
            return ev

        cache.lookup, cache.admit = rec_lookup, rec_admit
        self._rows: dict[int, np.ndarray] = {}

    def row(self, i: int) -> np.ndarray:
        """Message ``i``'s query row, one object per message so the
        recorder can tell whose it is."""
        r = self._rows.get(i)
        if r is None:
            r = self._rows[i] = self.msgs["emb"][i]
            self.msg_of[id(r)] = i
        return r

    def _warm(self):
        import jax.numpy as jnp
        eng, e = self.engine, self.cfg["engine"]
        b = int(e["max_batch"])
        kv = eng.model.init_cache(b, int(e["max_seq"]))
        batch = {"tokens": jnp.zeros((b, 1), jnp.int32),
                 "pos": jnp.zeros((b,), jnp.int32)}
        nxt, _, kv = eng.decode(eng.params, kv, batch)
        np.asarray(nxt)
        del kv
        emb = self.msgs["emb"]
        cache = eng.cache
        max_call = int(self.traffic["max_call"])
        cache.peek_batch(emb[:1])
        for w in range(1, max_call + 1):
            cache.decide_batch(emb[:w])
        fresh = list(cache.store.slot_of)[:b]
        for w in range(1, max_call - b + 1):
            cache.peek_rows(emb[:w], fresh)
        pol = cache.policy
        n = len(cache) + 1
        t_rows = len(pol.table.tp_last)
        for rows in (t_rows, 2 * t_rows):
            cache.backend.rac_value(
                np.zeros(n), np.zeros(n, np.int64), np.zeros(rows),
                np.zeros(rows, np.int64), pol.alpha, 0)
        self._warm_scatters(4 * max_call)

    def _warm_scatters(self, most: int):
        """The decision pass keeps device mirrors of the store and the RAC
        table fresh by eager row scatters, padded to 64-row buckets: each
        bucket size is its own program.  Between two engine calls at most
        a few rows change per request handed in (its admission, eviction,
        hit and parent update), so every bucket up to ``most`` rows is
        warmed, for each mirrored array at its size and at the next size
        the topic table grows to."""
        import jax
        be = self.engine.cache.backend
        for name in ("_store_mirror", "_slot_mirror", "_topic_mirror"):
            arrays = (getattr(be, name).arrays or {}).values()
            for a in arrays:
                sizes = (a.shape[0], 2 * a.shape[0]) \
                    if name == "_topic_mirror" else (a.shape[0],)
                for rows in sizes:
                    base = jax.numpy.zeros((rows,) + a.shape[1:], a.dtype)
                    top = min(max(64, rows // 4), -(-most // 64) * 64)
                    for k in range(64, top + 1, 64):
                        jax.block_until_ready(base.at[np.arange(k)].set(
                            np.zeros((k,) + a.shape[1:], a.dtype)))

    def reschedule(self, rate: float):
        """A new window at ``rate`` on the next messages (the knee sweep)."""
        self.due = self.gen.arrivals(self.traffic["arrivals"],
                                     self.gen.rng_for(self.seed, 4 + int(rate)),
                                     self.seconds, rate, self.lead)
        self.win = np.arange(self.next_msg, self.next_msg + len(self.due))
        assert self.win[-1] < len(self.msgs["cid"]), "sweep ran out of messages"

    # ------------------------------------------------------------ window
    def window(self) -> dict:
        eng, cid = self.engine, self.msgs["cid"]
        max_call = int(self.traffic["max_call"])
        n = len(self.win)
        done = np.full(n, np.nan)
        first = np.full(n, np.nan)
        hits = np.zeros(n, bool)
        self.served = []                       # (prompt, out) of misses
        steps0, gen0 = eng.stats["batches"], eng.stats["generated_tokens"]
        self.rac_calls.clear()
        self.snap0 = eng.cache.metrics_snapshot()
        clock = Clock(self.seconds, self.drain_s, self.lead)
        t0, end = clock.t0, clock.end
        li = 0
        self.slot_steps = 0                   # occupied slot-steps decoded
        self.ctx_tokens = 0                   # attention context they read
        while li < n:
            d = t0 + self.due[li]
            if d >= end:
                break
            now = clock.wait_until(d)
            if now > end + clock.drain_s:
                break
            j = li + 1
            while j < n and j - li < max_call and t0 + self.due[j] <= now:
                j += 1
            reqs = [(int(cid[i]), self.row(int(i)), self.prompts[i])
                    for i in self.win[li:j]]
            with annotate("chipbench/engine_run"):
                states = eng.run(reqs)
            for s in states:
                k = li + s.rid
                done[k] = s.t_done - t0
                first[k] = (s.t_first if s.t_first else s.t_done) - t0
                hits[k] = s.cached
                if not s.cached:
                    p = len(s.tokens)
                    steps = p + len(s.out_tokens) - 1
                    self.slot_steps += steps
                    self.ctx_tokens += steps * (steps + 1) // 2
                    self.served.append((list(s.tokens), list(s.out_tokens)))
            li = j
        t1 = time.perf_counter()
        self.next_msg = int(self.win[li - 1]) + 1 if li else int(self.win[0])
        self.snap1 = eng.cache.metrics_snapshot()
        unanswered = np.isnan(done)
        done[unanswered] = t1 - t0
        first[np.isnan(first)] = t1 - t0
        # the lead-in's requests brought the loop to its steady state;
        # the window's are those due inside it
        w = self.due >= 0
        return {"due": self.due[w], "done": done[w], "first": first[w],
                "hits": hits[w], "attempted": int(w.sum()),
                "failed": int(np.sum(w & unanswered)),
                "late_max_s": clock.late_max_s,
                "late": clock.late, "t0": t0,
                "t1": max(end, t1), "snap0": self.snap0, "snap1": self.snap1,
                "rac_value_calls": list(self.rac_calls),
                "steps": eng.stats["batches"] - steps0,
                "generated": eng.stats["generated_tokens"] - gen0,
                "slot_steps": self.slot_steps, "ctx_tokens": self.ctx_tokens,
                "max_batch": int(self.cfg["engine"]["max_batch"])}

    # ------------------------------------------------------------ check
    def release(self):
        """Free the program's state (weights, caches) before the
        reference runs."""
        self.engine = None
        self.params = None

    def sample(self) -> list:
        """Served responses to compare: the longest, then a seeded draw."""
        k = int(self.cfg["check"]["sequences"])
        if len(self.served) <= k:
            return list(self.served)
        lens = [len(p) + len(o) for p, o in self.served]
        first = int(np.argmax(lens))
        rest = [i for i in range(len(self.served)) if i != first]
        pick = self.gen.rng_for(self.seed, 7).choice(rest, k - 1,
                                                     replace=False)
        return [self.served[first]] + [self.served[i] for i in sorted(pick)]

    def check(self, control: bool = False) -> list[dict]:
        """The numbers compared, each with its limit; with ``control`` each
        also carries the lower-precision control's reading."""
        c = self.cfg["cache"]
        rac = cache_ref.RAC.from_config(c)
        rep = cache_ref.replay(rac, self.ops, self.msgs["emb"], control)
        look = rep["look"]
        seqs = self.sample()
        gaps = llama.token_gaps(self.cfg["model"], self.seed, seqs)
        print(f"[chipbench] replayed {len(self.ops)} cache operations: "
              f"{len(look['op'])} lookups ({int(look['hit'].sum())} hits), "
              f"{rep['evictions']} evictions; {len(gaps)} served tokens of "
              f"{len(seqs)} responses compared", file=sys.stderr, flush=True)
        out = cache_checks(rep, self.msgs["emb"], c, self.cfg["limits"],
                           control)
        gap = {"name": "logit_gap",
               "value": float(gaps.max()) if len(gaps) else float("inf"),
               "limit": self.cfg["limits"]["logit_gap"]}
        if control:
            ctrl = llama.token_gaps(self.cfg["model"], self.seed, seqs,
                                    control=True)
            gap["control"] = float(ctrl.max()) if len(ctrl) else 0.0
        return out + [gap]
