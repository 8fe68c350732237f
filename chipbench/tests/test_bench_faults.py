"""A run with the timed path broken underneath must come out not correct,
once for each fault these cells can have (the device check is skipped, the
rest of the run is the real one, at a tiny size on the CPU)."""
import numpy as np
import pytest

import run
from small import ENGINE, FACADE, args

SEED = 2 ** 31 + 321


def _facade_faults(mp, fault, cell):
    from repro.cache import backends
    from repro.cache.facade import SemanticCache
    kb = backends.KernelBackend
    if fault == "answer_altered":
        orig = kb.top1_batch

        def top1_batch(self, store, q):
            cids, sims = orig(self, store, q)
            return cids, sims + 1e-3
        mp.setattr(kb, "top1_batch", top1_batch)
    elif fault == "half_batch":
        orig = kb.top1_batch

        def top1_batch(self, store, q):
            h = (len(q) + 1) // 2
            cids, sims = orig(self, store, q[:h])
            return np.resize(cids, len(q)), np.resize(sims, len(q))
        mp.setattr(kb, "top1_batch", top1_batch)
    elif fault == "state_unchanged":
        mp.setattr(SemanticCache, "_admit_now",
                   lambda self, *a, **k: [])
    elif fault == "victim_altered":
        pol = cell.cache.policy
        orig = pol.value_backend

        def rac_value(*a):
            v = orig(*a)
            return v.max() - v                 # the most valuable goes
        mp.setattr(pol, "value_backend", rac_value)


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch",
                                   "state_unchanged", "victim_altered"])
def test_facade_fault_is_caught(fault, monkeypatch):
    traffic = {"arrivals": {"rate": 1500.0}}         # lookups in groups
    res = run.run(args("semcache.chat", SEED), require_tpu=False,
                  overrides={**FACADE, "traffic": traffic},
                  driver_hook=lambda c: _facade_faults(monkeypatch, fault,
                                                        c))
    assert res["correct"] is False


def _engine_fault(mp, fault):
    from repro.serving import engine as eng_mod
    orig = eng_mod.make_decode_step

    def make(model):
        step = orig(model)

        def broken(params, cache, batch):
            nxt, logits, cache = step(params, cache, batch)
            if fault == "token_altered":
                nxt = nxt.at[0].set((nxt[0] + 1) % logits.shape[-1])
            else:          # half of the batch left out, filled from the rest
                h = nxt.shape[0] // 2
                nxt = nxt.at[:h].set(nxt[h:2 * h])
            return nxt, logits, cache
        return broken
    mp.setattr(eng_mod, "make_decode_step", make)


@pytest.mark.parametrize("fault", ["token_altered", "half_batch"])
def test_engine_fault_is_caught(fault, monkeypatch):
    _engine_fault(monkeypatch, fault)
    res = run.run(args("served.chat", SEED), require_tpu=False,
                  overrides=ENGINE)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]
