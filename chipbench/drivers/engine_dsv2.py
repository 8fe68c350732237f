"""Driver for the served path with DeepSeek-V2-Lite as the model.

The same open loop, set-up and cache checks as ``drivers/engine.py``
(``ServingEngine.run`` as the entry); what differs is the model.  Its
configuration is the published ``config.json``, whose keys sit at the top
level of the configuration file; its weights are made on the chip from the
seed by ``reference/deepseek_v2.py``, in bfloat16, one layer at a time, as
the list of layers the program reads unstacked; and the served responses
are compared with that plain float32 reference.  The window's record also
carries the engine's MoE routing counters (``ServingEngine.stats``), as
deltas over the window.
"""
from __future__ import annotations

import sys

import numpy as np

from counters import count_rac_value
from drivers import engine
from reference import cache as cache_ref
from reference import deepseek_v2 as dsv2
from reference.cache import cache_checks
from timing import Phases

#: Keys of the configuration file that are the harness's, not the model's.
HARNESS_KEYS = ("name", "source", "deployment", "reduced", "published",
                "assumed", "driver", "engine", "cache", "check", "limits")
#: ``ServingEngine.stats`` keys of the MoE routing counters.
MOE_STATS = ("moe_rows", "moe_experts_touched", "moe_max_load",
             "moe_layer_steps")


class Cell(engine.Cell):
    def __init__(self, cfg: dict, traffic: dict, **kw):
        super().__init__(cfg, traffic, **kw)
        self.m = {k: v for k, v in cfg.items() if k not in HARNESS_KEYS}

    def model_config(self):
        """The repository's DeepSeek-V2-Lite preset (which also decides how
        the program lays out its layers) with the file's numbers."""
        import dataclasses
        from repro.configs import get_config
        from repro.models.config import Yarn
        z = dsv2.dims(self.m)
        if z["nope"] != z["vd"]:
            raise ValueError("the program's MLA keeps one head_dim for "
                             "qk_nope_head_dim and v_head_dim")
        if z["scaling"] != 1:
            raise ValueError("the program's router has no "
                             "routed_scaling_factor other than 1")
        ys = z["yarn"]
        dtype = self.m["torch_dtype"]
        return dataclasses.replace(
            get_config("deepseek_v2_lite_16b"),
            name=self.cfg["name"], n_layers=z["L"],
            d_model=z["d"], n_heads=z["h"], n_kv_heads=z["h"],
            head_dim=z["nope"], d_ff=z["f"], vocab_size=z["V"],
            kv_lora_rank=z["r"],
            rope_head_dim=z["rope"], rope_theta=z["theta"],
            norm_eps=z["eps"],
            yarn=None if not ys else Yarn(
                factor=float(ys["factor"]),
                original_max_pos=int(ys["original_max_position_embeddings"]),
                beta_fast=float(ys["beta_fast"]),
                beta_slow=float(ys["beta_slow"]),
                mscale=float(ys["mscale"]),
                mscale_all_dim=float(ys["mscale_all_dim"])),
            n_experts=z["E"], n_shared_experts=z["shared"], top_k=z["K"],
            expert_d_ff=z["fe"], n_dense_layers=z["dense"],
            norm_topk_prob=z["norm_topk"],
            param_dtype=dtype, compute_dtype=dtype)

    def setup(self, extra: int = 0):
        """``drivers/engine.Cell.setup``, with the model's weights from
        ``reference/deepseek_v2.py``."""
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
        vocab = int(self.m["vocab_size"])
        self.prompts = self.gen.prompts(self.traffic,
                                        self.gen.rng_for(self.seed, 5),
                                        msgs["cid"], vocab)
        ph.mark("stream")
        mcfg = self.model_config()
        self.params = dsv2.weights(self.m, self.seed)
        engine.jax_block(self.params)
        ph.mark("weights")
        self.engine = ServingEngine(
            mcfg,
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

    def release(self):
        """Free the program's weights before the reference makes its own:
        the engine's event hooks hold it in a reference cycle, which the
        collector leaves alone once ``run.py`` has frozen set-up's
        objects, so the weights are deleted on the device here."""
        import jax
        params = self.params
        super().release()
        for a in jax.tree.leaves(params):
            a.delete()

    def window(self) -> dict:
        stats = self.engine.stats
        before = {k: stats[k] for k in MOE_STATS}
        rec = super().window()
        stats = self.engine.stats
        rec.update({k: stats[k] - before[k] for k in MOE_STATS})
        return rec

    def check(self, control: bool = False) -> list[dict]:
        """The numbers compared, each with its limit; with ``control`` each
        also carries the lower-precision control's reading."""
        c = self.cfg["cache"]
        rac = cache_ref.RAC.from_config(c)
        rep = cache_ref.replay(rac, self.ops, self.msgs["emb"], control)
        look = rep["look"]
        seqs = self.sample()
        flips: list = []
        gaps = dsv2.token_gaps(self.m, self.seed, seqs, flips=flips)
        print(f"[chipbench] replayed {len(self.ops)} cache operations: "
              f"{len(look['op'])} lookups ({int(look['hit'].sum())} hits), "
              f"{rep['evictions']} evictions; {len(gaps)} served tokens of "
              f"{len(seqs)} responses compared; router top-k sets that "
              "change when its input is rounded to bfloat16: "
              f"{sum(n for n, _ in flips)} of {sum(r for _, r in flips)} "
              "token-layers", file=sys.stderr, flush=True)
        out = cache_checks(rep, self.msgs["emb"], c, self.cfg["limits"],
                           control)
        gap = {"name": "logit_gap",
               "value": float(gaps.max()) if len(gaps) else float("inf"),
               "limit": self.cfg["limits"]["logit_gap"]}
        if control:
            ctrl = dsv2.token_gaps(self.m, self.seed, seqs, control=True)
            gap["control"] = float(ctrl.max()) if len(ctrl) else 0.0
        return out + [gap]
