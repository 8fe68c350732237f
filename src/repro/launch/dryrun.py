import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("DRYRUN_DEVICES", "512")).strip()
"""Multi-pod dry-run: prove every (architecture × input shape × mesh) cell
lowers, SPMD-partitions and compiles on the production mesh, and extract
the roofline terms from the compiled artifact.

MUST be imported/run before anything else initializes jax (the XLA_FLAGS
assignment above is the very first executable statement).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b \
        --shape train_4k [--multi-pod] [--all] [--out results.json]
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ALIASES, ARCH_IDS, get_config, input_specs, shape_cells
from repro.distributed.api import use_rules
from repro.distributed.sharding import (ShardingPlan, activation_rules,
                                        batch_shardings, param_shardings)
from repro.launch.hlo_analysis import roofline_from_compiled
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, make_train_step
from repro.models.config import SHAPES
from repro.optim import AdamWConfig, adamw_init


def _build_compiled(arch: str, shape: str, multi_pod: bool):
    """Lower + compile one cell; returns (compiled, context)."""
    cfg = get_config(arch)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    sc = SHAPES[shape]
    plan = ShardingPlan.for_mesh(mesh, cfg, shape_kind=sc.kind)

    specs = input_specs(cfg, shape)
    params_struct = model.init_shapes()
    p_shard = param_shardings(params_struct, cfg, plan, mesh)
    b_shard = batch_shardings(cfg, shape, specs, plan, mesh)
    rules = activation_rules(cfg, shape, plan, mesh)

    with mesh, use_rules(mesh, rules):
        if sc.kind == "train":
            opt_cfg = AdamWConfig()
            opt_struct = jax.eval_shape(adamw_init, params_struct)
            # moments share the param specs; step is replicated
            o_shard = {
                "m": jax.tree.map(lambda p: p, p_shard),
                "v": jax.tree.map(lambda p: p, p_shard),
                "step": jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()),
            }
            # grad accumulation: cap the per-device microbatch token count
            # (DRYRUN_MICROBATCH_TOKENS tunes the memory/collective trade:
            # fewer microbatches = fewer FSDP weight re-gathers)
            budget = int(os.environ.get("DRYRUN_MICROBATCH_TOKENS", "16384"))
            dp_size = 1
            for a in plan.dp:
                dp_size *= mesh.shape[a]
            local_tokens = sc.global_batch // dp_size * sc.seq_len
            accum = max(1, min(sc.global_batch // dp_size,
                               local_tokens // budget))
            step = make_train_step(model, opt_cfg, accum_steps=accum)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_struct, opt_struct, specs)
        elif sc.kind == "prefill":
            def prefill_step(params, batch):
                return model.prefill(params, batch)
            jitted = jax.jit(prefill_step,
                             in_shardings=(p_shard, b_shard),
                             out_shardings=None)
            lowered = jitted.lower(params_struct, specs)
        else:  # decode
            cache_struct = specs.pop("cache")
            b_shard.pop("cache")
            cache_shard = batch_shardings(cfg, shape, {"cache": cache_struct},
                                          plan, mesh)["cache"]
            def serve_step(params, cache, batch):
                logits, new_cache = model.decode_step(params, cache, batch)
                return jnp.argmax(logits, -1), new_cache
            jitted = jax.jit(serve_step,
                             in_shardings=(p_shard, cache_shard, b_shard),
                             out_shardings=(None, cache_shard),
                             donate_argnums=(1,))   # in-place cache update
            lowered = jitted.lower(params_struct, cache_struct, specs)

        compiled = lowered.compile()
    return compiled, dict(cfg=cfg, mesh=mesh, plan=plan, sc=sc)


def lower_cell(arch: str, shape: str, multi_pod: bool,
               verbose: bool = True) -> dict:
    """Lower + compile one (arch × shape) cell; return roofline record."""
    t0 = time.time()
    compiled, ctx = _build_compiled(arch, shape, multi_pod)
    cfg, mesh, sc = ctx["cfg"], ctx["mesh"], ctx["sc"]

    mem = compiled.memory_analysis()
    n_chips = mesh.devices.size
    rl = roofline_from_compiled(compiled, n_chips)
    n_params = cfg.n_params()
    # MODEL_FLOPS = 6·N·D for train, 2·N·D for inference (per token),
    # MoE uses active params
    active = n_params
    if cfg.is_moe:
        e_ff = cfg.expert_d_ff or cfg.d_ff
        n_in = 3 if cfg.mlp in ("swiglu", "geglu") else 2
        n_moe = cfg.n_layers - cfg.n_dense_layers     # leading dense: none
        moe_total = n_moe * cfg.n_experts * n_in * cfg.d_model * e_ff
        moe_active = n_moe * cfg.top_k * n_in * cfg.d_model * e_ff
        active = n_params - moe_total + moe_active
    tokens = sc.global_batch * (sc.seq_len if sc.kind != "decode" else 1)
    model_flops = (6 if sc.kind == "train" else 2) * active * tokens

    rec = dict(
        arch=arch, shape=shape, mesh="2x16x16" if multi_pod else "16x16",
        n_chips=n_chips, kind=sc.kind,
        seconds_to_compile=round(time.time() - t0, 1),
        params_b=round(n_params / 1e9, 2),
        argument_bytes_per_device=getattr(mem, "argument_size_in_bytes", 0),
        output_bytes_per_device=getattr(mem, "output_size_in_bytes", 0),
        temp_bytes_per_device=getattr(mem, "temp_size_in_bytes", 0),
        peak_bytes_per_device=(getattr(mem, "argument_size_in_bytes", 0) +
                               getattr(mem, "output_size_in_bytes", 0) +
                               getattr(mem, "temp_size_in_bytes", 0)),
        model_flops_total=model_flops,
        **rl.row(),
    )
    rec["model_flops_per_chip"] = model_flops / n_chips
    rec["useful_flop_frac"] = (model_flops / n_chips) / max(rl.flops, 1.0)
    if verbose:
        print(f"[dryrun] {arch} × {shape} × {rec['mesh']}: "
              f"compile {rec['seconds_to_compile']}s, "
              f"peak {rec['peak_bytes_per_device']/2**30:.2f} GiB/dev, "
              f"t_comp {rl.t_compute*1e3:.2f} ms, "
              f"t_mem {rl.t_memory*1e3:.2f} ms, "
              f"t_coll {rl.t_collective*1e3:.2f} ms "
              f"-> {rl.bottleneck}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="assignment id (e.g. gemma-7b) or module id")
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all (arch × shape) cells")
    ap.add_argument("--out", default=None, help="append JSON records here")
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    if args.all:
        for a in ARCH_IDS:
            for s in shape_cells(get_config(a)):
                cells.append((a, s))
    else:
        arch = args.arch or "gemma-7b"
        shapes = [args.shape] if args.shape else shape_cells(
            get_config(arch))
        cells = [(arch, s) for s in shapes]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    records, failures = [], []
    for arch, shape in cells:
        for mp in meshes:
            try:
                records.append(lower_cell(arch, shape, mp))
            except Exception as e:  # noqa: BLE001 — report and continue
                traceback.print_exc()
                failures.append(dict(arch=arch, shape=shape,
                                     mesh="2x16x16" if mp else "16x16",
                                     error=str(e)[:500]))
    if args.out:
        with open(args.out, "a") as f:
            for r in records + failures:
                f.write(json.dumps(r) + "\n")
    print(f"[dryrun] {len(records)} ok, {len(failures)} failed")
    if failures:
        for f_ in failures:
            print("  FAIL:", f_["arch"], f_["shape"], f_["mesh"],
                  f_["error"][:200])
        raise SystemExit(1)


if __name__ == "__main__":
    main()
