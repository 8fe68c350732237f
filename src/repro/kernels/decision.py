"""Pallas TPU kernel: occupancy-masked RAC victim scoring with runtime time.

The fused decision path (``ops.fused_decide``) scores one replay chunk in a
single device dispatch: Top-1 similarity over the resident slab (hit
determination), Top-1 over the topic-representative table (Alg. 4
routing), and Eq. 1 victim values over the whole slot table.  The two
Top-1 passes reuse ``similarity_topk``'s kernel; this module supplies the
third leg.

``victim_value_pallas`` extends the ``rac_value`` kernel two ways that the
fused path needs:

  - ``t_now`` is a *runtime* scalar delivered through scalar prefetch
    (``PrefetchScalarGridSpec``), so simulation time advancing between
    chunks never recompiles — the per-eviction ``rac_value`` kernel instead
    bakes ``t_now=0`` and shifts timestamps on the host, which would force
    a re-upload of the whole ``t_last`` table per chunk here.
  - the occupancy mask is applied *in kernel*: free slots score ``+inf``
    directly, so the min-value victim scan can run on the fixed-shape slot
    table without a host-side where().

Tiling matches ``rac_value``: the per-topic tables are gathered per
entry by XLA before the kernel (Mosaic has no 1-D gather), and the kernel
is elementwise over (8, 128) tiles of the slot axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BN = 8 * LANES   # entries per tile


def _victim_value_kernel(tn_ref, tsi_ref, tp_ref, tl_ref, occ_ref, out_ref,
                         *, alpha: float):
    t_now = tn_ref[0]
    # subtract in int32 first: only the (small) age is cast, so absolute
    # timestamps past float32's 2^24 integer range never lose precision
    decay = jnp.exp2(-alpha * (t_now - tl_ref[...]).astype(jnp.float32))
    val = decay * tp_ref[...] * tsi_ref[...]
    out_ref[...] = jnp.where(occ_ref[...] > 0, val, jnp.inf)


def victim_value_pallas(tsi: jnp.ndarray, tid: jnp.ndarray,
                        occ: jnp.ndarray, tp_last: jnp.ndarray,
                        t_last: jnp.ndarray, t_now, alpha: float, *,
                        interpret: bool):
    """tsi (N,) f32; tid (N,) i32; occ (N,) i32 (0 = free → +inf);
    tp_last/t_last (T,) topic tables; ``t_now`` a runtime int32 scalar.
    N must be a BN multiple (pad tsi/tid with 0 and occ with 0)."""
    n = tsi.shape[0]
    assert n % BN == 0
    tid = jnp.maximum(tid, 0)                  # free slots carry tid -1
    cols = (tsi, jnp.take(tp_last.astype(jnp.float32), tid),
            jnp.take(t_last.astype(jnp.int32), tid), occ)
    spec = pl.BlockSpec((BN // LANES, LANES), lambda i, tn: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n // BN,),
        in_specs=[spec] * len(cols), out_specs=spec)
    out = pl.pallas_call(
        functools.partial(_victim_value_kernel, alpha=alpha),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n // LANES, LANES), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(t_now, jnp.int32).reshape(1),
      *(c.reshape(n // LANES, LANES) for c in cols))
    return out.reshape(n)


def victim_value_multi_pallas(tsi: jnp.ndarray, tid: jnp.ndarray,
                              occ: jnp.ndarray, tp_last: jnp.ndarray,
                              t_last: jnp.ndarray, t_now, alpha: float, *,
                              interpret: bool):
    """Policy-stacked victim scoring: one dispatch scores P slot tables.

    All slot-axis inputs carry a leading policy axis — tsi/tid/occ
    ``(P, N)``, the topic tables ``(P, T)`` — and the policy axis is
    walked grid-sequentially (``lax.map``) inside the single dispatch, so
    each slice runs the ``victim_value`` kernel unchanged and the arena
    pays one host→device round-trip for all P policies.  ``t_now`` and
    ``alpha`` are shared across policies (one simulated clock)."""

    def one(args):
        tsi_p, tid_p, occ_p, tp_p, tl_p = args
        return victim_value_pallas(tsi_p, tid_p, occ_p, tp_p, tl_p,
                                   t_now, alpha, interpret=interpret)

    return jax.lax.map(one, (tsi, tid, occ, tp_last, t_last))
