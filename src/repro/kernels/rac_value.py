"""Pallas TPU kernel: vectorized RAC eviction scoring (Eq. 1).

Computes  value[i] = TP_now(topic[i]) · TSI[i]  over all resident entries,
where  TP_now(s) = 2^(−α·(t_now − t_last(s))) · TP_last(s)  is the lazy
closed form of Def. 1.  The per-topic tables are gathered per entry by
XLA before the kernel (Mosaic has no 1-D gather); the kernel is
elementwise over (8, 128) tiles of the entry axis.  This is the
device-side half of the policy — the block-manager scores a whole block
table in one call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .decision import BN, LANES


def _rac_value_kernel(tsi_ref, tp_ref, tl_ref, out_ref, *,
                      alpha: float, t_now: int):
    decay = jnp.exp2(-alpha * (t_now - tl_ref[...]).astype(jnp.float32))
    out_ref[...] = decay * tp_ref[...] * tsi_ref[...]


def rac_value_pallas(tsi: jnp.ndarray, tid: jnp.ndarray,
                     tp_last: jnp.ndarray, t_last: jnp.ndarray,
                     alpha: float, t_now: int, *, interpret: bool):
    """tsi (N,) f32; tid (N,) i32; tp_last/t_last (T,) topic tables.
    N must be a BN multiple (pad tsi with 0 / tid with 0)."""
    n = tsi.shape[0]
    assert n % BN == 0
    cols = (tsi, jnp.take(tp_last.astype(jnp.float32), tid),
            jnp.take(t_last.astype(jnp.float32), tid))
    spec = pl.BlockSpec((BN // LANES, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_rac_value_kernel, alpha=alpha, t_now=t_now),
        grid=(n // BN,),
        name="rac_value",
        in_specs=[spec] * len(cols),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n // LANES, LANES), jnp.float32),
        interpret=interpret,
    )(*(c.reshape(n // LANES, LANES) for c in cols))
    return out.reshape(n)
