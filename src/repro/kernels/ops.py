"""Jit'd public wrappers around the Pallas kernels.

Each wrapper pads inputs to kernel tile multiples, dispatches to the kernel
(``interpret=True`` on CPU — the TPU path compiles the same kernels
natively), and unpads the result.  ``use_pallas=False`` falls back to the
ref oracle (used by the serving engine on CPU where interpret-mode overhead
isn't worth it).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .decision import (BN as _VV_BN, victim_value_multi_pallas,
                       victim_value_pallas)
from .decode_attention import decode_attention_pallas
from .flash_attention import BQ as _FA_BQ, flash_attention_pallas
from .rac_value import BN as _RV_BN, rac_value_pallas
from .similarity_topk import (BC as _ST_BC, BQ as _ST_BQ, sim_top1_pallas,
                              sim_topk_pallas, sim_topk_q8_pallas)


def _is_cpu() -> bool:
    return jax.devices()[0].platform == "cpu"


#: Process-global dispatch ledger.  ``launches`` counts kernel dispatches
#: (one per public wrapper call — each is one jitted program), ``host_syncs``
#: counts device→host materializations (every ``to_host``), ``kernel_s``
#: accumulates the host wall time of ``run_timed`` intervals — dispatch to
#: ready, so it includes the upload of any host array passed in and is not
#: kernel time.  ``upload_s``/``uploads`` stay at zero: no path makes an
#: explicit slab upload (the exact scan reads the journal-synced store
#: mirror, whose transfers the mirrors' ``sync_stats`` count); they remain
#: for readers of the ledger that still expect the keys.
dispatch_stats = {"launches": 0, "host_syncs": 0, "kernel_s": 0.0,
                  "upload_s": 0.0, "uploads": 0}


def count_launch(n: int = 1) -> None:
    """Tick the kernel-dispatch counter (one jitted program launched)."""
    dispatch_stats["launches"] += n


def to_host(x):
    """Materialize ``x`` on the host, counting the sync when it actually
    crosses the device boundary (numpy inputs pass through uncounted)."""
    if isinstance(x, jax.Array):
        dispatch_stats["host_syncs"] += 1
    return np.asarray(x)


def to_host_tuple(xs):
    """Materialize a tuple of device arrays as ONE counted sync — the
    fused pipeline's single device→host transfer per chunk."""
    dispatch_stats["host_syncs"] += 1
    return jax.device_get(xs)


def run_timed(fn):
    """Run ``fn`` (a zero-arg closure dispatching device work), block until
    its outputs are ready, and charge the interval to
    ``dispatch_stats["kernel_s"]``: host wall time from dispatch to ready,
    uploads of host arguments included."""
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    dispatch_stats["kernel_s"] += time.perf_counter() - t0
    return out


def _counted(fn):
    """Wrap a public dispatch wrapper so every call ticks ``launches``."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        count_launch()
        return fn(*args, **kwargs)

    return wrapper


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0.0) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def sim_top1_raw(queries, candidates, n_valid, *, use_pallas: bool = True,
                 interpret: bool | None = None):
    """Un-jitted Top-1 body shared by :func:`sim_top1` and the sharded
    backend (which calls it per shard inside a ``shard_map`` region).
    ``n_valid`` may be a traced int32 scalar — it masks the candidate tail
    at runtime, so one compilation serves every resident count."""
    if not use_pallas:
        return ref.sim_top1_ref(queries, candidates, n_valid)
    interp = _is_cpu() if interpret is None else interpret
    qp = _pad_to(_pad_to(queries, 1, 128), 0, _ST_BQ)
    cp = _pad_to(_pad_to(candidates, 1, 128), 0, _ST_BC)
    vals, idx = sim_top1_pallas(qp.astype(jnp.float32),
                                cp.astype(jnp.float32),
                                n_valid, interpret=interp)
    return vals[: queries.shape[0]], idx[: queries.shape[0]]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _sim_top1_jit(queries, candidates, n_valid, *, use_pallas, interpret):
    return sim_top1_raw(queries, candidates, n_valid, use_pallas=use_pallas,
                        interpret=interpret)


@_counted
def sim_top1(queries, candidates, n_valid=None, *, use_pallas: bool = True,
             interpret: bool | None = None):
    """Top-1 cosine retrieval: (Q,D)x(N,D) -> (vals (Q,), idx (Q,)).

    ``n_valid`` (default: all of ``candidates``) is a *runtime* resident
    count: rows at or past it are masked to -inf, so compacted and
    per-shard stores stop scoring their free tail without triggering a
    recompile per count."""
    if n_valid is None:
        n_valid = candidates.shape[0]
    return _sim_top1_jit(queries, candidates, jnp.int32(n_valid),
                         use_pallas=use_pallas, interpret=interpret)


def sim_topk_raw(queries, candidates, n_valid, k: int, *,
                 use_pallas: bool = True, interpret: bool | None = None):
    """Un-jitted Top-K body.  ``k`` is static (it sizes the kernel's
    revisited output block); ``n_valid`` may be a traced int32 scalar."""
    if not use_pallas:
        return ref.sim_topk_ref(queries, candidates, n_valid, k)
    interp = _is_cpu() if interpret is None else interpret
    qp = _pad_to(_pad_to(queries, 1, 128), 0, _ST_BQ)
    cp = _pad_to(_pad_to(candidates, 1, 128), 0, _ST_BC)
    vals, idx = sim_topk_pallas(qp.astype(jnp.float32),
                                cp.astype(jnp.float32),
                                n_valid, k, interpret=interp)
    return vals[: queries.shape[0]], idx[: queries.shape[0]]


@functools.partial(jax.jit, static_argnames=("k", "use_pallas", "interpret"))
def _sim_topk_jit(queries, candidates, n_valid, *, k, use_pallas, interpret):
    return sim_topk_raw(queries, candidates, n_valid, k,
                        use_pallas=use_pallas, interpret=interpret)


@_counted
def sim_topk(queries, candidates, k: int, n_valid=None, *,
             use_pallas: bool = True, interpret: bool | None = None):
    """Top-K cosine retrieval: (Q,D)x(N,D) -> (vals (Q,K), idx (Q,K)),
    sorted descending with ties toward the lower candidate index.

    The K-generalization of :func:`sim_top1` behind the host-tier
    promotion scan and shortlist peeks.  ``k`` is static per launch shape;
    ``n_valid`` is the runtime resident count masking the free tail (rows
    at or past it come back as (-inf, undefined) — callers map them to
    (-inf, -1))."""
    if n_valid is None:
        n_valid = candidates.shape[0]
    return _sim_topk_jit(queries, candidates, jnp.int32(n_valid), k=int(k),
                         use_pallas=use_pallas, interpret=interpret)


def route_topics_raw(queries, reps_aug, n_valid, k: int, *,
                     use_pallas: bool = True, interpret: bool | None = None):
    """Un-jitted topic-routing body: augment each query with its L2 norm
    and Top-K the (T, D+1) bound matrix ``[rep | spread]`` — the matmul
    computes ``q·rep_t + ‖q‖·spread_t`` directly (see cache/pruned.py)."""
    qf = queries.astype(jnp.float32)
    qn = jnp.sqrt(jnp.sum(qf * qf, axis=1, keepdims=True))
    qa = jnp.concatenate([qf, qn], axis=1)
    return sim_topk_raw(qa, reps_aug, n_valid, k,
                        use_pallas=use_pallas, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "use_pallas", "interpret"))
def _route_topics_jit(queries, reps_aug, n_valid, *, k, use_pallas,
                      interpret):
    return route_topics_raw(queries, reps_aug, n_valid, k,
                            use_pallas=use_pallas, interpret=interpret)


@_counted
def route_topics(queries, reps_aug, probes: int, n_valid=None, *,
                 use_pallas: bool = True, interpret: bool | None = None):
    """Stage-1 routing for the pruned lookup: (Q,D)x(T,D+1) ->
    (bounds (Q,K), tids (Q,K)), K = probes+1, sorted descending.

    ``reps_aug`` row ``t`` is ``[rep_t | spread_t]`` so scoring the
    norm-augmented query yields each topic's Cauchy–Schwarz score bound;
    the leading ``probes`` columns are the probe set and column
    ``probes`` (when present) bounds every unprobed topic.  ``n_valid``
    masks retired/unborn topic rows to (-inf, undefined), so with fewer
    live topics than probes the unprobed bound is naturally -inf."""
    if n_valid is None:
        n_valid = reps_aug.shape[0]
    k = int(min(probes + 1, reps_aug.shape[0]))
    return _route_topics_jit(queries, reps_aug, jnp.int32(n_valid), k=k,
                             use_pallas=use_pallas, interpret=interpret)


def sim_topk_q8_raw(q8, qscale, c8, cscale, n_valid, k: int, *,
                    use_pallas: bool = True, interpret: bool | None = None):
    """Un-jitted quantized Top-K body shared by :func:`sim_topk_q8` and the
    sharded backend (per shard inside ``shard_map``).  Inputs are the int8
    mirrors plus their per-row fp32 scales; int8 zero-padding is exact
    (zero rows score 0 and sit behind the ``n_valid`` mask anyway)."""
    if not use_pallas:
        return ref.sim_topk_q8_ref(q8, qscale, c8, cscale, n_valid, k)
    interp = _is_cpu() if interpret is None else interpret
    qp = _pad_to(_pad_to(q8, 1, 128, value=0), 0, _ST_BQ, value=0)
    cp = _pad_to(_pad_to(c8, 1, 128, value=0), 0, _ST_BC, value=0)
    qs = _pad_to(qscale, 0, _ST_BQ)
    cs = _pad_to(cscale, 0, _ST_BC)
    vals, idx = sim_topk_q8_pallas(qp.astype(jnp.int8),
                                   qs.astype(jnp.float32),
                                   cp.astype(jnp.int8),
                                   cs.astype(jnp.float32),
                                   n_valid, k, interpret=interp)
    return vals[: q8.shape[0]], idx[: q8.shape[0]]


@functools.partial(jax.jit, static_argnames=("k", "use_pallas", "interpret"))
def _sim_topk_q8_jit(q8, qscale, c8, cscale, n_valid, *, k, use_pallas,
                     interpret):
    return sim_topk_q8_raw(q8, qscale, c8, cscale, n_valid, k,
                           use_pallas=use_pallas, interpret=interpret)


@_counted
def sim_topk_q8(q8, qscale, c8, cscale, k: int, n_valid=None, *,
                use_pallas: bool = True, interpret: bool | None = None):
    """Quantized-slab Top-K candidate generation:
    (Q,D)i8×(N,D)i8 -> (vals (Q,K), idx (Q,K)) of *approximate* fp32
    similarities, same descending order / lower-index tie contract as
    :func:`sim_topk`.

    The candidate-generation half of the quantized lookup path
    (:mod:`repro.cache.quantized`): the scan streams the 4×-smaller int8
    slab, and the caller rescores the ≤K survivors in fp32 to make exact
    decisions.  ``k`` is static (clamped to the candidate count — a
    shortlist can never be wider than the slab); ``n_valid`` is the
    runtime resident count masking the free tail."""
    if n_valid is None:
        n_valid = c8.shape[0]
    return _sim_topk_q8_jit(q8, qscale, c8, cscale, jnp.int32(n_valid),
                            k=int(min(k, c8.shape[0])),
                            use_pallas=use_pallas, interpret=interpret)


def sim_topk_q8_multi_raw(q8, qscale, slabs8, cscales, n_valid, k: int, *,
                          use_pallas: bool = True,
                          interpret: bool | None = None):
    """Un-jitted policy-stacked quantized Top-K body: ``slabs8`` is
    ``(P, N, D)`` int8 with per-row scales ``cscales`` ``(P, N)`` and
    per-policy resident counts ``n_valid`` ``(P,)``.  Same dispatch shape
    as :func:`sim_top1_multi_raw` (grid-sequential ``lax.map`` on the
    pallas path, vmapped oracle otherwise), with the same per-row score
    independence: each policy's survivor set matches its own single-slab
    launch."""
    if use_pallas:
        def one(args):
            slab, cs, nv = args
            return sim_topk_q8_raw(q8, qscale, slab, cs, nv, k,
                                   use_pallas=True, interpret=interpret)

        return jax.lax.map(one, (slabs8, cscales, n_valid))
    return jax.vmap(
        lambda slab, cs, nv: ref.sim_topk_q8_ref(q8, qscale, slab, cs,
                                                 nv, k))(slabs8, cscales,
                                                         n_valid)


@functools.partial(jax.jit, static_argnames=("k", "use_pallas", "interpret"))
def _sim_topk_q8_multi_jit(q8, qscale, slabs8, cscales, n_valid, *, k,
                           use_pallas, interpret):
    return sim_topk_q8_multi_raw(q8, qscale, slabs8, cscales, n_valid, k,
                                 use_pallas=use_pallas, interpret=interpret)


@_counted
def sim_topk_q8_multi(q8, qscale, slabs8, cscales, k: int, n_valid=None, *,
                      use_pallas: bool = True,
                      interpret: bool | None = None):
    """Policy-stacked quantized Top-K: (B,D)i8×(P,N,D)i8 ->
    ((P,B,K), (P,B,K)) — the arena's stacked scan on the 4×-smaller slab,
    where the memory saving is multiplied by P.  ``k`` is clamped to the
    slot-axis width like :func:`sim_topk_q8`."""
    if n_valid is None:
        n_valid = np.full(slabs8.shape[0], slabs8.shape[1], dtype=np.int32)
    return _sim_topk_q8_multi_jit(q8, qscale, slabs8, cscales,
                                  jnp.asarray(n_valid, jnp.int32),
                                  k=int(min(k, slabs8.shape[1])),
                                  use_pallas=use_pallas, interpret=interpret)


def sim_top1_multi_raw(queries, slabs, n_valid, *, use_pallas: bool = True,
                       interpret: bool | None = None):
    """Un-jitted policy-stacked Top-1 body shared by :func:`sim_top1_multi`
    and the sharded backend (which runs it per shard inside ``shard_map``).

    ``slabs`` is ``(P, N, D)`` — one resident slab per policy — and
    ``n_valid`` ``(P,)`` the per-policy runtime resident counts.  The
    pallas path walks the policy axis grid-sequentially (``lax.map``) so
    the whole stack is one dispatch; the jnp-oracle path vmaps.  Per-row
    scores are computed by the same kernel math as :func:`sim_top1_raw`
    regardless of which rows share the launch, so each policy's Top-1
    *decision* is the one its own single-slab launch would have made."""
    if use_pallas:
        def one(args):
            slab, nv = args
            return sim_top1_raw(queries, slab, nv, use_pallas=True,
                                interpret=interpret)

        return jax.lax.map(one, (slabs, n_valid))
    return jax.vmap(
        lambda slab, nv: ref.sim_top1_ref(queries, slab, nv))(slabs, n_valid)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _sim_top1_multi_jit(queries, slabs, n_valid, *, use_pallas, interpret):
    return sim_top1_multi_raw(queries, slabs, n_valid,
                              use_pallas=use_pallas, interpret=interpret)


@_counted
def sim_top1_multi(queries, slabs, n_valid=None, *, use_pallas: bool = True,
                   interpret: bool | None = None):
    """Policy-stacked Top-1 retrieval: (B,D)x(P,N,D) -> ((P,B), (P,B)).

    The batched-over-policy variant of :func:`sim_top1` behind the
    multi-policy arena: ONE dispatch scores a query chunk against every
    policy's resident slab, with a per-policy runtime ``n_valid`` vector
    masking each slab's free tail (no recompiles as fill levels drift
    apart)."""
    if n_valid is None:
        n_valid = np.full(slabs.shape[0], slabs.shape[1], dtype=np.int32)
    return _sim_top1_multi_jit(queries, slabs,
                               jnp.asarray(n_valid, jnp.int32),
                               use_pallas=use_pallas, interpret=interpret)


@_counted
@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def flash_attention(q, k, v, *, use_pallas: bool = True,
                    interpret: bool | None = None):
    """Causal GQA flash attention.  q (B,H,S,D); k/v (B,Hkv,S,D)."""
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=True)
    interp = _is_cpu() if interpret is None else interpret
    s = q.shape[2]
    qp = _pad_to(q, 2, _FA_BQ)
    kp = _pad_to(k, 2, _FA_BQ)
    vp = _pad_to(v, 2, _FA_BQ)
    out = flash_attention_pallas(qp, kp, vp, interpret=interp)
    return out[:, :, :s]


@_counted
@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def decode_attention(q, k, v, pos, *, use_pallas: bool = True,
                     interpret: bool | None = None):
    """One-token GQA decode.  q (B,H,D); k/v (B,S,Hkv,D); pos (B,)."""
    if not use_pallas:
        return ref.decode_attention_ref(q, k, v, pos)
    interp = _is_cpu() if interpret is None else interpret
    return decode_attention_pallas(q, k, v, pos, interpret=interp)


def rac_value_raw(tsi, tid, tp_last, t_last, alpha: float, t_now: int, *,
                  use_pallas: bool = True, interpret: bool | None = None):
    """Un-jitted RAC Eq.1 body shared by :func:`rac_value` and the sharded
    backend (per-shard scoring of a chunk of the resident table)."""
    if not use_pallas:
        return ref.rac_value_ref(tsi, tid, tp_last, t_last, alpha, t_now)
    interp = _is_cpu() if interpret is None else interpret
    n = tsi.shape[0]
    tp = _pad_to(tsi.astype(jnp.float32), 0, _RV_BN)
    ti = _pad_to(tid.astype(jnp.int32), 0, _RV_BN)
    out = rac_value_pallas(tp, ti, tp_last, t_last, alpha, t_now,
                           interpret=interp)
    return out[:n]


@_counted
@functools.partial(jax.jit, static_argnames=("alpha", "t_now", "use_pallas",
                                             "interpret"))
def rac_value(tsi, tid, tp_last, t_last, alpha: float, t_now: int, *,
              use_pallas: bool = True, interpret: bool | None = None):
    """RAC Eq.1 scoring over the resident table."""
    return rac_value_raw(tsi, tid, tp_last, t_last, alpha, t_now,
                         use_pallas=use_pallas, interpret=interpret)


def victim_value_raw(tsi, tid, occ, tp_last, t_last, t_now, *, alpha: float,
                     use_pallas: bool = True, interpret: bool | None = None):
    """Un-jitted occupancy-masked Eq.1 body shared by :func:`victim_value`,
    :func:`fused_decide`, and the sharded backend (per-shard scoring of its
    slice of the slot table).  ``t_now`` may be a traced int32 scalar —
    unlike :func:`rac_value`'s static ``t_now=0`` + host timestamp shift,
    the decision path keeps the uploaded ``t_last`` table fixed and lets
    simulation time advance at runtime."""
    if not use_pallas:
        return ref.victim_value_ref(tsi, tid, occ, tp_last, t_last,
                                    t_now, alpha)
    interp = _is_cpu() if interpret is None else interpret
    n = tsi.shape[0]
    ts = _pad_to(tsi.astype(jnp.float32), 0, _VV_BN)
    ti = _pad_to(tid.astype(jnp.int32), 0, _VV_BN)
    oc = _pad_to(occ.astype(jnp.int32), 0, _VV_BN)      # pad rows score +inf
    out = victim_value_pallas(ts, ti, oc, tp_last, t_last, t_now, alpha,
                              interpret=interp)
    return out[:n]


@_counted
@functools.partial(jax.jit, static_argnames=("alpha", "use_pallas",
                                             "interpret"))
def victim_value(tsi, tid, occ, tp_last, t_last, t_now, *, alpha: float,
                 use_pallas: bool = True, interpret: bool | None = None):
    """Occupancy-masked RAC Eq.1 over the fixed-shape slot table with a
    runtime ``t_now`` (free slots score +inf)."""
    return victim_value_raw(tsi, tid, occ, tp_last, t_last,
                            jnp.int32(t_now), alpha=alpha,
                            use_pallas=use_pallas, interpret=interpret)


@_counted
@functools.partial(jax.jit, static_argnames=("alpha", "use_pallas",
                                             "interpret"))
def victim_value_multi(tsi, tid, occ, tp_last, t_last, t_now, *,
                       alpha: float, use_pallas: bool = True,
                       interpret: bool | None = None):
    """Policy-stacked occupancy-masked Eq.1: the victim-score leg of the
    arena's batched-over-policy decision surface.

    ``tsi``/``tid``/``occ`` are ``(P, N)`` slot tables, ``tp_last``/
    ``t_last`` ``(P, T)`` topic tables; returns ``(P, N)`` victim values
    (free slots ``+inf``) from one dispatch — the multi-policy analogue of
    :func:`victim_value`, for policy sets whose eviction scoring is
    table-driven (stacked RAC variants)."""
    if not use_pallas:
        return jax.vmap(
            lambda a, b, c, d, e: ref.victim_value_ref(
                a, b, c, d, e, jnp.int32(t_now), alpha)
        )(tsi, tid, occ, tp_last, t_last)
    interp = _is_cpu() if interpret is None else interpret
    n = tsi.shape[1]
    ts = _pad_to(tsi.astype(jnp.float32), 1, _VV_BN)
    ti = _pad_to(tid.astype(jnp.int32), 1, _VV_BN)
    oc = _pad_to(occ.astype(jnp.int32), 1, _VV_BN)      # pad rows score +inf
    out = victim_value_multi_pallas(ts, ti, oc,
                                    tp_last.astype(jnp.float32),
                                    t_last.astype(jnp.int32),
                                    jnp.int32(t_now), alpha,
                                    interpret=interp)
    return out[:, :n]


def fused_decide_raw(queries, slab, n_valid, reps, n_topics, tsi, tid, occ,
                     tp_last, t_last, t_now, *, alpha: float,
                     use_pallas: bool = True, interpret: bool | None = None):
    """Un-jitted fused decision body (also run per shard by the sharded
    backend): hit Top-1 + routing Top-1 + masked victim values."""
    hit_vals, hit_idx = sim_top1_raw(queries, slab, n_valid,
                                     use_pallas=use_pallas,
                                     interpret=interpret)
    route_vals, route_idx = sim_top1_raw(queries, reps, n_topics,
                                         use_pallas=use_pallas,
                                         interpret=interpret)
    victim = victim_value_raw(tsi, tid, occ, tp_last, t_last, t_now,
                              alpha=alpha, use_pallas=use_pallas,
                              interpret=interpret)
    return hit_vals, hit_idx, route_vals, route_idx, victim


@_counted
@functools.partial(jax.jit, static_argnames=("alpha", "use_pallas",
                                             "interpret"))
def fused_decide(queries, slab, n_valid, reps, n_topics, tsi, tid, occ,
                 tp_last, t_last, t_now, *, alpha: float,
                 use_pallas: bool = True, interpret: bool | None = None):
    """One fused decision dispatch per replay chunk.

    Composes ``sim_top1`` over the resident slab (hit determination, masked
    to the runtime resident count ``n_valid``), ``sim_top1`` over the dense
    topic-representative table (Alg. 4 routing, masked to the runtime topic
    high-water mark ``n_topics``), and the occupancy-masked Eq. 1 victim
    kernel — all under one jit, so a replay chunk costs one host→device
    round-trip regardless of chunk size or fill level."""
    return fused_decide_raw(queries, slab, jnp.int32(n_valid), reps,
                            jnp.int32(n_topics), tsi, tid, occ, tp_last,
                            t_last, jnp.int32(t_now), alpha=alpha,
                            use_pallas=use_pallas, interpret=interpret)


@_counted
@functools.partial(jax.jit, static_argnames=("alpha", "t_now", "use_pallas",
                                             "interpret"))
def rac_value_masked(tsi, tid, tp_last, t_last, valid, alpha: float,
                     t_now: int, *, use_pallas: bool = True,
                     interpret: bool | None = None):
    """RAC Eq.1 over a block table with a structural-validity mask.

    ``valid`` (bool, same shape as ``tsi``) marks entries that are legal
    eviction victims; invalid rows (e.g. radix blocks with live children,
    or the chain tip currently being extended) score ``+inf`` so a
    min-value victim scan can never elect them.  One fused jit: the Eq.1
    kernel plus the mask select, no host round-trip between them."""
    vals = rac_value_raw(tsi, tid, tp_last, t_last, alpha, t_now,
                         use_pallas=use_pallas, interpret=interpret)
    return jnp.where(valid, vals, jnp.inf)


@_counted
@functools.partial(jax.jit, static_argnames=("alpha", "use_pallas",
                                             "interpret"))
def decide_aux(queries, reps, n_topics, tsi, tid, occ, tp_last, t_last,
               t_now, *, alpha: float, use_pallas: bool = True,
               interpret: bool | None = None):
    """Auxiliary decision legs in one dispatch: routing Top-1 over the
    dense topic-representative table plus the occupancy-masked Eq.1 victim
    values.

    The approximate-lookup decide path can't use :func:`fused_decide` (its
    hit leg comes from the quantized/pruned pipeline instead of a dense
    ``sim_top1``), but its remaining legs — Alg. 4 routing and victim
    scoring — still fuse, so a decide chunk costs the fused-lookup launch
    plus exactly one aux launch instead of two separate dispatches."""
    route_vals, route_idx = sim_top1_raw(queries, reps, jnp.int32(n_topics),
                                         use_pallas=use_pallas,
                                         interpret=interpret)
    victim = victim_value_raw(tsi, tid, occ, tp_last, t_last,
                              jnp.int32(t_now), alpha=alpha,
                              use_pallas=use_pallas, interpret=interpret)
    return route_vals, route_idx, victim
