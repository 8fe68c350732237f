"""Device-resident fused lookup pipeline.

One jitted program (per static shape bucket) runs the whole approximate
lookup: topic routing → CSR candidate gather → int8 candidate scan →
fp32 union rescore → the ``resolve_pruned``/``resolve_topk`` safety
predicates — entirely on device.  The host gets back one compact result
tuple (winner slot, rescored sim, certification mask, ledger counts) and
only exact-rescans the uncertified rows, instead of interleaving 4–6
dispatches with blocking ``np.asarray`` syncs per chunk the way the
staged drivers in :mod:`repro.cache.pruned`/:mod:`repro.cache.quantized`
do.

Decision parity
---------------
The predicates move to the device but their arms do not change, and the
certified outputs are bit-equal to the exact scan by construction:

* Candidate *selection* is approximate (int8 scores — exact integer
  arithmetic via ``preferred_element_type=int32``, identical across
  batching shapes), but every *reported* similarity comes from the same
  per-pair fp32 kernel math as the exact path: the union of all
  shortlists is sorted by slot id and rescored with ``sim_top1_raw``, so
  a certified winner carries exactly the fp32 bits the full-slab scan
  would have produced, with the same lowest-slot tie rule (the union is
  slot-sorted, and the kernel breaks ties toward the lower index).
* The exclusion threshold ``kth + eps`` and the routing bound are
  evaluated in fp32 on device with an absolute + relative inflation
  (``x + |x|·1e-6 + 1e-6`` after the already-padded ``eps``), so fp32
  rounding can only *add* fallbacks, never certify something the f64
  host predicate would not have.
* ``tau`` comparisons use ``tau_lo`` — the largest float32 strictly
  below ``tau`` — so the device predicate ``v <= tau_lo`` is *exactly*
  the host predicate ``float64(v) < tau`` for any float32 ``v``.

Bucket padding policy
---------------------
Batch is padded to the next power of two (floor 1 — every padded row
pays a full ``cap_c``-row gather, and the serving path is ``b=1``); the
candidate width to a
geometric grid (powers of two plus the 1.5× midpoints, floor 64) sized
from the top-``P`` bucket counts and the probe budget, so a steady-state
chunk loop compiles once per bucket and re-uses that executable for the
rest of the run.  No buffer is donated: the outputs are per-row vectors
that no query buffer could alias, and importing this module must not
pick a backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .ops import count_launch, route_topics_raw, sim_top1_raw, sim_topk_q8_raw
from .quant import quantize_rows_int8

#: Shortlist width when the pruned path runs without a composed
#: quantized config (the fused scan is always int8 — see docs).
DEFAULT_K = 8

#: Driver-side ledger: calls into the fused pipeline, rows that fell back
#: to the exact scan, rows whose probe set was budget-capped.
fused_stats = {"calls": 0, "fallback_rows": 0, "capped_rows": 0}


def reset_stats() -> None:
    for k in fused_stats:
        fused_stats[k] = 0


def compile_counts() -> dict:
    """Number of distinct executables per fused entry point — the
    compile-count monitor the stability test asserts on."""
    return {"pruned": int(_fused_pruned_jit._cache_size()),
            "quant": int(_fused_quant_jit._cache_size())}


# ---------------------------------------------------------------------------
# static-bucket helpers (host side)

def pad_pow2(n: int, min_b: int = 8) -> int:
    """Smallest power of two ≥ ``n`` (floor ``min_b``)."""
    b = min_b
    while b < n:
        b *= 2
    return b


def pad_geo(n: int, min_b: int = 64) -> int:
    """Smallest bucket ≥ ``n`` from the geometric grid {64, 96, 128, 192,
    256, ...} — powers of two plus their 1.5× midpoints.  Roughly halves
    the worst-case overshoot of pure pow2 buckets for the candidate dim,
    which directly multiplies gather bytes."""
    b = min_b
    while True:
        if b >= n:
            return b
        mid = b + b // 2
        if mid >= n:
            return mid
        b *= 2


@functools.lru_cache(maxsize=64)
def tau_lo_f32(tau: float) -> np.float32:
    """Largest float32 strictly below ``tau`` (a float64 threshold).

    For float32 ``v``, ``v <= tau_lo_f32(tau)`` holds iff
    ``float64(v) < tau`` — the device-side form of the staged drivers'
    f64 certain-miss comparisons."""
    t = np.float32(tau)
    while float(t) >= float(tau):
        t = np.nextafter(t, np.float32(-np.inf))
    return t


def prep_queries(queries: np.ndarray, bq: int):
    """Pad a query chunk to the ``bq`` batch bucket and quantize it.

    Returns ``(qp, q8, qscale, ql1)`` — fp32 queries, their int8 mirror,
    per-row scales, and the f32-inflated L1 norms the device-side error
    bound consumes (cast rounding is swallowed by the 1e-6 relative pad,
    keeping the bound an upper bound)."""
    q = np.ascontiguousarray(queries, dtype=np.float32)
    b = q.shape[0]
    if bq > b:
        q = np.pad(q, ((0, bq - b), (0, 0)))
    q8, qs, ql1 = quantize_rows_int8(q)
    ql1_32 = (ql1 * (1.0 + 1e-6)).astype(np.float32)
    return q, q8, qs.astype(np.float32), ql1_32


def csr_device_arrays(indptr: np.ndarray, slot_ids: np.ndarray,
                      unassigned: np.ndarray, t_rows: int):
    """Pack the topic-bucket CSR plus the unassigned segment for device
    upload: ``indptr_dev`` has ``t_rows + 2`` entries (segment ``t_rows``
    is the always-scanned unassigned block) and ``slots_dev`` is padded to
    a pow2 bucket so membership churn doesn't force recompiles."""
    n_mem = int(indptr[-1]) if indptr.size else 0
    slots = np.concatenate([np.asarray(slot_ids, np.int64),
                            np.asarray(unassigned, np.int64)])
    npad = pad_pow2(max(int(slots.size), 1), 64)
    out = np.zeros(npad, np.int32)
    out[: slots.size] = slots
    ip = np.zeros(t_rows + 2, np.int32)
    ip[: t_rows + 1] = indptr
    ip[t_rows + 1] = n_mem + int(unassigned.size)
    return ip, out


def candidate_cap(counts: np.ndarray, n_una: int, probes: int,
                  budget: int) -> int:
    """Static candidate width for the gather: the unassigned block plus
    the smaller of the probe budget and the ``probes`` largest bucket
    counts — an upper bound on any query's candidate total, computed
    without a device sync."""
    p = int(min(probes, counts.size))
    if p <= 0:
        top = 0
    elif p >= counts.size:
        top = int(counts.sum())
    else:
        top = int(np.partition(counts, -p)[-p:].sum())
    return pad_geo(max(1, int(n_una) + min(int(budget), top)))


# ---------------------------------------------------------------------------
# fused bodies

def _union_rescore(qp, emb, u_slots, u_valid, *, use_pallas, interpret):
    """Rescore the (slot-sorted) union of all shortlists in fp32 with the
    same kernel as the exact scan, returning each query's max and the
    lowest winning slot.  Sorting by slot id makes the kernel's
    lowest-*index* tie rule the exact path's lowest-*slot* rule."""
    n_slots = emb.shape[0]
    big = jnp.int32(n_slots)
    flat = jnp.where(u_valid, u_slots.astype(jnp.int32), big).reshape(-1)
    order = jnp.sort(flat)                      # sentinels sort last
    n_u = jnp.sum(u_valid.astype(jnp.int32))
    blk = jnp.take(emb, jnp.minimum(order, n_slots - 1), axis=0)
    rvals, ridx = sim_top1_raw(qp, blk, n_u, use_pallas=use_pallas,
                               interpret=interpret)
    win = jnp.take(order, jnp.clip(ridx, 0, order.shape[0] - 1))
    win = jnp.where(jnp.isfinite(rvals), win, big)
    return win, rvals, n_u


def _eps_f32(ql1, qsc, cl1_max, cs_max, dim):
    """Device-side int8 error bound, padded: the staged ``scan_margin``
    terms evaluated in f32 with 1.06×+1e-6 inflation (vs the host's
    1.05×+1e-7) so f32 rounding of the bound itself stays conservative."""
    eps = (jnp.float32(0.5) * ql1 * cs_max
           + jnp.float32(0.5) * cl1_max * qsc
           + jnp.float32(0.25) * jnp.float32(dim) * qsc * cs_max)
    return eps * jnp.float32(1.06) + jnp.float32(1e-6)


def _inflate(thresh):
    """Absolute + relative inflation of a finite f32 threshold so device
    f32 comparisons can only be *more* conservative than the staged f64
    predicate (−inf passes through untouched)."""
    guard = jnp.where(jnp.isfinite(thresh),
                      jnp.abs(thresh) * jnp.float32(1e-6) + jnp.float32(1e-6),
                      jnp.float32(0.0))
    return thresh + guard


def _fused_pruned_body(qp, q8q, qsc, ql1, emb, q8s, csc, cl1, aug, indptr,
                      slots, n_topics, budget, b_real, tau_lo, *, probes,
                      cap_c, k, armed, use_pallas, interpret):
    """route → cap → CSR gather → int8 scan → fp32 union rescore →
    safety predicates, one trace.  See the module docstring for the
    parity argument; shapes: ``qp (B,D)``, ``emb/q8s (N,D)``,
    ``aug (T,D+1)``, ``indptr (T+2,)``, ``slots (Npad,)``."""
    bsz, dim = qp.shape
    t_rows = aug.shape[0]

    # ---- stage 1: routing (same kernel + k contract as ops.route_topics)
    k_route = min(probes + 1, t_rows)
    vals, tids = route_topics_raw(qp, aug, n_topics, k_route,
                                  use_pallas=use_pallas, interpret=interpret)
    n_pc = min(probes, k_route)
    if vals.shape[1] <= n_pc:      # no natural unprobed-bound column
        vals_e = jnp.concatenate(
            [vals, jnp.full((bsz, 1), -jnp.inf, vals.dtype)], axis=1)
    else:
        vals_e = vals
    pv = vals[:, :n_pc]
    pt = jnp.clip(tids[:, :n_pc], 0, max(t_rows - 1, 0))
    live = jnp.isfinite(pv)

    # ---- stage 2: adaptive probe cap — same greedy prefix rule as the
    # staged driver (cumulative bucket rows ≤ budget); dead columns sort
    # last so the kept set is always a prefix.
    cnt = jnp.where(live, jnp.take(indptr, pt + 1) - jnp.take(indptr, pt), 0)
    csum = jnp.cumsum(cnt, axis=1)
    allowed = jnp.cumprod((csum <= budget).astype(jnp.int32), axis=1) > 0
    take = live & allowed
    p_i = jnp.sum(take.astype(jnp.int32), axis=1)
    ub = jnp.take_along_axis(vals_e, p_i[:, None], axis=1)[:, 0]
    capped = jnp.any(live & ~allowed, axis=1)
    if armed:
        skip = vals[:, 0] <= tau_lo        # certain-miss routing arm
        take = take & ~skip[:, None]
        p_i = jnp.where(skip, 0, p_i)
        ub = jnp.where(skip, vals[:, 0], ub)
        capped = capped & ~skip

    # ---- stage 3: CSR candidate gather.  Per-query segments = kept
    # probes' buckets + the always-scanned unassigned block; position →
    # segment via searchsorted over the per-query segment-end cumsum.
    seg_cnt = jnp.where(take, cnt, 0)
    n_una = indptr[t_rows + 1] - indptr[t_rows]
    ends = jnp.cumsum(
        jnp.concatenate(
            [seg_cnt, jnp.full((bsz, 1), n_una, seg_cnt.dtype)], axis=1),
        axis=1)
    total = ends[:, -1]
    pos = jnp.arange(cap_c, dtype=jnp.int32)
    # searchsorted(e, pos, "right") over ≤ probes+1 segment ends is just
    # a count of ends ≤ pos — the closed form avoids XLA CPU lowering
    # the vmapped binary search to a serial while loop
    seg = jnp.sum((ends[:, :, None] <= pos[None, None, :]).astype(jnp.int32),
                  axis=1)
    seg = jnp.minimum(seg, n_pc).astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros((bsz, 1), ends.dtype), ends[:, :-1]], axis=1)
    off = pos[None, :] - jnp.take_along_axis(starts, seg, axis=1)
    topic = jnp.take_along_axis(pt, jnp.minimum(seg, n_pc - 1), axis=1)
    base = jnp.where(seg < n_pc, jnp.take(indptr, topic), indptr[t_rows])
    cvalid = pos[None, :] < total[:, None]
    cand = jnp.take(slots, jnp.clip(base + off, 0, slots.shape[0] - 1))
    cand = jnp.where(cvalid, cand, 0)

    # ---- stage 4: int8 candidate scan (exact integer accumulate; the
    # fixed (acc·qs)·cs order matches the q8 kernels bit-for-bit).
    c8 = jnp.take(q8s, cand, axis=0)
    acc = jax.lax.dot_general(q8q, c8, (((1,), (2,)), ((0,), (0,))),
                              preferred_element_type=jnp.int32)
    cs_g = jnp.take(csc, cand)
    scores = jnp.where(cvalid,
                       (acc.astype(jnp.float32) * qsc[:, None]) * cs_g,
                       -jnp.inf)
    cs_max = jnp.max(jnp.where(cvalid, cs_g, 0.0), axis=1)
    cl1_max = jnp.max(jnp.where(cvalid, jnp.take(cl1, cand), 0.0), axis=1)
    eps = _eps_f32(ql1, qsc, cl1_max, cs_max, dim)

    # ---- stage 5: shortlist + exclusion threshold
    k_eff = min(k, cap_c)
    svals, spos = jax.lax.top_k(scores, k_eff)
    kth = svals[:, -1]
    covers = total <= k_eff
    thresh = _inflate(jnp.where(jnp.isfinite(kth) & ~covers,
                                kth + eps, -jnp.inf))

    # ---- stage 6: fp32 union rescore (exact per-pair kernel math)
    row_ok = jnp.arange(bsz, dtype=jnp.int32) < b_real
    u_slots = jnp.take_along_axis(cand, spos, axis=1)
    u_valid = jnp.isfinite(svals) & row_ok[:, None]
    win, rmax, n_u = _union_rescore(qp, emb, u_slots, u_valid,
                                    use_pallas=use_pallas,
                                    interpret=interpret)

    # ---- stage 7: safety predicates (resolve_topk + resolve_pruned arms)
    cert = rmax > jnp.maximum(thresh, ub)
    if armed:
        cert = cert | ((rmax <= tau_lo) & (thresh <= tau_lo)
                       & (ub <= tau_lo))
    probed = jnp.sum((take & (cnt > 0)).astype(jnp.int32), axis=1)
    return (win, rmax, ub, cert, total, probed, capped.astype(jnp.int32),
            n_u)


def _fused_quant_body(qp, q8q, qsc, ql1, emb, q8s, csc, cl1, n_valid, b_real,
                     tau_lo, *, k, armed, use_pallas, interpret):
    """Pure-quantized fused lookup: full-slab int8 Top-K (the same
    ``sim_topk_q8`` kernel launch the staged path makes) + fp32 union
    rescore + the ``resolve_topk`` arms, one trace."""
    bsz, dim = qp.shape
    n_slots = q8s.shape[0]
    vals, rows = sim_topk_q8_raw(q8q, qsc, q8s, csc, n_valid, k,
                                 use_pallas=use_pallas, interpret=interpret)
    m = jnp.arange(n_slots, dtype=jnp.int32) < n_valid
    cs_max = jnp.max(jnp.where(m, csc, 0.0))
    cl1_max = jnp.max(jnp.where(m, cl1, 0.0))
    eps = _eps_f32(ql1, qsc, cl1_max, cs_max, dim)
    kth = vals[:, -1]
    covers = n_valid <= vals.shape[1]
    thresh = _inflate(jnp.where(jnp.isfinite(kth) & ~covers,
                                kth + eps, -jnp.inf))
    row_ok = jnp.arange(bsz, dtype=jnp.int32) < b_real
    u_valid = jnp.isfinite(vals) & row_ok[:, None]
    win, rmax, n_u = _union_rescore(qp, emb, rows, u_valid,
                                    use_pallas=use_pallas,
                                    interpret=interpret)
    cert = rmax > thresh
    if armed:
        cert = cert | ((rmax <= tau_lo) & (thresh <= tau_lo))
    return win, rmax, cert, n_u


_fused_pruned_jit = jax.jit(
    _fused_pruned_body, static_argnames=("probes", "cap_c", "k", "armed",
                                         "use_pallas", "interpret"))

_fused_quant_jit = jax.jit(
    _fused_quant_body, static_argnames=("k", "armed", "use_pallas",
                                        "interpret"))


def fused_pruned_lookup(qp, q8q, qsc, ql1, emb, q8s, csc, cl1, aug, indptr,
                        slots, n_topics, budget, b_real, tau, *, probes,
                        cap_c, k, use_pallas=True, interpret=None):
    """One-launch pruned (optionally quantize-composed) lookup.  ``tau``
    is the f64 hit threshold or None; everything else is device-ready.
    Returns the raw device tuple — callers slice off padding rows."""
    armed = tau is not None
    t_lo = tau_lo_f32(tau) if armed else np.float32(0.0)
    fused_stats["calls"] += 1
    count_launch()
    # numpy scalars on purpose: they ride the jit fast path, where eager
    # jnp casts would each dispatch a convert_element_type per call
    return _fused_pruned_jit(qp, q8q, qsc, ql1, emb, q8s, csc, cl1, aug,
                             indptr, slots, np.int32(n_topics),
                             np.int32(budget), np.int32(b_real),
                             np.float32(t_lo), probes=int(probes),
                             cap_c=int(cap_c), k=int(k), armed=armed,
                             use_pallas=use_pallas, interpret=interpret)


def fused_quant_lookup(qp, q8q, qsc, ql1, emb, q8s, csc, cl1, n_valid,
                       b_real, tau, *, k, use_pallas=True, interpret=None):
    """One-launch pure-quantized lookup (full-slab int8 Top-K + rescore +
    predicates).  Same conventions as :func:`fused_pruned_lookup`."""
    armed = tau is not None
    t_lo = tau_lo_f32(tau) if armed else np.float32(0.0)
    fused_stats["calls"] += 1
    count_launch()
    return _fused_quant_jit(qp, q8q, qsc, ql1, emb, q8s, csc, cl1,
                            np.int32(n_valid), np.int32(b_real),
                            np.float32(t_lo), k=int(k), armed=armed,
                            use_pallas=use_pallas, interpret=interpret)
