"""Pallas TPU kernels: batched cosine-similarity Top-1 and Top-K retrieval.

This is the semantic cache's hit-determination hot spot (the paper: "hit
determination itself requires costly similarity computation").  TPU-native
design: the (queries × candidates) score tile is one MXU matmul per grid
cell; a running (max, argmax) merge lives in the revisited output block
while candidate tiles stream HBM→VMEM.

Top-K (``sim_topk_pallas``) generalizes the merge: the revisited output
block holds the running (K values, K indices) per query, and each
candidate tile is folded in by K select-and-mask passes over the
``[running | tile]`` concatenation — K is small (shortlists, promotion
scans), so the extra VPU work is negligible next to the MXU matmul.
Ties break toward the lower candidate index, matching a stable descending
host sort.

``n_valid`` is a *runtime* scalar delivered through scalar prefetch
(``PrefetchScalarGridSpec``), so compacted and per-shard stores can mask
their free tail without recompiling as the resident count changes — the
kernel sees one stable (Q, N, D) shape per store geometry.

Tiling: (BQ=128 queries × BC=512 candidates × D) per grid cell; with D=128
fp32 that is  128·128·4 + 512·128·4 + 128·512·4  ≈ 0.6 MB of VMEM per cell,
MXU-aligned on every matmul dim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BQ = 128      # query tile
BC = 512      # candidate tile

# fp32 scores must be exact fp32 products: the safety predicates of the
# quantized and pruned paths bound error at fp32 scale, and the host
# oracle scores in fp32.  On a v5e, Mosaic's default contraction rounds
# f32 operands to bf16 (errors near 4e-4 for unit rows at D=384), so the
# precision is pinned.
_F32 = jax.lax.Precision.HIGHEST


def _first_max_index(x, m, index):
    """Lowest ``index`` per row among the entries of ``x`` equal to the row
    max ``m`` (BQ, 1).  ``jnp.argmax`` on the chip does not promise the
    first of equal maxima (an all -inf row came back as its last lane), so
    the lower-index tie rule is spelled out."""
    big = jnp.iinfo(jnp.int32).max
    return jnp.min(jnp.where(x == m, index, big), axis=1)[:, None]


def _sim_top1_kernel(nv_ref, q_ref, c_ref, val_ref, idx_ref):
    """grid = (nq, nc); candidate axis is a sequential reduction.

    ``nv_ref`` is the scalar-prefetched resident count: columns at or past
    it (free tail rows, padding) are masked to -inf before the merge."""
    j = pl.program_id(1)
    n_valid = nv_ref[0]
    q = q_ref[...]                                   # (BQ, D)
    c = c_ref[...]                                   # (BC, D)
    scores = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32)          # (BQ, BC) on the MXU
    col = j * BC + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(col < n_valid, scores, -jnp.inf)
    m = jnp.max(scores, axis=1)[:, None]
    a = _first_max_index(scores, m, col)

    @pl.when(j == 0)
    def _init():
        val_ref[...] = m
        idx_ref[...] = a

    @pl.when(j > 0)
    def _merge():
        prev = val_ref[...]
        take = m > prev
        val_ref[...] = jnp.where(take, m, prev)
        idx_ref[...] = jnp.where(take, a, idx_ref[...])


def sim_top1_pallas(queries: jnp.ndarray, candidates: jnp.ndarray,
                    n_valid, *, interpret: bool):
    """queries (Q, D), candidates (N, D) both padded to tile multiples;
    returns (vals (Q,), idx (Q,)).  ``n_valid`` is a runtime scalar (python
    int or traced int32) masking the candidate tail — free slots beyond the
    resident high-water mark and padding rows never win Top-1.

    The kernel writes (Q, 1) columns: a 1-D output's tiling in XLA grows
    with its length (512 for Q=384) while Mosaic tiles a (BQ,) block by
    128, which the TPU compiler refuses."""
    q_n, d = queries.shape
    c_n = candidates.shape[0]
    assert q_n % BQ == 0 and c_n % BC == 0 and d % 128 == 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_n // BQ, c_n // BC),
        in_specs=[pl.BlockSpec((BQ, d), lambda i, j, nv: (i, 0)),
                  pl.BlockSpec((BC, d), lambda i, j, nv: (j, 0))],
        out_specs=[pl.BlockSpec((BQ, 1), lambda i, j, nv: (i, 0)),
                   pl.BlockSpec((BQ, 1), lambda i, j, nv: (i, 0))])
    vals, idx = pl.pallas_call(
        _sim_top1_kernel,
        grid_spec=grid_spec,
        name="sim_top1",
        out_shape=[jax.ShapeDtypeStruct((q_n, 1), jnp.float32),
                   jax.ShapeDtypeStruct((q_n, 1), jnp.int32)],
        interpret=interpret,
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), queries, candidates)
    return vals[:, 0], idx[:, 0]

def _topk_fold(k: int, j, scores, col, val_ref, idx_ref):
    """Fold one masked score tile into the running per-query Top-K held in
    the revisited output block: K select-and-mask passes over the
    ``[running | tile]`` concatenation.  The running list is sorted
    descending with ties already resolved toward lower candidate index,
    and it sits left of the (higher-index) tile columns, so taking the
    first lane of equal maxima keeps "lower candidate index wins"
    globally.  Shared by the fp32 and int8 Top-K kernels — survivor sets
    are therefore selected identically in both."""

    @pl.when(j == 0)
    def _init():
        val_ref[...] = jnp.full((BQ, k), -jnp.inf, jnp.float32)
        idx_ref[...] = jnp.full((BQ, k), 0, jnp.int32)

    comb_v = jnp.concatenate([val_ref[...], scores], axis=1)
    comb_i = jnp.concatenate([idx_ref[...], col], axis=1)
    new_v, new_i = [], []
    lane = jax.lax.broadcasted_iota(jnp.int32, comb_v.shape, 1)
    for _ in range(k):
        m = jnp.max(comb_v, axis=1)[:, None]         # (BQ, 1)
        hit = lane == _first_max_index(comb_v, m, lane)
        # one-hot max instead of gather: the selected lane's index
        # (indices are >= 0, so the -1 fill never wins)
        new_v.append(m[:, 0])
        new_i.append(jnp.max(jnp.where(hit, comb_i, -1), axis=1))
        comb_v = jnp.where(hit, -jnp.inf, comb_v)
    val_ref[...] = jnp.stack(new_v, axis=1)
    idx_ref[...] = jnp.stack(new_i, axis=1)


def _make_sim_topk_kernel(k: int):
    """Build a Top-K kernel for a static K (K is a compile-time constant:
    it sizes the revisited output block)."""

    def _sim_topk_kernel(nv_ref, q_ref, c_ref, val_ref, idx_ref):
        # grid = (nq, nc); candidate axis is a sequential reduction over a
        # running per-query Top-K kept in the revisited output block.
        j = pl.program_id(1)
        n_valid = nv_ref[0]
        q = q_ref[...]                                   # (BQ, D)
        c = c_ref[...]                                   # (BC, D)
        scores = jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32)          # (BQ, BC) on the MXU
        col = j * BC + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(col < n_valid, scores, -jnp.inf)
        _topk_fold(k, j, scores, col, val_ref, idx_ref)

    return _sim_topk_kernel


def _make_sim_topk_q8_kernel(k: int):
    """Quantized-slab Top-K: int8 query and candidate tiles hit the MXU as
    an int8×int8→int32 matmul (the tile streams HBM→VMEM at a quarter the
    fp32 bytes — the whole point), then per-row scales rescale the exact
    integer scores into fp32 approximate similarities.  The scale multiply
    order ``(acc * qs) * cs`` is fixed across this kernel, the jnp oracle,
    and the numpy host gemm so all engines emit bit-identical scores."""

    def _sim_topk_q8_kernel(nv_ref, q_ref, qs_ref, c_ref, cs_ref,
                            val_ref, idx_ref):
        j = pl.program_id(1)
        n_valid = nv_ref[0]
        q = q_ref[...]                                   # (BQ, D) int8
        c = c_ref[...]                                   # (BC, D) int8
        acc = jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)            # exact int32 scores
        scores = (acc.astype(jnp.float32) * qs_ref[...]) * cs_ref[...]
        col = j * BC + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(col < n_valid, scores, -jnp.inf)
        _topk_fold(k, j, scores, col, val_ref, idx_ref)

    return _sim_topk_q8_kernel


def sim_topk_pallas(queries: jnp.ndarray, candidates: jnp.ndarray,
                    n_valid, k: int, *, interpret: bool):
    """queries (Q, D), candidates (N, D) padded to tile multiples; returns
    (vals (Q, K), idx (Q, K)) sorted descending, ties toward the lower
    candidate index.  ``n_valid`` is a runtime scalar masking the candidate
    tail; slots past it come back as (-inf, undefined-index) rows that the
    caller maps to (-inf, -1)."""
    q_n, d = queries.shape
    c_n = candidates.shape[0]
    assert q_n % BQ == 0 and c_n % BC == 0 and d % 128 == 0
    assert 1 <= k <= c_n
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_n // BQ, c_n // BC),
        in_specs=[pl.BlockSpec((BQ, d), lambda i, j, nv: (i, 0)),
                  pl.BlockSpec((BC, d), lambda i, j, nv: (j, 0))],
        out_specs=[pl.BlockSpec((BQ, k), lambda i, j, nv: (i, 0)),
                   pl.BlockSpec((BQ, k), lambda i, j, nv: (i, 0))])
    return pl.pallas_call(
        _make_sim_topk_kernel(k),
        grid_spec=grid_spec,
        name="sim_topk",
        out_shape=[jax.ShapeDtypeStruct((q_n, k), jnp.float32),
                   jax.ShapeDtypeStruct((q_n, k), jnp.int32)],
        interpret=interpret,
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), queries, candidates)


def sim_topk_q8_pallas(q8: jnp.ndarray, qscale: jnp.ndarray,
                       c8: jnp.ndarray, cscale: jnp.ndarray,
                       n_valid, k: int, *, interpret: bool):
    """Top-K over a per-row-quantized slab: ``q8`` (Q, D) int8 with
    ``qscale`` (Q,) fp32, ``c8`` (N, D) int8 with ``cscale`` (N,) fp32,
    all padded to tile multiples (zero rows quantize to zero, so padding
    is exact).  Returns (vals (Q, K), idx (Q, K)) of *approximate* fp32
    similarities, same ordering/tie contract as ``sim_topk_pallas``.

    The scales enter the kernel as (Q, 1) and (1, N) blocks: a 1-D
    operand's tiling in XLA (1024) differs from Mosaic's (512), which the
    TPU compiler refuses."""
    q_n, d = q8.shape
    c_n = c8.shape[0]
    assert q_n % BQ == 0 and c_n % BC == 0 and d % 128 == 0
    assert 1 <= k <= c_n
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_n // BQ, c_n // BC),
        in_specs=[pl.BlockSpec((BQ, d), lambda i, j, nv: (i, 0)),
                  pl.BlockSpec((BQ, 1), lambda i, j, nv: (i, 0)),
                  pl.BlockSpec((BC, d), lambda i, j, nv: (j, 0)),
                  pl.BlockSpec((1, BC), lambda i, j, nv: (0, j))],
        out_specs=[pl.BlockSpec((BQ, k), lambda i, j, nv: (i, 0)),
                   pl.BlockSpec((BQ, k), lambda i, j, nv: (i, 0))])
    return pl.pallas_call(
        _make_sim_topk_q8_kernel(k),
        grid_spec=grid_spec,
        name="sim_topk_q8",
        out_shape=[jax.ShapeDtypeStruct((q_n, k), jnp.float32),
                   jax.ShapeDtypeStruct((q_n, k), jnp.int32)],
        interpret=interpret,
    )(jnp.asarray(n_valid, jnp.int32).reshape(1),
      q8, qscale.reshape(q_n, 1), c8, cscale.reshape(1, c_n))
