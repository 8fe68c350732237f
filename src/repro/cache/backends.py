"""Pluggable lookup/scoring backends behind :class:`repro.cache.SemanticCache`.

A backend answers three questions over the resident slab
(:class:`repro.core.store.ResidentStore`) and the RAC scoring state
(:class:`repro.core.policy_table.PolicyTable`):

  - Top-1 retrieval: for a (batch of) query embedding(s), which resident
    entry is most similar, and how similar?  (hit determination)
  - RAC value scoring: Eq. 1 ``TP(Z_q)·TSI(q)`` over the resident table.
    (eviction scoring)
  - Fused decision scoring (``decide_batch``): hit Top-1 + Alg. 4 topic
    routing against the representative table + occupancy-masked Eq. 1
    victim values, all from ONE launch per query chunk — the replay loop's
    and the serving engine's snapshot scoring surface.

Three implementations with identical hit decisions:

  - :class:`NumpyBackend` — the host path: masked matmul over the dense
    slab (exactly ``ResidentStore.nearest`` for single queries, so the
    refactored simulator stays bit-for-bit with the historical loop).
  - :class:`KernelBackend` — the device path: one ``kernels/ops.sim_top1``
    call scores the whole query batch against the fixed-shape slab up to
    the store's high-water mark (the resident count is a scalar-prefetched
    runtime value, so one XLA compilation serves every fill level), and
    ``kernels/ops.rac_value`` scores evictions.  Free slots hold zero
    embeddings: a zero row can only win Top-1 when every real similarity
    is negative, in which case the query is far below any sensible
    ``tau_hit`` and is reported as a miss ``(-1, -inf)`` — the same
    *decision* the numpy path makes.
  - :class:`~repro.cache.sharded.ShardedKernelBackend` (``"sharded"``) —
    the multi-device path: the slab is row-partitioned across a 1-D cache
    mesh and ``sim_top1`` runs per shard under ``shard_map`` with an
    argmax-reduce merge (see ``repro/cache/sharded.py``).

Backends are stateless with respect to the host store: they read the store
that is passed in, so one backend instance can serve many caches and
``checkpoint()/restore()`` needs no backend cooperation.  Device backends
keep *mirrors* — device copies of the host arrays keyed by the owners'
globally-unique mutation versions, kept fresh by scattering only the rows
the :class:`~repro.core.store.MutationJournal` reports dirty (a full
re-upload only on a journal miss, a shape change, or bulk churn).  The
embedding slab mirrors against the store's journal; the policy table's
slot and topic array families mirror against its two journals the same
way, which is what makes the whole decision state device-resident.
"""
from __future__ import annotations

from typing import Optional, Protocol, runtime_checkable

import numpy as np

from repro.core.policy_table import PolicyTable
from repro.core.store import ResidentStore
from repro.telemetry.tracing import annotate

from .pruned import (TopicBucketIndex, account_prune, as_pruned_config,
                     new_prune_stats, pruned_top1_batch, route_topics_host)
from .quantized import (QuantizedSlabMirror, account_scan,
                        as_quantized_config, new_quant_stats, resolve_topk)
from .types import DecisionBatch


@runtime_checkable
class LookupBackend(Protocol):
    """Protocol every lookup/scoring backend implements."""

    name: str

    def top1(self, store: ResidentStore,
             query: np.ndarray) -> tuple[int, float]:
        """Top-1 resident for one query -> (cid, sim) or (-1, -inf)."""
        ...

    def top1_batch(self, store: ResidentStore,
                   queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Top-1 residents for (B, D) queries -> (cids (B,), sims (B,))."""
        ...

    def top1_rows(self, store: ResidentStore, queries: np.ndarray,
                  rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Top-1 restricted to the given store ``rows`` (slot indices) —
        the same cosine scoring as :meth:`top1_batch`, so an incremental
        rescan over recently-admitted rows can never disagree with a full
        peek near ``tau_hit``."""
        ...

    def topk_rows(self, store: ResidentStore, queries: np.ndarray,
                  rows: np.ndarray, k: int
                  ) -> tuple[np.ndarray, np.ndarray]:
        """Top-K restricted to the given store ``rows`` (slot indices) —
        the K-generalization of :meth:`top1_rows`, behind the host-tier
        promotion scan and shortlist peeks.  Returns ((B, K) cids, (B, K)
        sims) sorted descending per query, ties toward the lower row
        position; ranks past the restriction size are ``(-1, -inf)``."""
        ...

    def rac_value(self, tsi: np.ndarray, tids: np.ndarray,
                  tp_last: np.ndarray, t_last: np.ndarray,
                  alpha: float, t_now: int) -> np.ndarray:
        """RAC Eq. 1 ``2^(-alpha·(t_now - t_last[tid])) · TP_last[tid] · tsi``."""
        ...

    def rac_value_masked(self, tsi: np.ndarray, tids: np.ndarray,
                         tp_last: np.ndarray, t_last: np.ndarray,
                         alpha: float, t_now: int,
                         valid: np.ndarray) -> np.ndarray:
        """Eq. 1 with a validity mask: invalid entries score ``+inf``
        (used by radix block eviction, where structurally-protected blocks
        must never win the min-value victim scan)."""
        ...

    def decide_batch(self, store: ResidentStore,
                     table: Optional[PolicyTable], queries: np.ndarray, *,
                     alpha: float = 0.0, t_now: int = 0) -> DecisionBatch:
        """Fused snapshot decision scoring for a (B, D) query block: hit
        Top-1 + routing Top-1 + masked Eq. 1 victim values in one launch.
        ``table=None`` (baseline policies) degrades to hit Top-1 only."""
        ...

    def top1_multi(self, arena, queries: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
        """Policy-stacked Top-1 over an :class:`~repro.core.arena.
        ArenaStore`'s (P, S, D) slab — the multi-policy arena's snapshot
        scoring surface.  Returns ((P, B) cids, (P, B) sims); each row is
        exactly the answer :meth:`top1_batch` would give for that policy's
        store view."""
        ...


def small_delta(n_dirty: int, n_rows: int) -> bool:
    """The shared dirty-row sync policy: a delta this small is worth a
    device scatter; anything bigger re-uploads in full.  One definition
    for every mirror (``_DeviceMirror``, the sharded slab caches), so the
    threshold can never drift between copies."""
    return n_dirty <= max(64, n_rows // 4)


def bucket_rows(rows: np.ndarray, bucket: int = 64) -> np.ndarray:
    """Pad a sorted dirty-row index vector to a ``bucket`` multiple by
    repeating the last row (re-setting a row to the same value is a
    no-op), so XLA compiles one scatter per bucket, not one per distinct
    dirty count.  Shared by every dirty-row scatter path."""
    pad = (-len(rows)) % bucket
    if pad:
        rows = np.pad(rows, (0, pad), mode="edge")
    return rows


class _DeviceMirror:
    """Device copy of equally-row-indexed host arrays, kept fresh by
    dirty-row scatter against a :class:`MutationJournal`'s answers.

    ``sync(version, dirty_since, host_fn)`` returns jnp arrays of the
    ``dtypes`` declared at construction.  Same version → cached as-is with
    ZERO host work (``host_fn`` is only called on staleness, and the
    incremental branch casts only the dirty rows, so steady state is
    O(mutated rows) on the host too); journal-answerable small delta →
    ``.at[rows].set`` scatter; anything else (foreign lineage, aged-out
    journal, array growth, bulk churn) → full upload."""

    def __init__(self, dtypes: dict):
        self.dtypes = dtypes
        self.version = None
        self.arrays: Optional[dict] = None
        # "bytes" = host->device traffic this mirror moved (scattered rows
        # for incremental syncs, whole arrays for full uploads)
        self.stats = {"full": 0, "incremental": 0, "rows": 0, "bytes": 0}

    def sync(self, version: int, dirty_since, host_fn) -> dict:
        import jax.numpy as jnp
        if self.arrays is not None and version == self.version:
            return self.arrays
        host = host_fn()                       # raw host arrays, no casts
        dirty = None
        if self.arrays is not None and all(
                self.arrays[k].shape == v.shape for k, v in host.items()):
            dirty = dirty_since(self.version)
        n_rows = next(iter(host.values())).shape[0]
        if dirty is not None and small_delta(len(dirty), n_rows):
            if dirty:
                rows = bucket_rows(np.fromiter(sorted(dirty),
                                               dtype=np.int64,
                                               count=len(dirty)))
                out = {}
                for k, v in host.items():
                    block = np.asarray(v[rows], dtype=self.dtypes[k])
                    out[k] = self.arrays[k].at[rows].set(block)
                    self.stats["bytes"] += block.nbytes
                self.arrays = out
                self.stats["incremental"] += 1
                self.stats["rows"] += len(dirty)
        else:
            self.arrays = {k: jnp.asarray(np.asarray(v, self.dtypes[k]))
                           for k, v in host.items()}
            self.stats["full"] += 1
            self.stats["bytes"] += sum(
                v.size * np.dtype(self.dtypes[k]).itemsize
                for k, v in host.items())
        self.version = version
        return self.arrays


class NumpyBackend:
    """Host-side slab scan (the historical ``ResidentStore.nearest`` path).

    With ``quantized`` set (a :class:`~repro.cache.quantized.
    QuantizedLookupConfig`, or ``True``/a dict spec) this is the quantized
    path's *host oracle*: the same per-row int8 mirror, an exact int8 gemm
    (``kernels.quant.int8_scores``) instead of the Pallas scan, and the
    shared rescore/certify driver — bit-identical survivor scores to the
    device engines, so the whole quantized decision stack can be parity-
    tested without a device."""

    name = "numpy"

    def __init__(self, quantized=None, pruned=None):
        self.quantized = as_quantized_config(quantized)
        self.quant_stats = new_quant_stats()
        self._qhost = QuantizedSlabMirror()
        self._qhost_arena = QuantizedSlabMirror()
        # topic-pruned two-stage scan (cache/pruned.py): the facade wires
        # route_table/route_store when the acting policy exposes a
        # PolicyTable; run_arena wires route_tables (one per policy)
        self.pruned = as_pruned_config(pruned)
        self.prune_stats = new_prune_stats()
        self._pidx = TopicBucketIndex()
        self._pidx_arena: dict[int, TopicBucketIndex] = {}
        self.route_table = None
        self.route_store = None

    def top1(self, store: ResidentStore, query: np.ndarray) -> tuple[int, float]:
        if self.quantized is not None or self.pruned is not None:
            cids, sims = self.top1_batch(store, np.asarray(query)[None, :])
            return int(cids[0]), float(sims[0])
        return store.nearest(query)

    def top1_batch(self, store: ResidentStore,
                   queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        queries = np.asarray(queries, dtype=np.float32)
        b = queries.shape[0]
        if not store.slot_of:
            return (np.full(b, -1, dtype=np.int64),
                    np.full(b, -np.inf, dtype=np.float64))
        if self.pruned is not None:
            out = self._top1_batch_pruned(store, queries)
            if out is not None:
                return out
        if self.quantized is not None:
            return self._top1_batch_quantized(store, queries)
        return self._top1_batch_exact(store, queries)

    def _top1_batch_exact(self, store: ResidentStore,
                          queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        b = queries.shape[0]
        if not store.slot_of:
            return (np.full(b, -1, dtype=np.int64),
                    np.full(b, -np.inf, dtype=np.float64))
        sims = queries @ store.emb.T                      # (B, n_slots)
        sims[:, ~store.occ] = -np.inf
        idx = np.argmax(sims, axis=1)
        return (store.cid[idx].copy(),
                sims[np.arange(b), idx].astype(np.float64))

    def _top1_batch_quantized(self, store: ResidentStore, queries: np.ndarray
                              ) -> tuple[np.ndarray, np.ndarray]:
        """int8-gemm candidate scan over the host mirror + fp32 rescore.
        Scans slots up to the high-water mark (free rows are zeros — a
        certified free-row winner means every real score was negative,
        the same miss decision the masked exact scan makes)."""
        from repro.kernels.quant import (int8_scores, quantize_rows_int8,
                                         scan_margin)
        b = queries.shape[0]
        hwm, dim = store.hwm, store.emb.shape[1]
        qm = self._qhost.sync(store.version, store.dirty_since, store.emb)
        q8, qs, ql1 = quantize_rows_int8(queries)
        scores = (int8_scores(q8, qm.q8[:hwm])
                  * qs[:, None]) * qm.scale[None, :hwm]
        k = min(self.quantized.k, hwm)
        # stable descending sort = the kernel merge's lower-index tie break
        order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
        vals = np.take_along_axis(scores, order, axis=1).astype(np.float64)
        eps = scan_margin(qs, ql1, qm.scale, qm.l1, dim)
        cids, sims, n_fb, n_union = resolve_topk(
            vals, order, eps, self.quantized.k >= hwm,
            self.quantized.tau_hit,
            lambda rows: self.top1_rows(store, queries, rows),
            lambda sel: self._top1_batch_exact(store, queries[sel]))
        account_scan(self.quant_stats, n_valid=hwm, dim=dim, batch=b,
                     n_union=n_union, n_fallback=n_fb)
        return cids, sims

    def _top1_batch_pruned(self, store: ResidentStore, queries: np.ndarray
                           ) -> Optional[tuple]:
        """Topic-pruned two-stage scan, host oracle: host routing matmul,
        gathered-rows candidate scans (int8 when ``quantized`` is also
        set), and the shared certify-or-fallback driver.  Returns ``None``
        when the routing surface isn't wired for this store (table-less
        policies, foreign stores like arena views) so the caller falls
        through to the quantized/exact paths."""
        table = self.route_table
        if table is None or store is not self.route_store:
            return None
        dim = store.emb.shape[1]
        probes = self.pruned.probes

        if self.quantized is not None:
            scan = self._make_pruned_q8_scan_host(store, queries)
        else:
            def scan(sel, rows):
                c, s = self.top1_rows(store, queries[sel], rows)
                return c, s, rows.size * dim * 4

        return pruned_top1_batch(
            store, table, queries, self.pruned, self._pidx,
            self.prune_stats,
            route_fn=lambda qs, aug, nt: route_topics_host(qs, aug, nt,
                                                           probes),
            scan_fn=scan,
            exact_fn=lambda sel: self._top1_batch_exact(store, queries[sel]))

    def _make_pruned_q8_scan_host(self, store: ResidentStore,
                                  queries: np.ndarray):
        """Stage-2 scan composing ``quantized_lookup``: the gathered
        candidate block is scanned over the int8 host mirror and certified
        by the inner ``resolve_topk`` predicate *within the candidate
        set* (its fallback leg re-scans only the candidates — outer
        certification against unprobed topics still happens in the pruned
        driver).  Gathered int8 + rescore bytes land in the prune ledger;
        the quant ledger is untouched on this path."""
        from repro.kernels.quant import (int8_scores, quantize_rows_int8,
                                         scan_margin)
        dim = store.emb.shape[1]
        qm = self._qhost.sync(store.version, store.dirty_since, store.emb)
        k_cfg = self.quantized.k
        tau = self.quantized.tau_hit

        def scan(sel, rows):
            qs_q = queries[sel]
            q8, qsc, ql1 = quantize_rows_int8(qs_q)
            scores = (int8_scores(q8, qm.q8[rows])
                      * qsc[:, None]) * qm.scale[rows][None, :]
            k = min(k_cfg, rows.size)
            order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
            vals = np.take_along_axis(scores, order,
                                      axis=1).astype(np.float64)
            eps = scan_margin(qsc, ql1, qm.scale[rows], qm.l1[rows], dim)
            # local shortlist indices are ascending positions into the
            # ascending ``rows``, so the rescore keeps the lower-slot tie
            # contract within the candidate set
            cids, sims, n_fb, n_union = resolve_topk(
                vals, order, eps, k_cfg >= rows.size, tau,
                lambda lr: self.top1_rows(store, qs_q, rows[lr]),
                lambda ss: self.top1_rows(store, qs_q[ss], rows))
            nbytes = (rows.size * (dim + 4) + n_union * dim * 4
                      + (rows.size * dim * 4 if n_fb else 0))
            return cids, sims, nbytes

        return scan

    def _top1_multi_pruned(self, arena, queries: np.ndarray
                           ) -> Optional[tuple]:
        """Per-policy pruned pass over the arena's store views: each
        table-backed policy runs the two-stage driver against its own
        :class:`TopicBucketIndex`; table-less policies take a per-view
        exact scan (same per-row dots as the stacked gemm).  Returns
        ``None`` when ``run_arena`` didn't wire ``route_tables``."""
        tables = getattr(self, "route_tables", None)
        if tables is None:
            return None
        if not arena.track_rows:
            raise ValueError("pruned top1_multi needs an ArenaStore "
                             "built with track_rows=True")
        b = queries.shape[0]
        n_pol = arena.occ.shape[0]
        dim = arena.emb.shape[-1]
        probes = self.pruned.probes
        out_c = np.full((n_pol, b), -1, dtype=np.int64)
        out_s = np.full((n_pol, b), -np.inf)
        for p in range(n_pol):
            view = arena.views[p]
            if not view.slot_of:
                continue
            table = tables[p] if p < len(tables) else None
            if table is None:
                cids, sims = self._top1_batch_exact(view, queries)
            else:
                idx = self._pidx_arena.setdefault(p, TopicBucketIndex())
                cids, sims = pruned_top1_batch(
                    view, table, queries, self.pruned, idx,
                    self.prune_stats,
                    route_fn=lambda qs, aug, nt: route_topics_host(
                        qs, aug, nt, probes),
                    scan_fn=lambda sel, rows, v=view: (
                        *self.top1_rows(v, queries[sel], rows),
                        rows.size * dim * 4),
                    exact_fn=lambda sel, v=view: self._top1_batch_exact(
                        v, queries[sel]))
            out_c[p], out_s[p] = cids, sims
        return out_c, out_s

    def top1_rows(self, store: ResidentStore, queries: np.ndarray,
                  rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        queries = np.asarray(queries, dtype=np.float32)
        rows = np.asarray(rows, dtype=np.int64)
        sims = queries @ store.emb[rows].T                # (B, len(rows))
        best = np.argmax(sims, axis=1)
        b = np.arange(queries.shape[0])
        return (store.cid[rows[best]].copy(),
                sims[b, best].astype(np.float64))

    def topk_rows(self, store: ResidentStore, queries: np.ndarray,
                  rows: np.ndarray, k: int
                  ) -> tuple[np.ndarray, np.ndarray]:
        queries = np.asarray(queries, dtype=np.float32)
        rows = np.asarray(rows, dtype=np.int64)
        b = queries.shape[0]
        cids = np.full((b, k), -1, dtype=np.int64)
        sims = np.full((b, k), -np.inf, dtype=np.float64)
        if rows.size == 0:
            return cids, sims
        scores = queries @ store.emb[rows].T              # (B, len(rows))
        # stable descending sort: equal scores keep ascending row position,
        # matching the kernel merge's lower-candidate-index tie break
        order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
        kk = order.shape[1]
        cids[:, :kk] = store.cid[rows[order]]
        sims[:, :kk] = np.take_along_axis(scores, order,
                                          axis=1).astype(np.float64)
        return cids, sims

    def top1_multi(self, arena, queries: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
        """Host stacked pass: ONE (B, P*S) gemm scores the chunk against
        every policy's slab.  Free slots hold zero embeddings, so instead
        of masking, a zero row that wins maps to cid -1 → ``-inf`` — the
        same *decision* the masked per-view scan makes (a zero can only
        win when every real similarity is negative, far below any sensible
        ``tau_hit``); gate-adjacent outcomes are re-scored by the
        reference engine via the arena's epsilon flags."""
        queries = np.asarray(queries, dtype=np.float32)
        if self.pruned is not None:
            out = self._top1_multi_pruned(arena, queries)
            if out is not None:
                return out
        if self.quantized is not None:
            return self._top1_multi_quantized(arena, queries)
        b = queries.shape[0]
        n_pol, n_slots = arena.occ.shape
        flat = arena.emb.reshape(n_pol * n_slots, -1)
        sims3 = (queries @ flat.T).reshape(b, n_pol, n_slots)
        idx = sims3.argmax(axis=2)                        # (B, P)
        vals = np.take_along_axis(sims3, idx[:, :, None],
                                  axis=2)[:, :, 0]        # (B, P)
        cids = arena.cid[np.arange(n_pol)[None, :], idx].T.copy()
        sims = np.where(cids >= 0, vals.T.astype(np.float64), -np.inf)
        return cids, sims

    def _top1_multi_quantized(self, arena, queries: np.ndarray
                              ) -> tuple[np.ndarray, np.ndarray]:
        """Stacked host oracle of the quantized arena scan: one int8 gemm
        over the flat (P*S, D) mirror, then the shared per-policy
        rescore/certify driver against each policy's store view."""
        from repro.kernels.quant import (int8_scores, quantize_rows_int8,
                                         scan_margin)
        if not arena.track_rows:
            raise ValueError("quantized top1_multi needs an ArenaStore "
                             "built with track_rows=True")
        b = queries.shape[0]
        n_pol, n_slots = arena.occ.shape
        dim = arena.emb.shape[-1]
        qm = self._qhost_arena.sync(
            arena.version, arena.dirty_since,
            arena.emb.reshape(n_pol * n_slots, dim))
        q8, qs, ql1 = quantize_rows_int8(queries)
        scores3 = ((int8_scores(q8, qm.q8)
                    * qs[:, None]) * qm.scale[None, :]
                   ).reshape(b, n_pol, n_slots)
        scale2 = qm.scale.reshape(n_pol, n_slots)
        l12 = qm.l1.reshape(n_pol, n_slots)
        hwms = arena.hwms()
        k_cfg = self.quantized.k
        out_c = np.full((n_pol, b), -1, dtype=np.int64)
        out_s = np.full((n_pol, b), -np.inf)
        for p in range(n_pol):
            hw = int(hwms[p])
            if hw == 0:
                continue
            scores = scores3[:, p, :hw]
            k = min(k_cfg, hw)
            order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
            vals = np.take_along_axis(scores, order,
                                      axis=1).astype(np.float64)
            eps = scan_margin(qs, ql1, scale2[p], l12[p], dim)
            view = arena.views[p]
            cids, sims, n_fb, n_union = resolve_topk(
                vals, order, eps, k_cfg >= hw, self.quantized.tau_hit,
                lambda rows, v=view: self.top1_rows(v, queries, rows),
                lambda sel, v=view: self._top1_batch_exact(v, queries[sel]))
            account_scan(self.quant_stats, n_valid=hw, dim=dim, batch=b,
                         n_union=n_union, n_fallback=n_fb)
            out_c[p], out_s[p] = cids, sims
        return out_c, out_s

    def rac_value(self, tsi, tids, tp_last, t_last, alpha, t_now):
        decay = 0.5 ** (alpha * (t_now - t_last[tids]))
        return decay * tp_last[tids] * tsi

    def rac_value_masked(self, tsi, tids, tp_last, t_last, alpha, t_now,
                         valid):
        vals = self.rac_value(tsi, tids, tp_last, t_last, alpha, t_now)
        return np.where(np.asarray(valid, dtype=bool), vals, np.inf)

    def decide_batch(self, store, table, queries, *, alpha=0.0, t_now=0):
        """Host oracle of the fused decision pass (see the protocol)."""
        queries = np.asarray(queries, dtype=np.float32)
        b = queries.shape[0]
        hit_cid, hit_sim = self.top1_batch(store, queries)
        route_tid = np.full(b, -1, dtype=np.int64)
        route_sim = np.full(b, -np.inf, dtype=np.float64)
        victim = None
        if table is not None:
            k = table.topic_hwm
            live_tids = np.flatnonzero(table.rep_valid[:k])
            if live_tids.size:
                # score live topics only: tids are never recycled, so the
                # dense table is mostly retired rows — the gather keeps the
                # host oracle O(live topics), with identical decisions (a
                # retired row could never win a gated route anyway)
                sims = queries @ table.rep[live_tids].T      # (B, live)
                best = np.argmax(sims, axis=1)
                route_sim = sims[np.arange(b), best].astype(np.float64)
                route_tid = live_tids[best].astype(np.int64)
            victim = self.rac_value_masked(
                table.tsi, np.maximum(table.topic_of, 0), table.tp_last,
                table.t_last, alpha, t_now, store.occ)
        return DecisionBatch(hit_cid, hit_sim, route_tid, route_sim, victim)


class KernelBackend:
    """Device path: batched Top-1 via the ``sim_top1`` Pallas kernel and
    eviction scoring via the ``rac_value`` kernel.

    The full (capacity+1, D) slab is passed every call so XLA sees one
    stable shape; query batches are padded up to a multiple of ``q_pad``
    for the same reason.  ``use_pallas=False`` routes through the jnp
    oracles (useful on CPU where interpret-mode overhead dominates).

    The fused decision path keeps the whole scoring state device-resident:
    three :class:`_DeviceMirror`\\ s hold the embedding slab + occupancy
    (synced against the store's mutation journal, and scanned by the exact
    lookup as well), the policy table's slot
    slabs (tsi/topic, its slot journal), and its topic tables (TP state +
    representatives, its topic journal).  Steady-state replay therefore
    moves O(mutated rows) per chunk, not O(capacity).
    """

    name = "kernel"

    def __init__(self, use_pallas: bool = True,
                 interpret: bool | None = None, q_pad: int = 8,
                 quantized=None, pruned=None):
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.q_pad = max(1, q_pad)
        self.quantized = as_quantized_config(quantized)
        self.quant_stats = new_quant_stats()
        # topic-pruned two-stage scan (cache/pruned.py): the facade wires
        # route_table/route_store when the acting policy exposes a
        # PolicyTable; run_arena wires route_tables (one per policy)
        self.pruned = as_pruned_config(pruned)
        self.prune_stats = new_prune_stats()
        self._pidx = TopicBucketIndex()
        self._pidx_arena: dict[int, TopicBucketIndex] = {}
        self.route_table = None
        self.route_store = None
        # the (T, D+1) augmented routing matrix [rep | spread], mirrored
        # against the bucket index's own journal
        self._route_mirror = _DeviceMirror({"aug": np.float32})
        self._store_mirror = _DeviceMirror({"emb": np.float32,
                                            "occ": np.int32})
        self._slot_mirror = _DeviceMirror({"tsi": np.float32,
                                           "tid": np.int32})
        self._topic_mirror = _DeviceMirror({"rep": np.float32,
                                            "tp": np.float32,
                                            "tl": np.int32})
        # the arena's stacked (P*S, D) slab, synced against its flat journal
        self._arena_mirror = _DeviceMirror({"emb": np.float32})
        # quantized path: host int8 requantizers + their device mirrors,
        # all keyed on the same journal versions as the fp32 mirrors (the
        # int8 uploads land in sync_stats "bytes" like any other mirror)
        self._qhost = QuantizedSlabMirror()
        self._qhost_arena = QuantizedSlabMirror()
        self._q8_mirror = _DeviceMirror({"q8": np.int8,
                                         "scale": np.float32,
                                         "l1": np.float32})
        self._q8_arena_mirror = _DeviceMirror({"q8": np.int8,
                                               "scale": np.float32,
                                               "l1": np.float32})
        # fused pipeline: device CSR copy of the topic-bucket index, keyed
        # on the index's (store, table) journal triple — NOT its aug
        # version (unassigned-only churn doesn't move the aug journal)
        self._csr_mirror = _DeviceMirror({"indptr": np.int32,
                                          "slots": np.int32})
        self._csr_arena: dict[int, _DeviceMirror] = {}
        self._tracker = None                # telemetry sink (observation-only)
        self._sync_seen: dict[str, int] = {}   # last sync_stats flushed to it

    def set_tracker(self, tracker) -> None:
        """Attach a :class:`repro.telemetry.Tracker` child; the backend
        emits ``sync.*`` counter deltas after each fused decision pass.
        Strictly observation-only — decisions are unaffected."""
        self._tracker = tracker

    def _flush_sync(self) -> None:
        """Emit the since-last-flush delta of ``sync_stats`` as counters."""
        trk = self._tracker
        if trk is None:
            return
        stats = self.sync_stats
        for k, v in stats.items():
            d = v - self._sync_seen.get(k, 0)
            if d:
                trk.count(f"sync.{k}", d)
        self._sync_seen = stats

    @property
    def sync_stats(self) -> dict:
        """Aggregate mirror observability: full uploads vs dirty-row
        scatters, total rows scattered, and host→device bytes moved."""
        mirrors = (self._store_mirror, self._slot_mirror,
                   self._topic_mirror, self._arena_mirror,
                   self._q8_mirror, self._q8_arena_mirror,
                   self._route_mirror, self._csr_mirror,
                   *self._csr_arena.values())
        return {k: sum(m.stats[k] for m in mirrors)
                for k in ("full", "incremental", "rows", "bytes")}

    @property
    def dispatch_stats(self) -> dict:
        """Launch/transfer observability (``ops.dispatch_stats``): jitted
        dispatches issued, blocking device→host syncs, host wall seconds
        from dispatch to ready (``kernel_s``: uploads of host arguments
        included, not kernel time), and ``upload_s``/``uploads``, which
        stay at zero (see ``ops.dispatch_stats``).  Process-global (the jit
        caches are too) — consumers read deltas."""
        from repro.kernels import ops
        return dict(ops.dispatch_stats)

    def top1(self, store: ResidentStore, query: np.ndarray) -> tuple[int, float]:
        cids, sims = self.top1_batch(store, np.asarray(query)[None, :])
        return int(cids[0]), float(sims[0])

    def top1_batch(self, store: ResidentStore,
                   queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        queries = np.asarray(queries, dtype=np.float32)
        b = queries.shape[0]
        if not store.slot_of:
            return (np.full(b, -1, dtype=np.int64),
                    np.full(b, -np.inf, dtype=np.float64))
        if self.pruned is not None:
            out = self._top1_batch_pruned(store, queries)
            if out is not None:
                return out
        if self.quantized is not None:
            return self._top1_batch_quantized(store, queries)
        return self._top1_batch_exact(store, queries)

    def _store_slab(self, store: ResidentStore) -> dict:
        """``_store_mirror`` freshened against ``store``: nothing when the
        version is unchanged, a dirty-row scatter, or a full upload (churn,
        or a store of another lineage, such as an arena view)."""
        return self._store_mirror.sync(
            store.version, store.dirty_since,
            lambda: {"emb": store.emb, "occ": store.occ})

    def _top1_batch_exact(self, store: ResidentStore,
                          queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        import jax                                     # deferred, as ops is

        from repro.kernels import ops
        b = queries.shape[0]
        if not store.slot_of:
            return (np.full(b, -1, dtype=np.int64),
                    np.full(b, -np.inf, dtype=np.float64))
        pad = (-b) % self.q_pad
        qp = np.pad(queries, ((0, pad), (0, 0))) if pad else queries
        # the slab comes from the journal-synced mirror, in a span of its
        # own that ends when the sync's transfer is done, so a trace tells
        # it from the scan.  Unbound: the sharded backend runs this body
        # for dense arena views
        with annotate("rac/upload"):
            slab = jax.block_until_ready(
                KernelBackend._store_slab(self, store)["emb"])
        # runtime n_valid = the store's high-water mark: slots past it have
        # never been occupied, so the kernel skips scoring the free tail
        # (one compilation — the count is scalar-prefetched, not baked in)
        with annotate("rac/sim_top1"):
            vals, idx = ops.run_timed(
                lambda: ops.sim_top1(qp, slab, n_valid=store.hwm,
                                     use_pallas=self.use_pallas,
                                     interpret=self.interpret))
        vals = np.asarray(ops.to_host(vals)[:b], dtype=np.float64)
        idx = ops.to_host(idx)[:b]
        cids = store.cid[idx].copy()
        # a free (zeroed) slot can only win when all real sims < 0 → miss
        sims = np.where(cids >= 0, vals, -np.inf)
        self._flush_sync()
        return cids, sims

    def _top1_batch_quantized(self, store: ResidentStore,
                              queries: np.ndarray
                              ) -> tuple[np.ndarray, np.ndarray]:
        """Quantized candidate scan: the device streams the int8 mirror
        (4× fewer slab bytes) through ``sim_topk_q8``, then the ≤k
        survivors are rescored in fp32 by :meth:`top1_rows` — the same
        restricted-scan engine the admission rescans trust — and certified
        by the shared safety predicate (exact full scan on failure)."""
        from repro.kernels import ops
        from repro.kernels.quant import quantize_rows_int8, scan_margin
        b = queries.shape[0]
        dim = store.emb.shape[1]
        qm = self._qhost.sync(store.version, store.dirty_since, store.emb)
        dev = self._q8_mirror.sync(
            store.version, store.dirty_since,
            lambda: {"q8": qm.q8, "scale": qm.scale, "l1": qm.l1})
        if self.quantized.fused and b <= self.quantized.fused_max_batch:
            return self._top1_batch_quantized_fused(store, queries, dev)
        pad = (-b) % self.q_pad
        qp = np.pad(queries, ((0, pad), (0, 0))) if pad else queries
        q8, qs, ql1 = quantize_rows_int8(qp)
        k = self.quantized.k
        with annotate("rac/sim_topk_q8"):
            vals, idx = ops.run_timed(
                lambda: ops.sim_topk_q8(q8, qs, dev["q8"], dev["scale"], k,
                                        n_valid=store.hwm,
                                        use_pallas=self.use_pallas,
                                        interpret=self.interpret))
        vals = np.asarray(ops.to_host(vals)[:b], dtype=np.float64)
        rows = ops.to_host(idx)[:b]
        eps = scan_margin(qs[:b], ql1[:b], qm.scale, qm.l1, dim)
        cids, sims, n_fb, n_union = resolve_topk(
            vals, rows, eps, k >= store.hwm, self.quantized.tau_hit,
            lambda r: self.top1_rows(store, queries, r),
            lambda sel: self._top1_batch_exact(store, queries[sel]))
        account_scan(self.quant_stats, n_valid=store.hwm, dim=dim, batch=b,
                     n_union=n_union, n_fallback=n_fb)
        self._flush_sync()
        return cids, sims

    def _top1_batch_pruned(self, store: ResidentStore, queries: np.ndarray
                           ) -> Optional[tuple]:
        """Topic-pruned two-stage scan: stage 1 routes over the mirrored
        (T, D+1) augmented representative matrix (``ops.route_topics``,
        T ≪ S), stage 2 scans only the probed buckets' gathered rows
        (int8 when ``quantized`` is also set), and the shared driver
        certifies each decision against the unprobed-topic bound —
        uncertifiable queries take an exact full-scan fallback.  Returns
        ``None`` when the routing surface isn't wired for this store
        (table-less policies, foreign stores like arena views) so the
        caller falls through to the quantized/exact paths."""
        from repro.kernels import ops
        table = self.route_table
        if table is None or store is not self.route_store:
            return None
        cfg = self.pruned
        idx = self._pidx
        dim = store.emb.shape[1]

        if cfg.fused and queries.shape[0] <= cfg.fused_max_batch \
                and cfg.probes >= 1 and table.rep.shape[0] >= 1 \
                and store.hwm > 0:
            idx.sync(store, table)
            # unbound on purpose: the sharded backend delegates its whole
            # pruned pass here and carries the same mirror attributes but
            # not these helpers
            out = KernelBackend._fused_pruned_batch(self, store, table,
                                                    queries, cfg, idx)
            self._flush_sync()
            return out

        def route(qs, aug, n_top):
            # the driver synced ``idx`` already; freshen the device copy
            # of the aug matrix against the index's own journal
            dev = self._route_mirror.sync(idx.version, idx.dirty_since,
                                          lambda: {"aug": idx.aug})
            b = qs.shape[0]
            pad = (-b) % self.q_pad
            qp = np.pad(qs, ((0, pad), (0, 0))) if pad else qs
            with annotate("rac/route_topics"):
                vals, tids = ops.run_timed(
                    lambda: ops.route_topics(
                        qp, dev["aug"], cfg.probes, n_valid=n_top,
                        use_pallas=self.use_pallas,
                        interpret=self.interpret))
            return ops.to_host(vals)[:b], ops.to_host(tids)[:b]

        if self.quantized is not None:
            # unbound on purpose: the sharded backend delegates its whole
            # pruned pass here and carries the same mirror attributes but
            # not this helper
            scan = KernelBackend._make_pruned_q8_scan(self, store, queries)
        else:
            def scan(sel, rows):
                c, s = self.top1_rows(store, queries[sel], rows)
                return c, s, rows.size * dim * 4

        out = pruned_top1_batch(
            store, table, queries, cfg, idx, self.prune_stats,
            route_fn=route, scan_fn=scan,
            exact_fn=lambda sel: self._top1_batch_exact(store, queries[sel]))
        self._flush_sync()
        return out

    def _top1_batch_quantized_fused(self, store: ResidentStore,
                                    queries: np.ndarray, dev
                                    ) -> tuple[np.ndarray, np.ndarray]:
        """One-launch quantized lookup (``kernels/fused.py``): the int8
        Top-K, the fp32 union rescore, and the ``resolve_topk`` safety
        arms run inside one jitted program; the host maps winner slots to
        cids and exact-rescans only the uncertified rows.  The fp32 slab
        stays mirrored on device for the union gather — a capacity (not
        bandwidth) cost relative to the staged path: the scan itself still
        streams only int8 bytes."""
        from repro.kernels import fused, ops
        b, dim = queries.shape
        cfg = self.quantized
        slab = self._store_slab(store)
        bq = fused.pad_pow2(b, 1)       # pow2 bucket, floor 1 (serving b=1)
        qp, q8q, qsc, ql1 = fused.prep_queries(queries, bq)
        n_slots = store.emb.shape[0]
        with annotate("rac/fused_quant"):
            out = ops.run_timed(
                lambda: fused.fused_quant_lookup(
                    qp, q8q, qsc, ql1, slab["emb"], dev["q8"],
                    dev["scale"], dev["l1"], store.hwm, b, cfg.tau_hit,
                    k=min(int(cfg.k), n_slots), use_pallas=self.use_pallas,
                    interpret=self.interpret))
        win, rmax, cert, n_u = ops.to_host_tuple(out)
        win = win[:b].astype(np.int64)
        rmax = np.asarray(rmax[:b], dtype=np.float64)
        certm = cert[:b].astype(bool)
        ok = win < n_slots                       # sentinel = no finite score
        cids = np.where(ok, store.cid[np.minimum(win, n_slots - 1)], -1)
        sims = np.where(cids >= 0, rmax, -np.inf)
        n_fb = int(b - np.count_nonzero(certm))
        if n_fb:
            sel = np.flatnonzero(~certm)
            f_c, f_s = self._top1_batch_exact(store, queries[sel])
            cids[sel] = np.asarray(f_c, dtype=np.int64)
            sims[sel] = np.asarray(f_s, dtype=np.float64)
        account_scan(self.quant_stats, n_valid=store.hwm, dim=dim, batch=b,
                     n_union=int(n_u), n_fallback=n_fb)
        fused.fused_stats["fallback_rows"] += n_fb
        self._flush_sync()
        return cids, sims

    def _fused_pruned_batch(self, store: ResidentStore, table: PolicyTable,
                            queries: np.ndarray, cfg, idx):
        """Mirror-freshening wrapper of :meth:`_fused_pruned_call` for a
        single journaled store (``idx`` must already be synced).  The int8
        mirror is maintained even without a composed quantized config —
        the fused candidate scan is always int8 (see docs)."""
        qm = self._qhost.sync(store.version, store.dirty_since, store.emb)
        slab = KernelBackend._store_slab(self, store)
        q8d = self._q8_mirror.sync(
            store.version, store.dirty_since,
            lambda: {"q8": qm.q8, "scale": qm.scale, "l1": qm.l1})
        augd = self._route_mirror.sync(idx.version, idx.dirty_since,
                                       lambda: {"aug": idx.aug})
        return KernelBackend._fused_pruned_call(
            self, store, table, queries, cfg, idx, emb_dev=slab["emb"],
            q8_dev=q8d, aug_dev=augd["aug"], csr_mirror=self._csr_mirror,
            slot_off=0, n_slots=store.emb.shape[0], cid_arr=store.cid,
            exact_fn=lambda sel: self._top1_batch_exact(store, queries[sel]),
            stats=self.prune_stats)

    def _fused_pruned_call(self, store, table, queries: np.ndarray, cfg,
                           idx, *, emb_dev, q8_dev, aug_dev, csr_mirror,
                           slot_off: int, n_slots: int, cid_arr,
                           exact_fn, stats: dict):
        """Shared fused-pruned driver (single stores and arena views):
        prep the static shape buckets, make ONE jitted launch covering
        routing → probe cap → CSR gather → int8 scan → fp32 union rescore
        → safety predicates, then map winners/fallbacks and ledger on the
        host.  ``slot_off`` shifts CSR slot ids into the flat (P·S) arena
        slab; ``n_slots`` is the per-view slot count winners map back
        into (the sentinel row lands outside it)."""
        from repro.kernels import fused, ops
        b, dim = queries.shape
        probes = int(cfg.probes)
        indptr_h, slot_ids, unassigned = idx.csr()
        t_rows = idx.aug.shape[0]
        budget = 1 << 30
        if cfg.max_scan_frac is not None:
            budget = max(int(cfg.min_scan_rows),
                         int(cfg.max_scan_frac * store.hwm))
        cap_c = fused.candidate_cap(np.diff(indptr_h), unassigned.size,
                                    probes, budget)
        csr = csr_mirror.sync(
            (idx.key, t_rows, slot_off), lambda v: None,
            lambda: dict(zip(
                ("indptr", "slots"),
                fused.csr_device_arrays(indptr_h, slot_ids + slot_off,
                                        unassigned + slot_off, t_rows))))
        # pow2 bucket, floor 1: every padded row pays a full cap_c-row
        # gather, and the serving path is b=1
        bq = fused.pad_pow2(b, 1)
        qp, q8q, qsc, ql1 = fused.prep_queries(queries, bq)
        k = (int(self.quantized.k) if self.quantized is not None
             else fused.DEFAULT_K)
        with annotate("rac/fused_pruned"):
            out = ops.run_timed(
                lambda: fused.fused_pruned_lookup(
                    qp, q8q, qsc, ql1, emb_dev, q8_dev["q8"],
                    q8_dev["scale"], q8_dev["l1"], aug_dev, csr["indptr"],
                    csr["slots"], int(table.topic_hwm), budget, b,
                    cfg.tau_hit, probes=probes, cap_c=cap_c, k=k,
                    use_pallas=self.use_pallas, interpret=self.interpret))
        win, rmax, ub, cert, total, probed, capped, n_u = \
            ops.to_host_tuple(out)
        local = win[:b].astype(np.int64) - slot_off
        rmax = np.asarray(rmax[:b], dtype=np.float64)
        certm = cert[:b].astype(bool)
        ok = (local >= 0) & (local < n_slots)
        cids = np.where(ok, cid_arr[np.clip(local, 0, n_slots - 1)], -1)
        sims = np.where(cids >= 0, rmax, -np.inf)
        n_fb = int(b - np.count_nonzero(certm))
        if n_fb:
            sel = np.flatnonzero(~certm)
            f_c, f_s = exact_fn(sel)
            cids[sel] = np.asarray(f_c, dtype=np.int64)
            sims[sel] = np.asarray(f_s, dtype=np.float64)
        tot = int(total[:b].sum())
        ncap = int(capped[:b].sum())
        # gathered int8 candidate bytes (codes + scale + l1) + the fp32
        # union-rescore gather
        slab_bytes = tot * (dim + 8) + int(n_u) * dim * 4
        account_prune(stats, n_valid=int(store.hwm), dim=dim,
                      n_topics=int(table.topic_hwm), batch=b,
                      probes=int(probed[:b].sum()), scanned_rows=tot,
                      slab_bytes=slab_bytes, n_fallback=n_fb,
                      n_capped=ncap)
        fused.fused_stats["fallback_rows"] += n_fb
        fused.fused_stats["capped_rows"] += ncap
        return cids, sims

    def _make_pruned_q8_scan(self, store: ResidentStore,
                             queries: np.ndarray):
        """Stage-2 scan composing ``quantized_lookup``: the gathered
        candidate block is scanned as int8 through ``sim_topk_q8`` and
        certified by the inner ``resolve_topk`` predicate *within the
        candidate set* (its fallback leg re-scans only the candidates —
        outer certification against unprobed topics still happens in the
        pruned driver).  Gathered int8 + rescore bytes land in the prune
        ledger; the quant ledger is untouched on this path."""
        from repro.kernels import ops
        from repro.kernels.quant import quantize_rows_int8, scan_margin
        dim = store.emb.shape[1]
        qm = self._qhost.sync(store.version, store.dirty_since, store.emb)
        k_cfg = self.quantized.k
        tau = self.quantized.tau_hit

        def scan(sel, rows):
            qs_q = queries[sel]
            b = qs_q.shape[0]
            pad = (-b) % self.q_pad
            qp = np.pad(qs_q, ((0, pad), (0, 0))) if pad else qs_q
            q8, qsc, ql1 = quantize_rows_int8(qp)
            # bucket the gathered block like top1_rows so XLA compiles
            # one kernel per bucket, not per distinct candidate count
            n = rows.size
            npad = -(-n // 64) * 64
            c8 = np.zeros((npad, dim), dtype=np.int8)
            c8[:n] = qm.q8[rows]
            csc = np.zeros(npad, dtype=np.float32)
            csc[:n] = qm.scale[rows]
            k = min(k_cfg, n)
            with annotate("rac/sim_topk_q8_pruned"):
                vals, idx = ops.run_timed(
                    lambda: ops.sim_topk_q8(q8, qsc, c8, csc, k, n_valid=n,
                                            use_pallas=self.use_pallas,
                                            interpret=self.interpret))
            vals = np.asarray(ops.to_host(vals)[:b], dtype=np.float64)
            lrows = ops.to_host(idx)[:b]
            eps = scan_margin(qsc[:b], ql1[:b], qm.scale[rows],
                              qm.l1[rows], dim)
            # local shortlist indices are ascending positions into the
            # ascending ``rows``, so the rescore keeps the lower-slot tie
            # contract within the candidate set
            cids, sims, n_fb, n_union = resolve_topk(
                vals, lrows, eps, k_cfg >= n, tau,
                lambda lr: self.top1_rows(store, qs_q, rows[lr]),
                lambda ss: self.top1_rows(store, qs_q[ss], rows))
            nbytes = (n * (dim + 4) + n_union * dim * 4
                      + (n * dim * 4 if n_fb else 0))
            return cids, sims, nbytes

        return scan

    def _top1_multi_pruned(self, arena, queries: np.ndarray
                           ) -> Optional[tuple]:
        """Per-policy pruned pass over the arena's store views: each
        table-backed policy runs the two-stage driver against its own
        :class:`TopicBucketIndex` (host routing matrices go straight to
        the jitted kernel — per-policy device mirrors aren't worth their
        bookkeeping at arena sizes); table-less policies take a per-view
        exact kernel scan (same per-row f32 dots as the stacked launch).
        Unbound-delegation-safe: the sharded backend calls this body too,
        and arena views are dense, so the exact legs go through
        ``KernelBackend._top1_batch_exact`` explicitly.  Returns ``None``
        when ``run_arena`` didn't wire ``route_tables``."""
        from repro.kernels import ops
        tables = getattr(self, "route_tables", None)
        if tables is None:
            return None
        if not arena.track_rows:
            raise ValueError("pruned top1_multi needs an ArenaStore "
                             "built with track_rows=True")
        b = queries.shape[0]
        n_pol = arena.occ.shape[0]
        n_slots = arena.occ.shape[1]
        dim = arena.emb.shape[-1]
        cfg = self.pruned
        fused_on = cfg.fused and cfg.probes >= 1
        if fused_on:
            # one flat (P·S, D) fp32 + int8 mirror pair serves every
            # policy's fused launch; per-policy CSR slot ids are shifted
            # by p·S into the flat slab
            flat_dev = self._arena_mirror.sync(
                arena.version, arena.dirty_since,
                lambda: {"emb": arena.emb.reshape(n_pol * n_slots, dim)})
            qm = self._qhost_arena.sync(
                arena.version, arena.dirty_since,
                arena.emb.reshape(n_pol * n_slots, dim))
            q8d = self._q8_arena_mirror.sync(
                arena.version, arena.dirty_since,
                lambda: {"q8": qm.q8, "scale": qm.scale, "l1": qm.l1})

        def route(qs, aug, n_top):
            bq = qs.shape[0]
            pad = (-bq) % self.q_pad
            qp = np.pad(qs, ((0, pad), (0, 0))) if pad else qs
            with annotate("rac/route_topics"):
                vals, tids = ops.run_timed(
                    lambda: ops.route_topics(
                        qp, aug, cfg.probes, n_valid=n_top,
                        use_pallas=self.use_pallas,
                        interpret=self.interpret))
            return ops.to_host(vals)[:bq], ops.to_host(tids)[:bq]

        out_c = np.full((n_pol, b), -1, dtype=np.int64)
        out_s = np.full((n_pol, b), -np.inf)
        for p in range(n_pol):
            view = arena.views[p]
            if not view.slot_of:
                continue
            table = tables[p] if p < len(tables) else None
            if table is None:
                cids, sims = KernelBackend._top1_batch_exact(self, view,
                                                             queries)
            elif fused_on and table.rep.shape[0] >= 1 and view.hwm > 0:
                idx = self._pidx_arena.setdefault(p, TopicBucketIndex())
                idx.sync(view, table)
                csr_m = self._csr_arena.setdefault(
                    p, _DeviceMirror({"indptr": np.int32,
                                      "slots": np.int32}))
                # the aug matrix rides the launch as a host array (the
                # staged arena route does the same) — per-policy device
                # mirrors aren't worth their bookkeeping at arena sizes
                cids, sims = KernelBackend._fused_pruned_call(
                    self, view, table, queries, cfg, idx,
                    emb_dev=flat_dev["emb"], q8_dev=q8d,
                    aug_dev=np.asarray(idx.aug, dtype=np.float32),
                    csr_mirror=csr_m, slot_off=p * n_slots,
                    n_slots=n_slots, cid_arr=view.cid,
                    exact_fn=lambda sel, v=view:
                        KernelBackend._top1_batch_exact(self, v,
                                                        queries[sel]),
                    stats=self.prune_stats)
            else:
                idx = self._pidx_arena.setdefault(p, TopicBucketIndex())
                cids, sims = pruned_top1_batch(
                    view, table, queries, cfg, idx, self.prune_stats,
                    route_fn=route,
                    scan_fn=lambda sel, rows, v=view: (
                        *self.top1_rows(v, queries[sel], rows),
                        rows.size * dim * 4),
                    exact_fn=lambda sel, v=view:
                        KernelBackend._top1_batch_exact(self, v,
                                                        queries[sel]))
            out_c[p], out_s[p] = cids, sims
        self._flush_sync()
        return out_c, out_s

    def top1_rows(self, store: ResidentStore, queries: np.ndarray,
                  rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        from repro.kernels import ops
        queries = np.asarray(queries, dtype=np.float32)
        rows = np.asarray(rows, dtype=np.int64)
        b, k = queries.shape[0], rows.shape[0]
        pad = (-b) % self.q_pad
        qp = np.pad(queries, ((0, pad), (0, 0))) if pad else queries
        # gather the restricted candidate block; its row count is padded to
        # a bucket so XLA compiles one kernel per bucket, not per count —
        # the runtime n_valid masks the zero tail exactly as in top1_batch
        kp = -(-k // 64) * 64
        cand = np.zeros((kp, store.emb.shape[1]), dtype=np.float32)
        cand[:k] = store.emb[rows]
        vals, idx = ops.sim_top1(qp, cand, n_valid=k,
                                 use_pallas=self.use_pallas,
                                 interpret=self.interpret)
        vals = np.asarray(ops.to_host(vals)[:b], dtype=np.float64)
        idx = ops.to_host(idx)[:b]
        return store.cid[rows[idx]].copy(), vals

    def topk_rows(self, store: ResidentStore, queries: np.ndarray,
                  rows: np.ndarray, k: int
                  ) -> tuple[np.ndarray, np.ndarray]:
        from repro.kernels import ops
        queries = np.asarray(queries, dtype=np.float32)
        rows = np.asarray(rows, dtype=np.int64)
        b, n = queries.shape[0], rows.shape[0]
        out_c = np.full((b, k), -1, dtype=np.int64)
        out_s = np.full((b, k), -np.inf, dtype=np.float64)
        if n == 0:
            return out_c, out_s
        pad = (-b) % self.q_pad
        qp = np.pad(queries, ((0, pad), (0, 0))) if pad else queries
        # same bucketed candidate gather as top1_rows; the kernel's K is
        # capped at the padded block size (ranks past the restriction come
        # back -inf and are mapped to (-1, -inf) below)
        kp = -(-n // 64) * 64
        cand = np.zeros((kp, store.emb.shape[1]), dtype=np.float32)
        cand[:n] = store.emb[rows]
        kk = min(k, kp)
        vals, idx = ops.sim_topk(qp, cand, kk, n_valid=n,
                                 use_pallas=self.use_pallas,
                                 interpret=self.interpret)
        vals = np.asarray(ops.to_host(vals)[:b], dtype=np.float64)  # (B, kk)
        idx = ops.to_host(idx)[:b]
        finite = np.isfinite(vals)
        out_c[:, :kk] = np.where(
            finite, store.cid[rows[np.minimum(idx, n - 1)]], -1)
        out_s[:, :kk] = np.where(finite, vals, -np.inf)
        return out_c, out_s

    def top1_multi(self, arena, queries: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
        """Stacked device pass: ONE ``sim_top1_multi`` dispatch scores the
        query chunk against all P policy slabs, each masked to its own
        runtime high-water mark.  The (P*S, D) flat slab is mirrored
        against the arena's flat journal (dirty-row scatter), so
        steady-state chunks move O(mutations) rows for the whole arena."""
        from repro.kernels import ops                  # deferred: jax import
        if not arena.track_rows:
            # host-only arenas skip journaling entirely; a version-keyed
            # mirror would silently serve stale rows
            raise ValueError("KernelBackend.top1_multi needs an ArenaStore "
                             "built with track_rows=True")
        queries = np.asarray(queries, dtype=np.float32)
        b = queries.shape[0]
        n_pol, n_slots = arena.occ.shape
        if not any(v.slot_of for v in arena.views):
            return (np.full((n_pol, b), -1, dtype=np.int64),
                    np.full((n_pol, b), -np.inf, dtype=np.float64))
        if self.pruned is not None:
            out = self._top1_multi_pruned(arena, queries)
            if out is not None:
                return out
        if self.quantized is not None:
            return self._top1_multi_quantized(arena, queries)
        pad = (-b) % self.q_pad
        qp = np.pad(queries, ((0, pad), (0, 0))) if pad else queries
        dim = arena.emb.shape[-1]
        dev = self._arena_mirror.sync(
            arena.version, arena.dirty_since,
            lambda: {"emb": arena.emb.reshape(n_pol * n_slots, dim)})
        with annotate("rac/sim_top1_multi"):
            vals, idx = ops.run_timed(
                lambda: ops.sim_top1_multi(
                    qp, dev["emb"].reshape(n_pol, n_slots, dim),
                    n_valid=arena.hwms(), use_pallas=self.use_pallas,
                    interpret=self.interpret))
        vals = np.asarray(ops.to_host(vals)[:, :b], dtype=np.float64)
        idx = ops.to_host(idx)[:, :b]
        cids = arena.cid[np.arange(n_pol)[:, None], idx].copy()
        # a free (zeroed) slot can only win when all real sims < 0 → miss
        sims = np.where(cids >= 0, vals, -np.inf)
        self._flush_sync()
        return cids, sims

    def _top1_multi_quantized(self, arena, queries: np.ndarray
                              ) -> tuple[np.ndarray, np.ndarray]:
        """Stacked quantized arena scan: ONE ``sim_topk_q8_multi``
        dispatch streams every policy's int8 slab (the 4× byte saving
        multiplied by P), then each policy's survivors are rescored and
        certified against its own store view — per-row kernel-score
        independence makes each policy's shortlist the one its single-slab
        launch would have produced."""
        from repro.kernels import ops
        from repro.kernels.quant import quantize_rows_int8, scan_margin
        b = queries.shape[0]
        n_pol, n_slots = arena.occ.shape
        dim = arena.emb.shape[-1]
        qm = self._qhost_arena.sync(
            arena.version, arena.dirty_since,
            arena.emb.reshape(n_pol * n_slots, dim))
        dev = self._q8_arena_mirror.sync(
            arena.version, arena.dirty_since,
            lambda: {"q8": qm.q8, "scale": qm.scale, "l1": qm.l1})
        pad = (-b) % self.q_pad
        qp = np.pad(queries, ((0, pad), (0, 0))) if pad else queries
        q8, qs, ql1 = quantize_rows_int8(qp)
        k = self.quantized.k
        hwms = arena.hwms()
        with annotate("rac/sim_topk_q8_multi"):
            vals, idx = ops.run_timed(
                lambda: ops.sim_topk_q8_multi(
                    q8, qs, dev["q8"].reshape(n_pol, n_slots, dim),
                    dev["scale"].reshape(n_pol, n_slots), k, n_valid=hwms,
                    use_pallas=self.use_pallas, interpret=self.interpret))
        vals = np.asarray(ops.to_host(vals)[:, :b], dtype=np.float64)
        rows = ops.to_host(idx)[:, :b]
        scale2 = qm.scale.reshape(n_pol, n_slots)
        l12 = qm.l1.reshape(n_pol, n_slots)
        out_c = np.full((n_pol, b), -1, dtype=np.int64)
        out_s = np.full((n_pol, b), -np.inf)
        for p in range(n_pol):
            hw = int(hwms[p])
            if hw == 0:
                continue
            eps = scan_margin(qs[:b], ql1[:b], scale2[p], l12[p], dim)
            view = arena.views[p]
            cids, sims, n_fb, n_union = resolve_topk(
                vals[p], rows[p], eps, k >= hw, self.quantized.tau_hit,
                lambda r, v=view: self.top1_rows(v, queries, r),
                # unbound on purpose: the sharded backend delegates its
                # stacked quantized pass here, and arena views are dense —
                # its own _top1_batch_exact expects sharded-store geometry
                lambda sel, v=view: KernelBackend._top1_batch_exact(
                    self, v, queries[sel]))
            account_scan(self.quant_stats, n_valid=hw, dim=dim, batch=b,
                         n_union=n_union, n_fallback=n_fb)
            out_c[p], out_s[p] = cids, sims
        self._flush_sync()
        return out_c, out_s

    def rac_value_masked(self, tsi, tids, tp_last, t_last, alpha, t_now,
                         valid):
        from repro.kernels import ops
        with annotate("rac/rac_value"):
            out = ops.rac_value_masked(
                np.asarray(tsi, dtype=np.float32),
                np.asarray(tids, dtype=np.int32),
                np.asarray(tp_last, dtype=np.float32),
                np.asarray(t_last - t_now, dtype=np.int32),
                np.asarray(valid, dtype=bool), float(alpha), 0,
                use_pallas=self.use_pallas, interpret=self.interpret)
            return np.asarray(ops.to_host(out), dtype=np.float64)

    def rac_value(self, tsi, tids, tp_last, t_last, alpha, t_now):
        from repro.kernels import ops
        # shift timestamps so t_now is the static constant 0: the kernel
        # sees 0 - (t_last - t_now) = t_now - t_last, and jit never
        # recompiles as simulation time advances.
        with annotate("rac/rac_value"):
            out = ops.rac_value(np.asarray(tsi, dtype=np.float32),
                                np.asarray(tids, dtype=np.int32),
                                np.asarray(tp_last, dtype=np.float32),
                                np.asarray(t_last - t_now, dtype=np.int32),
                                float(alpha), 0, use_pallas=self.use_pallas,
                                interpret=self.interpret)
            return np.asarray(ops.to_host(out), dtype=np.float64)

    def _device_state(self, store: ResidentStore, table: PolicyTable) -> dict:
        """The mirrored decision state, freshened by dirty-row scatter."""
        slab = self._store_slab(store)
        slot = self._slot_mirror.sync(
            table.slot_version, table.dirty_slots_since,
            lambda: {"tsi": table.tsi, "tid": table.topic_of})
        topic = self._topic_mirror.sync(
            table.topic_version, table.dirty_topics_since,
            lambda: {"rep": table.rep, "tp": table.tp_last,
                     "tl": table.t_last})
        return {**slab, **slot, **topic}

    def decide_batch(self, store, table, queries, *, alpha=0.0, t_now=0):
        from repro.kernels import ops
        queries = np.asarray(queries, dtype=np.float32)
        b = queries.shape[0]
        if table is None:
            hit_cid, hit_sim = self.top1_batch(store, queries)
            return DecisionBatch(hit_cid, hit_sim,
                                 np.full(b, -1, dtype=np.int64),
                                 np.full(b, -np.inf, dtype=np.float64), None)
        if self.quantized is not None or self.pruned is not None:
            return self._decide_batch_quantized(store, table, queries,
                                                alpha=alpha, t_now=t_now)
        pad = (-b) % self.q_pad
        qp = np.pad(queries, ((0, pad), (0, 0))) if pad else queries
        dev = self._device_state(store, table)
        # ONE fused dispatch: hit Top-1 (runtime n_valid = store hwm) +
        # routing Top-1 (runtime n_topics = topic hwm) + masked Eq.1 victim
        # values with a runtime t_now — nothing recompiles as fill level,
        # topic count, or simulation time advance
        with annotate("rac/fused_decide"):
            out = ops.run_timed(
                lambda: ops.fused_decide(
                    qp, dev["emb"], store.hwm, dev["rep"], table.topic_hwm,
                    dev["tsi"], dev["tid"], dev["occ"], dev["tp"],
                    dev["tl"], t_now, alpha=float(alpha),
                    use_pallas=self.use_pallas, interpret=self.interpret))
        hv, hi, rv, ri, vv = ops.to_host_tuple(out)
        hv = np.asarray(hv[:b], dtype=np.float64)
        cids = store.cid[np.asarray(hi[:b])].copy()
        # a free (zeroed) slot can only win when all real sims < 0 → miss
        sims = np.where(cids >= 0, hv, -np.inf)
        rv = np.asarray(rv[:b], dtype=np.float64)
        ri = np.where(np.isfinite(rv),
                      np.asarray(ri[:b], dtype=np.int64), -1)
        self._flush_sync()
        return DecisionBatch(cids, sims, ri, rv,
                             np.asarray(vv, dtype=np.float64))

    def _decide_batch_quantized(self, store, table, queries, *, alpha,
                                t_now):
        """Fused decision pass with a reduced-traffic hit leg: the hit
        Top-1 rides ``top1_batch`` — the topic-pruned and/or int8 scan,
        whichever is configured (skipping the fp32 slab upload entirely
        when quantized — the int8 mirror replaces it) — while routing and
        victim scoring run the same ``sim_top1``/``victim_value`` kernel
        math as the exact path's fused launch (per-leg score independence
        keeps the decisions identical)."""
        from repro.kernels import ops
        b = queries.shape[0]
        hit_cid, hit_sim = self.top1_batch(store, queries)
        pad = (-b) % self.q_pad
        qp = np.pad(queries, ((0, pad), (0, 0))) if pad else queries
        slot = self._slot_mirror.sync(
            table.slot_version, table.dirty_slots_since,
            lambda: {"tsi": table.tsi, "tid": table.topic_of})
        topic = self._topic_mirror.sync(
            table.topic_version, table.dirty_topics_since,
            lambda: {"rep": table.rep, "tp": table.tp_last,
                     "tl": table.t_last})
        # ONE auxiliary launch (routing Top-1 + victim values together)
        # instead of the former sim_top1 + victim_value pair
        with annotate("rac/decide_aux"):
            out = ops.run_timed(
                lambda: ops.decide_aux(
                    qp, topic["rep"], table.topic_hwm, slot["tsi"],
                    slot["tid"], np.asarray(store.occ, dtype=np.int32),
                    topic["tp"], topic["tl"], t_now, alpha=float(alpha),
                    use_pallas=self.use_pallas, interpret=self.interpret))
        rv, ri, vv = ops.to_host_tuple(out)
        rv = np.asarray(rv[:b], dtype=np.float64)
        ri = np.where(np.isfinite(rv),
                      np.asarray(ri[:b], dtype=np.int64), -1)
        self._flush_sync()
        return DecisionBatch(hit_cid, hit_sim, ri, rv,
                             np.asarray(vv, dtype=np.float64))


def _backends() -> dict:
    # deferred: repro.cache.sharded pulls in jax-facing modules lazily, but
    # keep even its import off the module path of numpy-only consumers
    from .sharded import ShardedKernelBackend
    return {"numpy": NumpyBackend, "kernel": KernelBackend,
            "sharded": ShardedKernelBackend}


def get_backend(name: str, **kwargs) -> LookupBackend:
    """Instantiate a backend by config name
    (``"numpy"`` | ``"kernel"`` | ``"sharded"``).

    ``kwargs`` are forwarded to the backend constructor *uniformly*;
    unexpected ones raise (a ``TypeError`` from the constructor), they are
    never silently dropped.  An already-built backend instance passes
    through unchanged — constructor kwargs cannot apply to it, so passing
    any alongside an instance raises ``ValueError``."""
    if not isinstance(name, str):
        if not isinstance(name, LookupBackend):
            raise ValueError(f"expected a backend name or LookupBackend "
                             f"instance, got {name!r}")
        if kwargs:
            raise ValueError(f"backend instance {name!r} cannot take "
                             f"constructor kwargs {sorted(kwargs)}")
        return name
    registry = _backends()
    try:
        cls = registry[name]
    except KeyError:
        raise ValueError(f"unknown cache backend {name!r}; "
                         f"expected one of {sorted(registry)}") from None
    return cls(**kwargs)
