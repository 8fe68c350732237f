"""Sharded resident store: multi-device semantic-cache lookup.

Scales :class:`repro.cache.SemanticCache` capacity past one chip's HBM by
partitioning the resident slab row-wise across the devices of a 1-D
``("cache",)`` mesh (``repro.launch.mesh.make_cache_mesh``):

  - **Layout** — :class:`ShardedStore` keeps one contiguous ``(S·R, D)``
    slab viewed as ``(S, R, D)``: shard ``s`` owns rows
    ``[s·R, (s+1)·R)``.  Slot placement routes every new entry onto the
    least-loaded shard (ties → lowest shard id), and each shard tracks a
    local high-water mark so device lookups only score its locally-valid
    prefix (runtime ``n_valid``, scalar-prefetched into the kernel).
  - **Lookup** — :class:`ShardedKernelBackend` runs ``kernels/ops.sim_top1``
    per shard under ``shard_map`` (every device scores its own ``(R, D)``
    block against the replicated query batch), ``all_gather``\\ s the
    per-shard ``(val, local_idx)`` pairs and merges them with a single
    argmax-reduce over the shard axis into global ``(cid, sim)``.
  - **Eviction** — ``rac_value`` shards the resident-table entry axis over
    the same mesh (each device scores its chunk with the ``rac_value``
    kernel); ``shard_map`` stitches the chunks back into one value vector
    and the policy's deterministic ``(value, last-access, cid)`` lexsort
    takes the global min.  Doing the min inside the collective would lose
    those tie-breaks, so the merge hands back values, not a victim.
  - **Fallback** — with fewer devices than shards (e.g. a 1-device CPU
    box) the backend loops the identical per-shard kernel + argmax merge
    on one device, so hit/admit/evict decisions never depend on the
    machine: ``tests/test_cache_api.py`` asserts decision parity with the
    numpy backend for shard counts {1, 2, 4}.
  - **Checkpoint/restore** — all sharded state (slab, per-shard free lists,
    loads, high-water marks) lives in the store object; the facade's
    ``checkpoint()`` deep copy captures it with no backend cooperation.
    Device-side slabs are cached keyed by the store's globally-unique
    mutation ``version`` stamp, so a restored snapshot re-attaches to its
    uploaded slab for free and any divergence forces a re-upload.
"""
from __future__ import annotations

import numpy as np

from repro.core.store import ResidentStore
from repro.telemetry.tracing import annotate

from .types import DecisionBatch


class ShardedStore(ResidentStore):
    """Row-partitioned resident slab with least-loaded shard placement.

    ``n_shards`` shards of ``rows_per_shard = ceil((capacity+1)/n_shards)``
    rows each (the +1 is Alg. 1's insert-then-evict spare slot).  The numpy
    arrays are the plain :class:`ResidentStore` layout, so every host-side
    consumer (policies, the numpy backend, metrics) works unchanged — only
    slot *placement* differs.
    """

    def __init__(self, capacity: int, dim: int, n_shards: int = 1):
        n_shards = max(1, int(n_shards))
        rows = -(-(capacity + 1) // n_shards)          # ceil division
        super().__init__(capacity, dim, n_slots=rows * n_shards)
        self.n_shards = n_shards
        self.rows_per_shard = rows
        # per-shard LIFO free lists mirror the parent's slot-reuse order,
        # keeping each shard's occupied slots below its local high-water
        # mark; the parent's single free list is retired so no stale copy
        # rides along in checkpoints
        self._free.clear()
        self._free_by_shard = [list(range((s + 1) * rows - 1, s * rows - 1, -1))
                               for s in range(n_shards)]
        self.load = np.zeros(n_shards, dtype=np.int64)
        self.local_hwm = np.zeros(n_shards, dtype=np.int64)

    def shard_of(self, slot: int) -> int:
        return slot // self.rows_per_shard

    def shard_view(self) -> np.ndarray:
        """The slab as ``(n_shards, rows_per_shard, D)`` (a zero-copy view)."""
        return self.emb.reshape(self.n_shards, self.rows_per_shard, -1)

    def _alloc(self) -> int:
        shard = int(np.argmin(self.load))              # ties → lowest shard
        slot = self._free_by_shard[shard].pop()
        self.load[shard] += 1
        local = slot - shard * self.rows_per_shard
        if local + 1 > self.local_hwm[shard]:
            self.local_hwm[shard] = local + 1
        return slot

    def _release(self, slot: int):
        shard = self.shard_of(slot)
        self._free_by_shard[shard].append(slot)
        self.load[shard] -= 1


class ShardedKernelBackend:
    """Multi-device lookup/scoring over a :class:`ShardedStore`.

    ``n_shards=None`` means one shard per addressable device.  When the
    machine has at least ``n_shards`` devices the lookup runs under
    ``shard_map`` on a ``("cache",)`` mesh; otherwise a per-shard loop on
    one device computes the identical math (see module docstring).
    ``use_pallas=False`` routes through the jnp oracles, as in
    :class:`~repro.cache.backends.KernelBackend`.
    """

    name = "sharded"

    def __init__(self, n_shards: int | None = None, use_pallas: bool = True,
                 interpret: bool | None = None, q_pad: int = 8,
                 quantized=None, pruned=None):
        from .backends import _DeviceMirror
        from .pruned import (TopicBucketIndex, as_pruned_config,
                             new_prune_stats)
        from .quantized import (QuantizedSlabMirror, as_quantized_config,
                                new_quant_stats)
        self._n_shards = n_shards
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.q_pad = max(1, q_pad)
        self.quantized = as_quantized_config(quantized)
        self.quant_stats = new_quant_stats()
        # topic-pruned two-stage scan: the routing + gathered candidate
        # scans delegate to the dense KernelBackend body (small blocks —
        # same rationale as top1_rows below); only the exact-fallback leg
        # fans out across the mesh
        self.pruned = as_pruned_config(pruned)
        self.prune_stats = new_prune_stats()
        self._pidx = TopicBucketIndex()
        self._pidx_arena: dict[int, TopicBucketIndex] = {}
        self.route_table = None
        self.route_store = None
        self._route_mirror = _DeviceMirror({"aug": np.float32})
        self._mesh = None
        self._mesh_built = False
        self._lookup_fn = None
        self._multi_fn = None                      # arena stacked lookup
        self._arena_cache = None       # (version, rearranged slab, shape)
        self._arena_scatter_fn = None
        self._rac_fns: dict[float, object] = {}
        self._decide_fns: dict[float, object] = {}
        self._slab_cache: dict[int, tuple] = {}    # store.version -> (slab, nv)
        self._scatter_fn = None                    # dirty-row device update
        # quantized path: host int8 requantizer + its sharded device slab
        # cache (same version-keyed dirty-row scatter protocol as _slab);
        # the arena variants back the dense stacked delegation (see
        # top1_multi) with KernelBackend-compatible mirror attributes
        self._qhost = QuantizedSlabMirror()
        self._qhost_arena = QuantizedSlabMirror()
        self._q8_arena_mirror = _DeviceMirror({"q8": np.int8,
                                               "scale": np.float32,
                                               "l1": np.float32})
        # fused-pipeline delegation mirrors: the pruned pass hands the
        # whole batch to KernelBackend._fused_pruned_batch (unbound), which
        # expects the dense backend's mirror attributes on ``self`` — the
        # fp32/int8 single-device copies it launches against, the arena's
        # flat stacked slab, and the device CSR form of each bucket index
        self._store_mirror = _DeviceMirror({"emb": np.float32,
                                            "occ": np.int32})
        self._q8_mirror = _DeviceMirror({"q8": np.int8,
                                         "scale": np.float32,
                                         "l1": np.float32})
        self._arena_mirror = _DeviceMirror({"emb": np.float32})
        self._csr_mirror = _DeviceMirror({"indptr": np.int32,
                                          "slots": np.int32})
        self._csr_arena: dict[int, _DeviceMirror] = {}
        self._q8_slab_cache: dict[int, tuple] = {}
        self._q8_scatter_fn = None
        self._qlookup_fns: dict[int, object] = {}   # k -> shard_map lookup
        # observability for the incremental path: full uploads vs dirty-row
        # scatters, how many rows the scatters moved in total, and the
        # host→device bytes those transfers shipped
        self._sync = {"full": 0, "incremental": 0, "rows": 0, "bytes": 0}
        self._tracker = None                # telemetry sink (observation-only)
        self._sync_seen: dict[str, int] = {}   # last sync_stats flushed to it

    @property
    def sync_stats(self) -> dict:
        """Aggregate sync observability: the sharded slab caches' own
        ledger plus every dense-delegation device mirror (the arena int8
        mirror, the routing matrix, and the fused pipeline's fp32/int8/CSR
        copies) — their uploads land here alongside the fp32 slab
        traffic."""
        mirrors = (self._q8_arena_mirror, self._route_mirror,
                   self._store_mirror, self._q8_mirror, self._arena_mirror,
                   self._csr_mirror, *self._csr_arena.values())
        return {k: self._sync[k] + sum(m.stats[k] for m in mirrors)
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
        for k, v in self.sync_stats.items():
            d = v - self._sync_seen.get(k, 0)
            if d:
                trk.count(f"sync.{k}", d)
        self._sync_seen = dict(self.sync_stats)

    # ------------------------------------------------------------- topology
    @property
    def n_shards(self) -> int:
        if self._n_shards is None:
            import jax
            self._n_shards = max(1, len(jax.devices()))
        return self._n_shards

    def make_store(self, capacity: int, dim: int) -> ShardedStore:
        """Facade hook: the sharded backend owns its store geometry."""
        return ShardedStore(capacity, dim, n_shards=self.n_shards)

    def mesh(self):
        """The 1-D cache mesh, or None on machines with too few devices."""
        if not self._mesh_built:
            from repro.launch.mesh import make_cache_mesh
            self._mesh = make_cache_mesh(self.n_shards)
            self._mesh_built = True
        return self._mesh

    # ---------------------------------------------------------- device slab
    def _build_scatter(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = NamedSharding(self._mesh, P("cache"))

        def scatter(slab, shards, locals_, vals):
            return slab.at[shards, locals_].set(vals)

        return jax.jit(scatter, out_shardings=spec)

    def _slab(self, store: ShardedStore):
        """(S, R, D) slab + per-shard valid counts, cached by store version.

        The version stamp is globally unique per mutation, so a checkpoint
        restored from this store lineage re-attaches to its uploaded slab;
        any divergent mutation forces a fresh upload.  (Host fallback keeps
        a zero-copy numpy view, so the cache is free there.)

        On a version miss the backend first asks the store which rows
        changed since a cached snapshot (:meth:`ResidentStore.dirty_since`)
        and, when the answer is small, scatters only those rows into the
        device slab instead of re-uploading the whole thing — admission-
        heavy replay moves O(mutations) rows per sync, not O(capacity).
        """
        if self.mesh() is None:
            # host fallback: the live zero-copy view is always current —
            # caching it would alias rows the store later overwrites
            return store.shard_view(), store.local_hwm.astype(np.int32)
        hit = self._slab_cache.get(store.version)
        if hit is not None:
            return hit
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = NamedSharding(self._mesh, P("cache"))
        nv = jax.device_put(store.local_hwm.astype(np.int32), spec)
        slab = self._incremental_slab(store, spec)
        if slab is None:
            self._sync["full"] += 1
            self._sync["bytes"] += store.emb.nbytes
            slab = jax.device_put(np.ascontiguousarray(store.shard_view()),
                                  spec)
        if len(self._slab_cache) >= 4:              # keep a few snapshots
            self._slab_cache.pop(next(iter(self._slab_cache)))
        self._slab_cache[store.version] = (slab, nv)
        return slab, nv

    def _incremental_slab(self, store: ShardedStore, spec):
        """Dirty-row DMA: patch the freshest reusable cached slab, or None
        when no cached version of this lineage can answer (→ full upload)."""
        best = None
        for version, (slab, _) in self._slab_cache.items():
            dirty = store.dirty_since(version)
            if dirty is not None and (best is None or len(dirty) < len(best[0])):
                best = (dirty, slab)
        if best is None:
            return None
        dirty, slab = best
        from .backends import bucket_rows, small_delta
        if not small_delta(len(dirty), store.emb.shape[0]):
            return None                  # not worth a scatter: bulk upload
        if not dirty:
            return slab
        slots = bucket_rows(np.fromiter(sorted(dirty), dtype=np.int64,
                                        count=len(dirty)))
        if self._scatter_fn is None:
            self._scatter_fn = self._build_scatter()
        self._sync["incremental"] += 1
        self._sync["rows"] += len(dirty)
        self._sync["bytes"] += (slots.size * store.emb.shape[1]
                                     * store.emb.itemsize)
        return self._scatter_fn(slab,
                                (slots // store.rows_per_shard).astype(np.int32),
                                (slots % store.rows_per_shard).astype(np.int32),
                                store.emb[slots])

    # ------------------------------------------------- quantized device slab
    def _build_q8_scatter(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = NamedSharding(self._mesh, P("cache"))

        def scatter(q8slab, csslab, shards, locals_, qv, sv):
            return (q8slab.at[shards, locals_].set(qv),
                    csslab.at[shards, locals_].set(sv))

        return jax.jit(scatter, out_shardings=(spec, spec))

    def _q8_slab(self, store: ShardedStore, qm):
        """(S, R, D) int8 slab + (S, R) per-row scales for the quantized
        scan, cached by store version exactly like :meth:`_slab` (dirty-row
        scatter on a version miss, full upload otherwise).  ``qm`` is the
        freshly synced host mirror; the host fallback scans its zero-copy
        reshape directly, so the cache is free there."""
        s, r = store.n_shards, store.rows_per_shard
        if self.mesh() is None:
            return qm.q8.reshape(s, r, -1), qm.scale.reshape(s, r)
        hit = self._q8_slab_cache.get(store.version)
        if hit is not None:
            return hit
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = NamedSharding(self._mesh, P("cache"))
        slabs = self._incremental_q8_slab(store, qm)
        if slabs is None:
            self._sync["full"] += 1
            self._sync["bytes"] += qm.q8.nbytes + qm.scale.nbytes
            slabs = (jax.device_put(
                         np.ascontiguousarray(qm.q8.reshape(s, r, -1)), spec),
                     jax.device_put(
                         np.ascontiguousarray(qm.scale.reshape(s, r)), spec))
        if len(self._q8_slab_cache) >= 4:           # keep a few snapshots
            self._q8_slab_cache.pop(next(iter(self._q8_slab_cache)))
        self._q8_slab_cache[store.version] = slabs
        return slabs

    def _incremental_q8_slab(self, store: ShardedStore, qm):
        """Dirty-row DMA for the int8 slab pair: one int8 row + one fp32
        scale per dirty slot, or None when no cached version can answer."""
        best = None
        for version, slabs in self._q8_slab_cache.items():
            dirty = store.dirty_since(version)
            if dirty is not None and (best is None
                                      or len(dirty) < len(best[0])):
                best = (dirty, slabs)
        if best is None:
            return None
        dirty, (q8slab, csslab) = best
        from .backends import bucket_rows, small_delta
        if not small_delta(len(dirty), store.emb.shape[0]):
            return None                  # not worth a scatter: bulk upload
        if not dirty:
            return q8slab, csslab
        slots = bucket_rows(np.fromiter(sorted(dirty), dtype=np.int64,
                                        count=len(dirty)))
        if self._q8_scatter_fn is None:
            self._q8_scatter_fn = self._build_q8_scatter()
        self._sync["incremental"] += 1
        self._sync["rows"] += len(dirty)
        self._sync["bytes"] += slots.size * (store.emb.shape[1] + 4)
        return self._q8_scatter_fn(
            q8slab, csslab,
            (slots // store.rows_per_shard).astype(np.int32),
            (slots % store.rows_per_shard).astype(np.int32),
            qm.q8[slots], qm.scale[slots])

    # -------------------------------------------------------------- lookup
    def _build_lookup(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro.kernels.ops import sim_top1_raw
        use_pallas, interpret = self.use_pallas, self.interpret

        def local_top1(q, slab, nv):
            # q (B, D) replicated; slab (1, R, D) / nv (1,) = this shard
            vals, idx = sim_top1_raw(q, slab[0], nv[0],
                                     use_pallas=use_pallas,
                                     interpret=interpret)
            gv = jax.lax.all_gather(vals, "cache")             # (S, B)
            gi = jax.lax.all_gather(idx, "cache")              # (S, B)
            win = jnp.argmax(gv, axis=0)       # ONE argmax-reduce over shards
            b = jnp.arange(gv.shape[1])
            return gv[win, b], win.astype(jnp.int32), gi[win, b]

        return jax.jit(jax.shard_map(
            local_top1, mesh=self._mesh,
            in_specs=(P(), P("cache"), P("cache")),
            out_specs=(P(), P(), P()), check_vma=False))

    def top1(self, store: ShardedStore, query: np.ndarray) -> tuple[int, float]:
        cids, sims = self.top1_batch(store, np.asarray(query)[None, :])
        return int(cids[0]), float(sims[0])

    def top1_batch(self, store: ShardedStore,
                   queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        queries = np.asarray(queries, dtype=np.float32)
        if self.pruned is not None and store.slot_of:
            out = self._top1_batch_pruned(store, queries)
            if out is not None:
                return out
        if self.quantized is not None and store.slot_of:
            return self._top1_batch_quantized(store, queries)
        return self._top1_batch_exact(store, queries)

    def _top1_batch_pruned(self, store: ShardedStore, queries: np.ndarray):
        # routing scores a (T, D+1) matrix and stage 2 scans small
        # gathered candidate blocks — dense single-device work, so the
        # whole two-stage driver delegates to the KernelBackend body
        # (same rationale as top1_rows); the exact-fallback leg it closes
        # over is *this* backend's _top1_batch_exact, i.e. the per-shard
        # scan with the all_gather argmax merge
        from .backends import KernelBackend
        return KernelBackend._top1_batch_pruned(self, store, queries)

    def _top1_batch_exact(self, store: ShardedStore,
                          queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        queries = np.asarray(queries, dtype=np.float32)
        b = queries.shape[0]
        if not store.slot_of:
            return (np.full(b, -1, dtype=np.int64),
                    np.full(b, -np.inf, dtype=np.float64))
        pad = (-b) % self.q_pad
        qp = np.pad(queries, ((0, pad), (0, 0))) if pad else queries
        slab, nv = self._slab(store)
        rows = store.rows_per_shard
        if self.mesh() is not None:
            if self._lookup_fn is None:
                self._lookup_fn = self._build_lookup()
            with annotate("rac/sharded_top1"):
                vals, shard, local = self._lookup_fn(qp, slab, nv)
            vals = np.asarray(vals[:b], dtype=np.float64)
            gslot = (np.asarray(shard[:b], dtype=np.int64) * rows
                     + np.asarray(local[:b], dtype=np.int64))
        else:
            # single-device fallback: same per-shard kernel, same merge
            from repro.kernels import ops
            per_v, per_i = [], []
            for s in range(store.n_shards):
                v, i = ops.sim_top1(qp, slab[s], n_valid=int(nv[s]),
                                    use_pallas=self.use_pallas,
                                    interpret=self.interpret)
                per_v.append(np.asarray(v))
                per_i.append(np.asarray(i))
            gv = np.stack(per_v)                               # (S, B)
            gi = np.stack(per_i)
            win = np.argmax(gv, axis=0)
            cols = np.arange(qp.shape[0])
            vals = gv[win, cols][:b].astype(np.float64)
            gslot = (win * rows + gi[win, cols])[:b].astype(np.int64)
        cids = store.cid[gslot].copy()
        # a free (zeroed) slot can only win when all real sims < 0 → miss
        sims = np.where(cids >= 0, vals, -np.inf)
        return cids, sims

    def _build_qlookup(self, ks: int, km: int):
        """Quantized shard_map lookup: per-shard int8 Top-``ks`` merged
        into a global Top-``km``.  The width split keeps the error-bound
        argument sound: either ``ks`` equals the shard row count (no shard
        can hide a row) or ``km == ks`` (any hidden row sits below its
        shard's ``ks`` survivors, hence below the merged ``km``-th)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro.kernels.ops import sim_topk_q8_raw
        use_pallas, interpret = self.use_pallas, self.interpret

        def local_qtopk(q8, qs, slab, cs, nv):
            # q8/qs replicated; slab (1, R, D) / cs (1, R) / nv (1,) = shard
            vals, idx = sim_topk_q8_raw(q8, qs, slab[0], cs[0], nv[0], ks,
                                        use_pallas=use_pallas,
                                        interpret=interpret)
            gv = jax.lax.all_gather(vals, "cache")             # (S, B, ks)
            gi = jax.lax.all_gather(idx, "cache")              # (S, B, ks)
            s, b = gv.shape[0], gv.shape[1]
            offs = (jnp.arange(s, dtype=jnp.int32)
                    * slab.shape[1])[:, None, None]
            # shard-major concat: equal-value ties pick the earlier entry,
            # i.e. the globally lower slot — the same tie contract as the
            # host fallback's stable descending sort
            allv = jnp.moveaxis(gv, 0, 1).reshape(b, s * ks)
            alli = jnp.moveaxis(gi + offs, 0, 1).reshape(b, s * ks)
            mv, pos = jax.lax.top_k(allv, km)
            return mv, jnp.take_along_axis(alli, pos, axis=1)

        return jax.jit(jax.shard_map(
            local_qtopk, mesh=self._mesh,
            in_specs=(P(), P(), P("cache"), P("cache"), P("cache")),
            out_specs=(P(), P()), check_vma=False))

    def _top1_batch_quantized(self, store: ShardedStore, queries: np.ndarray
                              ) -> tuple[np.ndarray, np.ndarray]:
        """Quantized candidate scan over the sharded int8 slab.

        Every shard streams its (R, D) int8 block (4× fewer slab bytes)
        through ``sim_topk_q8_raw`` and contributes k survivors; the
        all-gathered (S·K) candidates merge into a global Top-K by one
        ``top_k`` — the quantized analogue of the exact path's
        argmax-reduce.  The merged union is rescored in fp32 by
        :meth:`top1_rows` and certified by the shared safety predicate
        (per-shard exact scan fallback), so hit/miss decisions match
        :meth:`_top1_batch_exact` by construction.  Any row outside the
        merged Top-K has approximate score ≤ the merged kth value (its
        own shard kept k candidates at or above it), so the single-slab
        error bound applies unchanged."""
        from repro.kernels import ops
        from repro.kernels.quant import quantize_rows_int8, scan_margin

        from .quantized import account_scan, resolve_topk
        b = queries.shape[0]
        dim = store.emb.shape[1]
        qm = self._qhost.sync(store.version, store.dirty_since, store.emb)
        q8slab, csslab = self._q8_slab(store, qm)
        pad = (-b) % self.q_pad
        qp = np.pad(queries, ((0, pad), (0, 0))) if pad else queries
        q8, qs, ql1 = quantize_rows_int8(qp)
        k = self.quantized.k
        rows_per = store.rows_per_shard
        # per-shard shortlist width cannot exceed the shard row count; the
        # merged width then cannot exceed the concat width (see
        # _build_qlookup for why this split keeps the bound sound)
        ks = min(k, rows_per)
        km = min(k, store.n_shards * ks)
        hwm_total = int(store.local_hwm.sum())
        if self.mesh() is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            spec = NamedSharding(self._mesh, P("cache"))
            nv = jax.device_put(store.local_hwm.astype(np.int32), spec)
            fn = self._qlookup_fns.get((ks, km))
            if fn is None:
                fn = self._qlookup_fns[(ks, km)] = self._build_qlookup(ks, km)
            with annotate("rac/sharded_topk_q8"):
                mv, mi = fn(q8, qs, q8slab, csslab, nv)
            vals = np.asarray(mv[:b], dtype=np.float64)
            rows = np.asarray(mi[:b], dtype=np.int64)
        else:
            # single-device fallback: same per-shard quantized kernel, and
            # the stable descending sort implements the same lower-slot
            # tie merge as the mesh path's shard-major top_k
            per_v, per_i = [], []
            with annotate("rac/sharded_topk_q8"):
                for si in range(store.n_shards):
                    v, i = ops.sim_topk_q8(
                        q8, qs, q8slab[si], csslab[si], ks,
                        n_valid=int(store.local_hwm[si]),
                        use_pallas=self.use_pallas,
                        interpret=self.interpret)
                    per_v.append(np.asarray(v))
                    per_i.append(np.asarray(i, dtype=np.int64)
                                 + si * rows_per)
            allv = np.concatenate(per_v, axis=1)               # (Bp, S·K)
            alli = np.concatenate(per_i, axis=1)
            order = np.argsort(-allv, axis=1, kind="stable")[:, :km]
            vals = np.take_along_axis(allv, order,
                                      axis=1)[:b].astype(np.float64)
            rows = np.take_along_axis(alli, order, axis=1)[:b]
        eps = scan_margin(qs[:b], ql1[:b], qm.scale, qm.l1, dim)
        cids, sims, n_fb, n_union = resolve_topk(
            vals, rows, eps, k >= hwm_total, self.quantized.tau_hit,
            lambda r: self.top1_rows(store, queries, r),
            lambda sel: self._top1_batch_exact(store, queries[sel]))
        account_scan(self.quant_stats, n_valid=hwm_total, dim=dim, batch=b,
                     n_union=n_union, n_fallback=n_fb)
        self._flush_sync()
        return cids, sims

    # ------------------------------------------------- multi-policy arena
    def _build_arena_scatter(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = NamedSharding(self._mesh, P("cache"))

        def scatter(slab, sh, ps, loc, vals):
            return slab.at[sh, ps, loc].set(vals)

        return jax.jit(scatter, out_shardings=spec)

    def _arena_slab(self, arena, rows: int):
        """(n_shards, P, R, D) rearranged stacked slab, version-keyed
        against the arena's flat journal: the mesh path keeps a device
        copy freshened by dirty-row scatter, the host fallback a
        rearranged host copy patched in place — steady-state chunks move
        O(mutations) rows, exactly like the single-policy ``_slab``."""
        import numpy as _np

        from .backends import bucket_rows, small_delta
        n_pol, n_slots = arena.occ.shape
        dim = arena.emb.shape[-1]
        shape_key = (n_pol, rows, dim)
        cached = self._arena_cache
        if cached is not None and cached[2] == shape_key:
            if cached[0] == arena.version:
                return cached[1]
            dirty = arena.dirty_since(cached[0])
            if dirty is not None and small_delta(len(dirty),
                                                 n_pol * n_slots):
                slab = cached[1]
                if dirty:
                    flat = _np.fromiter(sorted(dirty), dtype=_np.int64,
                                        count=len(dirty))
                    self._sync["incremental"] += 1
                    self._sync["rows"] += len(dirty)
                    self._sync["bytes"] += (len(dirty) * dim
                                                 * arena.emb.itemsize)
                    if self.mesh() is not None:
                        flat = bucket_rows(flat)
                        ps = flat // n_slots
                        slot = flat % n_slots
                        if self._arena_scatter_fn is None:
                            self._arena_scatter_fn = \
                                self._build_arena_scatter()
                        slab = self._arena_scatter_fn(
                            slab, (slot // rows).astype(_np.int32),
                            ps.astype(_np.int32),
                            (slot % rows).astype(_np.int32),
                            arena.emb[ps, slot])
                    else:
                        ps = flat // n_slots
                        slot = flat % n_slots
                        slab[slot // rows, ps, slot % rows] = \
                            arena.emb[ps, slot]
                self._arena_cache = (arena.version, slab, shape_key)
                return slab
        # full (re)build: pad the slot axis and rearrange shard-major
        s = self.n_shards
        tail = rows * s - n_slots
        emb = arena.emb
        if tail:
            emb = _np.concatenate(
                [emb, _np.zeros((n_pol, tail, dim), _np.float32)], axis=1)
        slab = _np.ascontiguousarray(
            emb.reshape(n_pol, s, rows, dim).transpose(1, 0, 2, 3))
        self._sync["full"] += 1
        self._sync["bytes"] += slab.nbytes
        if self.mesh() is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            slab = jax.device_put(slab, NamedSharding(self._mesh,
                                                      P("cache")))
        self._arena_cache = (arena.version, slab, shape_key)
        return slab

    def _build_multi_lookup(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro.kernels.ops import sim_top1_multi_raw
        use_pallas, interpret = self.use_pallas, self.interpret

        def local_multi(q, slab, nv):
            # q (B, D) replicated; slab (1, P, R, D) / nv (1, P) = this
            # shard's slice of every policy's slab
            vals, idx = sim_top1_multi_raw(q, slab[0], nv[0],
                                           use_pallas=use_pallas,
                                           interpret=interpret)
            gv = jax.lax.all_gather(vals, "cache")         # (S, P, B)
            gi = jax.lax.all_gather(idx, "cache")          # (S, P, B)
            win = jnp.argmax(gv, axis=0)   # ONE argmax-reduce over shards
            p = jnp.arange(gv.shape[1])[:, None]
            b = jnp.arange(gv.shape[2])[None, :]
            return gv[win, p, b], win.astype(jnp.int32), gi[win, p, b]

        return jax.jit(jax.shard_map(
            local_multi, mesh=self._mesh,
            in_specs=(P(), P("cache"), P("cache")),
            out_specs=(P(), P(), P()), check_vma=False))

    def top1_multi(self, arena, queries: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
        """Policy-stacked Top-1 with the shard_map merge.

        The arena's dense (P, S, D) slab is row-partitioned over the cache
        mesh along the SLOT axis — each device holds every policy's slice
        of R rows as (P, R, D) — and runs the stacked per-shard kernel
        (``sim_top1_multi_raw``); the per-(policy, query) candidates are
        all-gathered and merged by the same single argmax-reduce as
        ``top1_batch``.  Per-shard valid counts derive from each policy's
        dense high-water mark (LIFO slot reuse keeps occupied slots below
        it), so free tails are never scored.  With too few devices the
        identical per-shard math runs as a host loop."""
        import numpy as _np
        if not arena.track_rows:
            # the version-keyed slab cache syncs against the arena's flat
            # journal; a host-only arena never stamps it
            raise ValueError("ShardedKernelBackend.top1_multi needs an "
                             "ArenaStore built with track_rows=True")
        queries = _np.asarray(queries, dtype=_np.float32)
        b = queries.shape[0]
        n_pol, n_slots = arena.occ.shape
        if not any(v.slot_of for v in arena.views):
            return (_np.full((n_pol, b), -1, dtype=_np.int64),
                    _np.full((n_pol, b), -_np.inf, dtype=_np.float64))
        if self.pruned is not None:
            # the per-policy pruned pass is dense (arena slabs are small
            # next to the resident slab): delegate to the KernelBackend
            # body — same precedent as top1_rows
            from .backends import KernelBackend
            out = KernelBackend._top1_multi_pruned(self, arena, queries)
            if out is not None:
                return out
        if self.quantized is not None:
            # the stacked quantized pass is dense (arena slabs are small
            # next to the resident slab): delegate to the KernelBackend
            # body, which only needs the q_pad/mirror attributes this
            # backend also carries — same precedent as top1_rows
            from .backends import KernelBackend
            return KernelBackend._top1_multi_quantized(self, arena, queries)
        pad = (-b) % self.q_pad
        qp = _np.pad(queries, ((0, pad), (0, 0))) if pad else queries
        s = self.n_shards
        rows = -(-n_slots // s)                        # ceil division
        # per-(shard, policy) valid prefix of the dense hwm
        hwms = arena.hwms()[None, :]                   # (1, P)
        offs = (_np.arange(s) * rows)[:, None]         # (S, 1)
        lnv = _np.clip(hwms - offs, 0, rows).astype(_np.int32)   # (S, P)
        shard_slab = self._arena_slab(arena, rows)
        if self.mesh() is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            spec = NamedSharding(self._mesh, P("cache"))
            dnv = jax.device_put(lnv, spec)
            if self._multi_fn is None:
                self._multi_fn = self._build_multi_lookup()
            with annotate("rac/sharded_top1_multi"):
                vals, win, local = self._multi_fn(qp, shard_slab, dnv)
            vals = _np.asarray(vals[:, :b], dtype=_np.float64)
            gslot = (_np.asarray(win[:, :b], dtype=_np.int64) * rows
                     + _np.asarray(local[:, :b], dtype=_np.int64))
        else:
            # single-device fallback: same per-shard stacked kernel + the
            # same argmax merge, looped on one device
            from repro.kernels import ops
            per_v, per_i = [], []
            for si in range(s):
                v, i = ops.sim_top1_multi(qp, shard_slab[si],
                                          n_valid=lnv[si],
                                          use_pallas=self.use_pallas,
                                          interpret=self.interpret)
                per_v.append(_np.asarray(v))
                per_i.append(_np.asarray(i))
            gv = _np.stack(per_v)                      # (S, P, B)
            gi = _np.stack(per_i)
            win = _np.argmax(gv, axis=0)               # (P, Bp)
            pi = _np.arange(n_pol)[:, None]
            bi = _np.arange(qp.shape[0])[None, :]
            vals = gv[win, pi, bi][:, :b].astype(_np.float64)
            gslot = (win * rows + gi[win, pi, bi])[:, :b].astype(_np.int64)
        # padded tail rows are zeros: they can only win when every real
        # sim < 0, which maps to a miss exactly like a free slot
        safe = _np.minimum(gslot, n_slots - 1)
        cids = _np.where(gslot < n_slots,
                         arena.cid[_np.arange(n_pol)[:, None], safe], -1)
        sims = _np.where(cids >= 0, vals, -_np.inf)
        self._flush_sync()
        return cids, sims

    def top1_rows(self, store: ShardedStore, queries: np.ndarray,
                  rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        # a row-restricted rescan touches a handful of rows — one gathered
        # single-device kernel call (KernelBackend's path, which only needs
        # q_pad/use_pallas/interpret) beats fanning a tiny candidate block
        # across the mesh
        from .backends import KernelBackend
        return KernelBackend.top1_rows(self, store, queries, rows)

    def topk_rows(self, store: ShardedStore, queries: np.ndarray,
                  rows: np.ndarray, k: int
                  ) -> tuple[np.ndarray, np.ndarray]:
        # same rationale as top1_rows: a restricted Top-K touches a small
        # gathered candidate block, so the single-device kernel path wins
        from .backends import KernelBackend
        return KernelBackend.topk_rows(self, store, queries, rows, k)

    # ------------------------------------------------------------- eviction
    def _build_rac(self, alpha: float):
        import jax
        from jax.sharding import PartitionSpec as P

        from repro.kernels.ops import rac_value_raw
        use_pallas, interpret = self.use_pallas, self.interpret

        def local_rac(tsi, tid, tp_last, t_last):
            # tsi/tid (chunk,) = this shard's slice of the resident table
            return rac_value_raw(tsi, tid, tp_last, t_last, alpha, 0,
                                 use_pallas=use_pallas, interpret=interpret)

        return jax.jit(jax.shard_map(
            local_rac, mesh=self._mesh,
            in_specs=(P("cache"), P("cache"), P(), P()),
            out_specs=P("cache"), check_vma=False))

    def rac_value(self, tsi, tids, tp_last, t_last, alpha, t_now):
        """Per-shard Eq. 1 scoring over the resident-table entry axis.

        Each shard scores its chunk; the stitched value vector goes back to
        the policy whose lexsort performs the global min-merge (keeping the
        deterministic (value, last-access, cid) tie-breaks)."""
        from repro.kernels import ops
        tsi = np.asarray(tsi, dtype=np.float32)
        tids = np.asarray(tids, dtype=np.int32)
        tp_last = np.asarray(tp_last, dtype=np.float32)
        # shift timestamps so t_now is the static constant 0 (no recompiles
        # as simulation time advances; same trick as KernelBackend)
        t_rel = np.asarray(t_last - t_now, dtype=np.int32)
        n, s = tsi.shape[0], self.n_shards
        if self.mesh() is None or n < s:
            out = ops.rac_value(tsi, tids, tp_last, t_rel, float(alpha), 0,
                                use_pallas=self.use_pallas,
                                interpret=self.interpret)
            return np.asarray(out, dtype=np.float64)
        fn = self._rac_fns.get(float(alpha))
        if fn is None:
            fn = self._rac_fns[float(alpha)] = self._build_rac(float(alpha))
        chunk = -(-n // s)
        pad = chunk * s - n
        out = fn(np.pad(tsi, (0, pad)), np.pad(tids, (0, pad)),
                 tp_last, t_rel)
        return np.asarray(out[:n], dtype=np.float64)

    def rac_value_masked(self, tsi, tids, tp_last, t_last, alpha, t_now,
                         valid):
        vals = self.rac_value(tsi, tids, tp_last, t_last, alpha, t_now)
        return np.where(np.asarray(valid, dtype=bool), vals, np.inf)

    # ------------------------------------------------------ fused decisions
    def _build_decide(self, alpha: float):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro.kernels.ops import fused_decide_raw
        use_pallas, interpret = self.use_pallas, self.interpret

        def local_decide(q, slab, nv, reps, ntop, tsi, tid, occ, tp, tl, tn):
            # q/reps/topic tables replicated; slab (1, R, D), nv (1,), and
            # the flat slot arrays' (R,) slices belong to this shard
            hv, hi, rv, ri, vv = fused_decide_raw(
                q, slab[0], nv[0], reps, ntop[0], tsi, tid, occ, tp, tl,
                tn[0], alpha=alpha, use_pallas=use_pallas,
                interpret=interpret)
            gv = jax.lax.all_gather(hv, "cache")               # (S, B)
            gi = jax.lax.all_gather(hi, "cache")               # (S, B)
            win = jnp.argmax(gv, axis=0)   # ONE argmax-reduce over shards —
            b = jnp.arange(gv.shape[1])    # the same merge as top1_batch
            return (gv[win, b], win.astype(jnp.int32), gi[win, b],
                    rv, ri, vv)

        return jax.jit(jax.shard_map(
            local_decide, mesh=self._mesh,
            in_specs=(P(), P("cache"), P("cache"), P(), P(), P("cache"),
                      P("cache"), P("cache"), P(), P(), P()),
            out_specs=(P(), P(), P(), P(), P(), P("cache")),
            check_vma=False))

    def decide_batch(self, store: ShardedStore, table, queries, *,
                     alpha=0.0, t_now=0):
        """Fused per-shard decision pass with the PR 2 Top-1 merge.

        Every shard runs the identical fused body (hit Top-1 over its slab
        rows + replicated routing Top-1 + masked Eq. 1 over its slice of
        the slot table) in ONE ``shard_map`` launch; the per-shard hit
        candidates are all-gathered and merged by a single argmax-reduce —
        exactly how ``top1_batch`` merges — and the per-shard victim
        slices are stitched back into one slot-indexed value vector.  The
        big embedding slab rides the version-keyed device cache
        (dirty-row scatter); the small slot/topic arrays are shipped per
        call.  With too few devices the identical math runs as the
        single-device loop, so decisions stay topology-independent.
        """
        queries = np.asarray(queries, dtype=np.float32)
        b = queries.shape[0]
        if table is None:
            hit_cid, hit_sim = self.top1_batch(store, queries)
            return DecisionBatch(hit_cid, hit_sim,
                                 np.full(b, -1, dtype=np.int64),
                                 np.full(b, -np.inf, dtype=np.float64), None)
        from repro.kernels import ops
        pad = (-b) % self.q_pad
        qp = np.pad(queries, ((0, pad), (0, 0))) if pad else queries
        tsi = table.tsi.astype(np.float32)
        tid = table.topic_of.astype(np.int32)
        occ = store.occ.astype(np.int32)
        tp = table.tp_last.astype(np.float32)
        tl = table.t_last.astype(np.int32)
        rows = store.rows_per_shard
        # quantized/pruned lookups take the split path below: its
        # top1_batch call dispatches to the reduced-traffic scan while
        # routing + victim stay fused
        if (self.mesh() is not None and self.quantized is None
                and self.pruned is None):
            slab, nv = self._slab(store)
            fn = self._decide_fns.get(float(alpha))
            if fn is None:
                fn = self._decide_fns[float(alpha)] = \
                    self._build_decide(float(alpha))
            with annotate("rac/sharded_fused_decide"):
                hv, shard, local, rv, ri, vv = fn(
                    qp, slab, nv, table.rep, np.asarray([table.topic_hwm],
                                                        dtype=np.int32),
                    tsi, tid, occ, tp, tl,
                    np.asarray([t_now], dtype=np.int32))
            hv = np.asarray(hv[:b], dtype=np.float64)
            gslot = (np.asarray(shard[:b], dtype=np.int64) * rows
                     + np.asarray(local[:b], dtype=np.int64))
            rv = np.asarray(rv[:b], dtype=np.float64)
            ri = np.asarray(ri[:b], dtype=np.int64)
            vv = np.asarray(vv, dtype=np.float64)
        else:
            # single-device fallback: the hit merge is top1_batch's loop
            # (identical decisions), routing + victim are one call each
            hit_cid, hit_sim = self.top1_batch(store, queries)
            rv_, ri_ = ops.sim_top1(qp, table.rep, n_valid=table.topic_hwm,
                                    use_pallas=self.use_pallas,
                                    interpret=self.interpret)
            vv = np.asarray(ops.victim_value(
                tsi, tid, occ, tp, tl, t_now, alpha=float(alpha),
                use_pallas=self.use_pallas, interpret=self.interpret),
                dtype=np.float64)
            rv = np.asarray(rv_[:b], dtype=np.float64)
            ri = np.where(np.isfinite(rv),
                          np.asarray(ri_[:b], dtype=np.int64), -1)
            self._flush_sync()
            return DecisionBatch(hit_cid, hit_sim, ri, rv, vv)
        cids = store.cid[gslot].copy()
        # a free (zeroed) slot can only win when all real sims < 0 → miss
        sims = np.where(cids >= 0, hv, -np.inf)
        ri = np.where(np.isfinite(rv), ri, -1)
        self._flush_sync()
        return DecisionBatch(cids, sims, ri, rv, vv)
