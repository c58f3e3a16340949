"""Per-plan-group device arenas: many tenants' parameters, one dispatch.

A :class:`PlanGroupArena` holds every grouped tenant of one
:class:`~repro.serve_filter.plan.GroupKey` in STACKED device arrays:

* embedding tables in ONE combined row-padded matrix
  (``(capacity * sum(rows_c), e_max)``, column blocks back to back and
  narrow tables zero-padded to ``e_max`` columns) so the compiled
  program does a single gather across all subcolumns — XLA's CPU
  gather pays per-op, and one big gather is ~2x the speed of one per
  subcolumn while returning bit-identical rows,
* dense MLP weights/biases stacked on a leading tenant axis,
* fixup bitsets CONCATENATED into one packed ``uint32`` arena, each
  tenant owning the word range ``[word_base, word_base + n_words)``
  (tenants' ``m_bits`` differ — bitset size tracks each tenant's
  false-negative count — so slots are ranges, not a matrix),
* per-tenant ``tau`` / ``m_bits`` / ``word_base`` vectors indexed by
  the slot id.

The grouped executor's compiled program takes a per-row ``tenant_idx``
into these arrays, so ONE device call answers rows from many tenants —
the megabatch path that rescues the many-tenant/low-per-tenant-load
regime where per-tenant dispatches can never fill a large bucket.

Slot lifecycle: ``add`` reuses freed slots (and first-fit reuses freed
bitset word ranges) before growing; ``remove`` frees; when churn leaves
more holes than live tenants — or the bitset arena more dead words than
live — ``maybe_compact`` repacks into (possibly smaller) fresh arrays.
Entries never cache their slot id: they ask :meth:`slot_of`, so
compaction is invisible to the serving layers above. Host mirrors are
authoritative; device views are materialized lazily and invalidated on
every mutation. Capacity and bitset allocation grow geometrically so
the compiled program's shapes (and thus recompiles) change
O(log tenants) times, not per registration.

Grouping composes with placement: when the arena's
:class:`~repro.serve_filter.plan.GroupKey` carries a SHARDED placement,
the device views are laid out for the grouped ``shard_map`` program —
the combined embedding matrix row-sharded and the concatenated bitsets
word-sharded over the mesh axis (each padded so the leading dim divides
the shard count; pad rows/words are zero and never gathered/probed),
dense stacks and per-slot vectors replicated. Every view is
``device_put`` with an explicit ``NamedSharding`` straight from the
(padded copy of the) host mirror, so growth, compaction, and reload
repacking never materialize a full-size replica on any one device —
each shard only ever receives its own slice.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import existence, lmbf
from repro.runtime.trace import NULL_TRACER, Tracer
from repro.serve_filter.faults import NULL_INJECTOR, FaultInjector
from repro.serve_filter.plan import GroupKey, quantize_index

MIN_CAPACITY = 4
_BITS_GROWTH = 1.5


class PlanGroupArena:
    """Stacked device residence for every tenant sharing one GroupKey."""

    def __init__(self, key: GroupKey, executor,
                 min_capacity: int = MIN_CAPACITY, mesh=None,
                 injector: FaultInjector = NULL_INJECTOR,
                 tracer: Tracer = NULL_TRACER):
        self.key = key
        self.executor = executor            # GroupedExecutor (owns .fn)
        self.tracer = tracer                # ``tiles``/``launch`` spans
        # fault-injection sites fire BEFORE any mutation (add/swap) or
        # materialization (device_arrays): an injected fault can fail a
        # hydration or a dispatch but never corrupt arena bookkeeping
        self.injector = injector
        # placement axis: a sharded group key means the device views
        # live split over this mesh (normally the executor's own)
        self.mesh = mesh if mesh is not None \
            else getattr(executor, "mesh", None)
        if key.placement.sharded:
            if self.mesh is None:
                raise ValueError("a sharded group key needs a mesh (none "
                                 "on the executor and none passed)")
            found = self.mesh.shape.get(key.placement.axis, 1)
            if found != key.placement.n_shards:
                raise ValueError(
                    f"mesh axis {key.placement.axis!r} has size {found} "
                    f"but the group key expects "
                    f"{key.placement.n_shards} shards")
        self.min_capacity = max(1, int(min_capacity))
        self.capacity = 0
        self.version = 0                    # bumped on every mutation
        self.compactions = 0                # lifetime _repack count
        self.growths = 0                    # slot-axis + bitset growths
        self._slots: Dict[str, int] = {}    # tenant -> slot id
        self._free: List[int] = []
        # combined-embedding layout: [(col index, rows, e)] for the
        # embedded (non-one-hot) subcolumns, in column order
        self._emb_cols = [(i, rows, e) for i, (rows, e)
                          in enumerate(key.cfg.column_encodings)
                          if e is not None]
        self._emb_rows = sum(rows for _, rows, _ in self._emb_cols)
        self._e_max = max((e for _, _, e in self._emb_cols), default=1)
        # compressed storage: a quantized group key stores the combined
        # matrix int8 — or, at bits=4, nibble-PACKED uint8 (two codes per
        # byte along the feature axis, so the stored width is
        # ceil(e_max / 2) and row indexing/sharding is untouched) — with
        # a flat per-row-group scale vector laid out
        # [column block][slot][group] (a scale group never straddles a
        # tenant boundary), and the dense stacks int8 / packed uint8
        # (packed along the input axis) with per-slot per-channel scale
        # stacks — the device views carry the compressed dtype, so
        # device_nbytes drops for real
        self._quant = key.quant.enabled
        self._bits4 = self._quant and key.quant.bits == 4
        self._rg = key.quant.row_group
        self._sg_cols = [-(-rows // self._rg)
                         for _, rows, _ in self._emb_cols]
        self._sg_rows = sum(self._sg_cols)
        self._embed_scale = np.zeros(0, np.float32)
        # stored column width of the combined matrix (packed at bits=4)
        self._e_store = lmbf.packed_dim(self._e_max) if self._bits4 \
            else self._e_max
        # host mirrors (authoritative); shapes carry a leading slot axis
        if self._bits4:
            emb_dtype = np.dtype(np.uint8)
        elif self._quant:
            emb_dtype = np.dtype(np.int8)
        else:
            emb_dtype = jnp.dtype(key.cfg.dtype)
        self._embed_flat = np.zeros((0, self._e_store), emb_dtype)
        self._params: Dict[str, Dict[str, np.ndarray]] = {}
        self._tau = np.zeros(0, np.float32)
        self._m_bits = np.zeros(0, np.uint32)
        self._word_base = np.zeros(0, np.int32)
        self._word_len = np.zeros(0, np.int32)
        # concatenated fixup bitsets + free-range bookkeeping
        self._bits = np.zeros(0, np.uint32)
        self._bits_used = 0                          # high-water mark
        self._free_ranges: List[Tuple[int, int]] = []   # (base, length)
        self._device = None                 # lazily built device views
        # per-tile gathered dense weights, memoized on the batch's tile
        # signature: steady-state traffic repeats tenant layouts, and
        # the gather costs as much as the GEMM it feeds
        self._tile_cache: Dict[bytes, object] = {}
        self.tile_hits = 0                  # dispatches the cache spared
        self.tile_misses = 0                # dispatches that gathered

    # ------------------------------------------------------------- access
    def __len__(self) -> int:
        return len(self._slots)

    def __contains__(self, tenant: str) -> bool:
        return tenant in self._slots

    @property
    def tenants(self) -> List[str]:
        return list(self._slots)

    def slot_of(self, tenant: str) -> int:
        """The tenant's CURRENT slot id (compaction renumbers slots, so
        callers must not cache this across mutations)."""
        return self._slots[tenant]

    @property
    def nbytes(self) -> int:
        """ACTUAL host-mirror footprint (stacked params, combined
        embeddings incl. e_max padding, the over-allocated bitset, the
        per-slot vectors) — a bounded multiple of the members' nominal
        sizes (<= 2x slots after growth, <= 1.5x bitset, e_max-padded
        columns; compaction pulls it back down). The registry's
        ``budget_mb`` counts nominal per-filter sizes; this is the
        observable truth for capacity planning."""
        n = self._embed_flat.nbytes + self._embed_scale.nbytes + \
            self._bits.nbytes + self._tau.nbytes + self._m_bits.nbytes + \
            self._word_base.nbytes + self._word_len.nbytes
        for d in self._params.values():
            for arr in d.values():
                n += arr.nbytes
        return n

    @property
    def n_shards(self) -> int:
        """Shards the device views are split over (1 on a local arena)."""
        p = self.key.placement
        return p.n_shards if p.sharded else 1

    @property
    def device_nbytes(self) -> int:
        """TRUE per-shard device footprint of the arena's device views:
        the sharded arrays (combined embedding matrix, concatenated
        bitsets) contribute their padded per-shard slice, the
        replicated ones (dense stacks, per-slot vectors) their full
        size. Equals the device-view total on a local arena. This —
        not :attr:`nbytes`, the whole-arena host-mirror total — is
        what HBM capacity planning must watch on a sharded fleet:
        charging the full arena to every device overstates pressure by
        ~the shard count exactly where sharding is the point."""
        n = self.n_shards
        # STORED width (packed at bits=4), not the logical e_max — the
        # device views hold packed bytes, so capacity math must too
        per_shard = -(-self._embed_flat.shape[0] // n) * \
            self._embed_flat.shape[1] * self._embed_flat.itemsize
        per_shard += -(-self._bits.size // n) * self._bits.itemsize
        per_shard += self._embed_scale.nbytes      # replicated (tiny)
        per_shard += self._tau.nbytes + self._m_bits.nbytes + \
            self._word_base.nbytes
        for d in self._params.values():
            for arr in d.values():
                per_shard += arr.nbytes
        return per_shard

    @property
    def live_words(self) -> int:
        return int(self._word_len[list(self._slots.values())].sum()) \
            if self._slots else 0

    # ------------------------------------------------------------- health
    @property
    def holes(self) -> int:
        """Freed slot ids awaiting reuse (churn debt on the slot axis)."""
        return len(self._free)

    @property
    def dead_words(self) -> int:
        """Allocated-but-unowned bitset words below the high-water mark
        (churn debt on the bitset arena; what drives compaction)."""
        return self._bits_used - self.live_words

    @property
    def slot_occupancy(self) -> float:
        """Live tenants / slot capacity in [0, 1] (0.0 when empty)."""
        return len(self._slots) / self.capacity if self.capacity else 0.0

    def health(self) -> Dict[str, float]:
        """Gauge snapshot for the stats surface: occupancy, churn debt,
        lifetime compaction/growth counts, and footprints."""
        return {
            "tenants": float(len(self._slots)),
            "capacity": float(self.capacity),
            "slot_occupancy": self.slot_occupancy,
            "holes": float(self.holes),
            "dead_words": float(self.dead_words),
            "live_words": float(self.live_words),
            "compactions": float(self.compactions),
            "growths": float(self.growths),
            "host_mb": self.nbytes / 1e6,
            "device_mb": self.device_nbytes / 1e6,
        }

    # ----------------------------------------------------------- mutation
    def _emb_starts(self, cap: int) -> List[int]:
        """Start row of each embedded column's block in the combined
        embedding matrix, for a given slot capacity."""
        starts, prefix = [], 0
        for _, rows, _ in self._emb_cols:
            starts.append(cap * prefix)
            prefix += rows
        return starts

    def _sg_starts(self, cap: int) -> List[int]:
        """Start index of each embedded column's block in the flat
        per-row-group scale vector, for a given slot capacity."""
        starts, prefix = [], 0
        for ng in self._sg_cols:
            starts.append(cap * prefix)
            prefix += ng
        return starts

    def _write_slot(self, slot: int,
                    index: existence.ExistenceIndex) -> None:
        """Write a fitted index's payload into an OWNED slot whose
        bitset word range is already allocated (``word_base`` /
        ``word_len`` set for this index's filter): dense params,
        embedding blocks, tau, bitset words, m_bits. Shared by admit
        (:meth:`add`) and hot-reload (:meth:`swap`) so the two paths
        can never drift.  A quantized arena quantizes HERE — once per
        admit/reload — and stores the tenant's calibrated threshold in
        the tau vector, so quantized slots keep the no-false-negative
        invariant and reload stays zero-drain (the mirrors mutate, but
        in-flight batches hold the previous device snapshots)."""
        if self._quant:
            # the shared quantize entry point: cached on the index, so a
            # v3-checkpoint hydration (or a second placement of the same
            # index) never requantizes or recalibrates here
            qp, tau = quantize_index(index, self.key.quant)
            for name, arr in qp["dense"].items():
                self._params["dense"][name][slot] = arr
            for name, arr in qp["dense_scale"].items():
                self._params["dense_scale"][name][slot] = arr
            for (i, rows, e), start, sstart, ng in zip(
                    self._emb_cols, self._emb_starts(self.capacity),
                    self._sg_starts(self.capacity), self._sg_cols):
                e_w = lmbf.packed_dim(e) if self._bits4 else e
                self._embed_flat[start + slot * rows:
                                 start + (slot + 1) * rows, :e_w] = \
                    qp["embed"][f"col{i}"]
                self._embed_scale[sstart + slot * ng:
                                  sstart + (slot + 1) * ng] = \
                    qp["embed_scale"][f"col{i}"]
            self._tau[slot] = np.float32(tau)
        else:
            for name, arr in index.params["dense"].items():
                self._params["dense"][name][slot] = np.asarray(arr)
            starts = self._emb_starts(self.capacity)
            for (i, rows, e), start in zip(self._emb_cols, starts):
                tbl = np.asarray(index.params["embed"][f"col{i}"])
                self._embed_flat[start + slot * rows:
                                 start + (slot + 1) * rows, :e] = tbl
            self._tau[slot] = np.float32(index.tau)
        fp = index.fixup_filter.params
        base = int(self._word_base[slot])
        self._bits[base:base + fp.n_words] = \
            np.asarray(index.fixup_filter.bits)
        self._m_bits[slot] = fp.m_bits

    def add(self, tenant: str, index: existence.ExistenceIndex) -> int:
        """Stack a fitted index into the arena; returns its slot id.
        Re-adding a tenant (hot-swap) releases its old slot first."""
        self.injector.check("device_put", tenant)
        if tenant in self._slots:
            self.remove(tenant)
        slot = self._free.pop() if self._free else self._grow_one()
        fp = index.fixup_filter.params
        self._word_base[slot] = self._alloc_words(fp.n_words)
        self._word_len[slot] = fp.n_words
        self._write_slot(slot, index)
        self._slots[tenant] = slot
        self._touch()
        return slot

    def swap(self, tenant: str, index: existence.ExistenceIndex) -> int:
        """Hot-reload a member IN PLACE: overwrite the tenant's slot
        with a re-fitted index without releasing the slot id — the
        zero-drain reload path. The group key guarantees the new
        index's table rows and dense shapes match the arena layout, so
        only the payloads change; the bitset word range is reused when
        the new filter's word count matches, else reallocated (the old
        range is freed for first-fit reuse — the registry's
        ``maybe_compact`` bounds the waste across repeated reloads).

        Host mirrors mutate, but batches already dispatched hold the
        PREVIOUS device views (``device_arrays`` snapshots bound at
        dispatch time) and retire against them; the next dispatch
        materializes fresh views. Returns the (unchanged) slot id.
        """
        self.injector.check("device_put", tenant)
        slot = self._slots[tenant]
        fp = index.fixup_filter.params
        base, length = int(self._word_base[slot]), int(self._word_len[slot])
        if fp.n_words != length:
            # allocate the NEW range before touching the old one: if
            # allocation fails (growth OOM), the registry rolls the
            # tenant back to SERVING on its old epoch — which is only
            # sound if the old bitset is still intact
            new_base = self._alloc_words(fp.n_words)
            if length:
                self._bits[base:base + length] = 0
                self._free_ranges.append((base, length))
            self._word_base[slot] = new_base
            self._word_len[slot] = fp.n_words
        self._write_slot(slot, index)
        self._touch()
        return slot

    def remove(self, tenant: str) -> None:
        slot = self._slots.pop(tenant, None)
        if slot is None:
            return
        self._free.append(slot)
        base, length = int(self._word_base[slot]), int(self._word_len[slot])
        if length:
            self._bits[base:base + length] = 0
            self._free_ranges.append((base, length))
        # park the freed slot on safe geometry: padding/misrouted rows
        # must never hit a zero modulo, and probing words [0, 1) of a
        # zeroed range answers False
        self._zero_slot(slot)
        self._touch()

    def maybe_compact(self) -> bool:
        """Repack when churn leaves more holes than live tenants (slot
        axis) or more dead words than live ones (bitset arena). Returns
        True when a repack happened; slot ids are renumbered — which is
        why they are always re-read through :meth:`slot_of`."""
        n_live = len(self._slots)
        slot_waste = self.capacity - n_live
        bits_waste = self._bits_used - self.live_words
        if not ((slot_waste > max(n_live, self.min_capacity - 1)
                 and self.capacity > self.min_capacity)
                or bits_waste > max(self.live_words, 32)):
            return False
        self._repack()
        return True

    # ------------------------------------------------------------ serving
    def _snap(self, v: np.ndarray, spec: Optional[P] = None):
        """Device view of a PRIVATE copy of a host mirror. The copy is
        load-bearing: JAX may perform the host->device transfer
        asynchronously, so handing it the live mirror races an
        in-place ``swap``/``remove`` mutating that memory right after
        a dispatch — an in-flight batch could observe the NEXT epoch's
        bytes. A private copy is never mutated, so batches always
        retire against the arrays they were dispatched with (the
        zero-drain reload guarantee — placement does not change it).

        On a sharded arena, ``spec`` names the array's mesh layout:
        arrays split on their leading dim are zero-padded so it divides
        the shard count, then ``device_put`` with ``NamedSharding``
        straight onto their slices (no full replica on one device);
        everything else is replicated."""
        if self.mesh is None:
            return jnp.asarray(v.copy())
        if spec is not None and spec and spec[0] is not None:
            pad = (-v.shape[0]) % self.key.placement.n_shards
            # one pass: the zero-padded buffer IS the private copy
            arr = np.zeros((v.shape[0] + pad,) + v.shape[1:], v.dtype)
            arr[:v.shape[0]] = v
        else:
            arr = v.copy()
        return jax.device_put(arr, NamedSharding(self.mesh, spec or P()))

    def device_arrays(self):
        """(params, bits, tau, m_bits, word_base) as device arrays —
        snapshots of the mirrors, cached until the next mutation. On a
        sharded arena the combined embedding matrix is row-sharded and
        the concatenated bitsets word-sharded over the group key's mesh
        axis; dense stacks and per-slot vectors are replicated."""
        if self._device is None:
            self.injector.check("device_put", "arena")
            snap = self._snap
            axis = self.key.placement.axis      # None on a local arena
            params = {g: {k: snap(v) for k, v in d.items()}
                      for g, d in self._params.items()}
            params["embed_flat"] = snap(self._embed_flat, P(axis, None))
            if self._quant:
                # flat scale vector: replicated on every placement —
                # it is ~1/(row_group * e_max) the matrix's size
                params["embed_scale"] = snap(self._embed_scale)
            self._device = (params, snap(self._bits, P(axis)),
                            snap(self._tau),
                            snap(self._m_bits),
                            snap(self._word_base))
        return self._device

    def run(self, raw_ids, tenant_idx):
        """One megabatch dispatch: ``raw_ids`` (n, n_cols) with per-row
        arena slots ``tenant_idx`` (n,) -> (answers, model, backup).

        The executor wants whole single-tenant tiles of
        ``key.tile_rows``; callers whose n is not tile-aligned get
        padded here (wildcard rows on the last row's slot — a full
        single-tenant batch stays single-tenant) and the outputs
        sliced back. Each call counts as a tile-cache hit or miss
        (``tile_hits`` / ``tile_misses``); with tracing on, the
        ``tiles`` span covers the cache lookup (and on a miss the gather
        and the slot vector's transfer), the ``launch`` span the
        fused program's dispatch.
        """
        raw = np.asarray(raw_ids, np.int32)
        idx = np.asarray(tenant_idx, np.int32)
        n = raw.shape[0]
        pad = (-n) % self.key.tile_rows
        if pad:
            raw = np.concatenate(
                [raw, np.zeros((pad, raw.shape[1]), raw.dtype)])
            idx = np.concatenate(
                [idx, np.full(pad, idx[-1] if n else 0, np.int32)])
        params, bits, tau, m_bits, base = self.device_arrays()
        with self.tracer.span("tiles", cat="detail"):
            sig = idx.tobytes()
            hit = self._tile_cache.get(sig)
            if hit is None:
                self.tile_misses += 1
                tile_idx = idx.reshape(-1, self.key.tile_rows)[:, 0]
                hit = (self.executor.gather_tiles(params,
                                                  jnp.asarray(tile_idx)),
                       jnp.asarray(idx))
                if len(self._tile_cache) >= 8:  # bounded: drop arbitrary
                    self._tile_cache.pop(next(iter(self._tile_cache)))
                self._tile_cache[sig] = hit
            else:
                self.tile_hits += 1
        tiles, idx_dev = hit
        with self.tracer.span("launch", cat="detail"):
            out = self.executor.call(params, tiles, bits, tau, m_bits,
                                     base, idx_dev, raw)
        if pad:
            out = tuple(o[:n] for o in out)
        return out

    def run_single(self, raw_ids, slot: int):
        """Whole-batch dispatch for ONE tenant through the grouped
        program (a constant tenant_idx vector) — the degenerate case the
        scheduler hits when no group sibling has queued rows."""
        n = np.asarray(raw_ids).shape[0]
        return self.run(raw_ids, np.full(n, slot, np.int32))

    @property
    def tile_rows(self) -> int:
        return self.key.tile_rows

    # ----------------------------------------------------------- plumbing
    def _touch(self) -> None:
        self.version += 1
        self._device = None
        self._tile_cache.clear()    # slot ids / weights may have moved

    def _zero_slot(self, slot: int) -> None:
        for d in self._params.values():
            for arr in d.values():
                arr[slot] = 0
        for (_, rows, _), start in zip(self._emb_cols,
                                       self._emb_starts(self.capacity)):
            self._embed_flat[start + slot * rows:
                             start + (slot + 1) * rows] = 0
        if self._quant:
            for ng, sstart in zip(self._sg_cols,
                                  self._sg_starts(self.capacity)):
                self._embed_scale[sstart + slot * ng:
                                  sstart + (slot + 1) * ng] = 0
        self._tau[slot] = 0.0
        self._m_bits[slot] = 32
        self._word_base[slot] = 0
        self._word_len[slot] = 0

    def _grow_one(self) -> int:
        """Claim a fresh slot, doubling the stacked arrays as needed."""
        used = self.capacity - len(self._free)
        if used < self.capacity:
            # unreachable via add() (free slots pop first); guard anyway
            return self._free.pop()
        new_cap = max(self.min_capacity, 2 * self.capacity)
        self.growths += 1
        self._resize_slots(new_cap)
        slot = len(self._slots)     # first never-used slot
        self._free.extend(range(self.capacity - 1, slot, -1))
        return slot

    def _resize_slots(self, new_cap: int) -> None:
        spec = lmbf.params_spec(self.key.cfg)
        old = self.capacity
        keep = min(old, new_cap)
        fresh: Dict[str, Dict[str, np.ndarray]] = {"dense": {}}
        if self._quant:
            fresh["dense_scale"] = {}
        for name, s in spec["dense"].items():
            dtype = jnp.dtype(s.dtype)
            shape = tuple(s.shape)
            if self._quant and name.startswith("w"):
                if self._bits4:
                    # packed along the input axis: two codes per byte
                    dtype = np.dtype(np.uint8)
                    shape = (lmbf.packed_dim(shape[0]),) + shape[1:]
                else:
                    dtype = np.dtype(np.int8)
                sc = np.zeros((new_cap, s.shape[-1]), np.float32)
                if old:
                    sc[:keep] = self._params["dense_scale"][name][:keep]
                fresh["dense_scale"][name] = sc
            arr = np.zeros((new_cap,) + shape, dtype)
            if old:
                arr[:keep] = self._params["dense"][name][:keep]
            fresh["dense"][name] = arr
        self._params = fresh
        flat = np.zeros((new_cap * self._emb_rows, self._e_store),
                        self._embed_flat.dtype)
        if old:
            for (_, rows, _), new_start, old_start in zip(
                    self._emb_cols, self._emb_starts(new_cap),
                    self._emb_starts(old)):
                flat[new_start:new_start + keep * rows] = \
                    self._embed_flat[old_start:old_start + keep * rows]
        self._embed_flat = flat
        if self._quant:
            scale = np.zeros(new_cap * self._sg_rows, np.float32)
            if old:
                for ng, new_start, old_start in zip(
                        self._sg_cols, self._sg_starts(new_cap),
                        self._sg_starts(old)):
                    scale[new_start:new_start + keep * ng] = \
                        self._embed_scale[old_start:old_start + keep * ng]
            self._embed_scale = scale

        def vec(v, fill, dtype):
            out = np.full(new_cap, fill, dtype)
            out[:min(old, new_cap)] = v[:min(old, new_cap)]
            return out

        self._tau = vec(self._tau, 0.0, np.float32)
        self._m_bits = vec(self._m_bits, 32, np.uint32)
        self._word_base = vec(self._word_base, 0, np.int32)
        self._word_len = vec(self._word_len, 0, np.int32)
        self.capacity = new_cap

    def _alloc_words(self, n_words: int) -> int:
        """First-fit over freed bitset ranges, else append (growing the
        packed arena geometrically so its device shape is stable across
        minor churn)."""
        for i, (base, length) in enumerate(self._free_ranges):
            if length >= n_words:
                if length > n_words:
                    self._free_ranges[i] = (base + n_words,
                                            length - n_words)
                else:
                    del self._free_ranges[i]
                return base
        base = self._bits_used
        need = base + n_words
        if need > self._bits.size:
            alloc = max(int(need * _BITS_GROWTH), 64)
            grown = np.zeros(alloc, np.uint32)
            grown[:self._bits.size] = self._bits
            self._bits = grown
            self.growths += 1
        self._bits_used = need
        return base

    def _repack(self) -> None:
        """Rebuild packed: live tenants keep their relative slot order,
        bitsets land back to back, stacked arrays shrink to the growth
        curve's smallest fit."""
        self.compactions += 1
        live = sorted(self._slots.items(), key=lambda kv: kv[1])
        old_params, old_bits = self._params, self._bits
        old_tau, old_mb = self._tau, self._m_bits
        old_base, old_len = self._word_base, self._word_len
        old_flat, old_cap = self._embed_flat, self.capacity
        old_scale = self._embed_scale

        new_cap = self.min_capacity
        while new_cap < len(live):
            new_cap *= 2
        self.capacity = 0
        self._params = {}
        self._embed_flat = np.zeros((0, self._e_store), old_flat.dtype)
        self._resize_slots(new_cap)

        total_words = int(sum(old_len[s] for _, s in live))
        self._bits = np.zeros(max(int(total_words * _BITS_GROWTH), 64),
                              np.uint32)
        self._bits_used = total_words
        self._free_ranges = []
        self._slots = {}
        self._free = list(range(new_cap - 1, len(live) - 1, -1))

        new_starts = self._emb_starts(new_cap)
        old_starts = self._emb_starts(old_cap)
        new_sg = self._sg_starts(new_cap)
        old_sg = self._sg_starts(old_cap)
        cursor = 0
        for new_slot, (tenant, old_slot) in enumerate(live):
            for group, d in self._params.items():
                for name, arr in d.items():
                    arr[new_slot] = old_params[group][name][old_slot]
            for (_, rows, _), ns, os_ in zip(self._emb_cols, new_starts,
                                             old_starts):
                self._embed_flat[ns + new_slot * rows:
                                 ns + (new_slot + 1) * rows] = \
                    old_flat[os_ + old_slot * rows:
                             os_ + (old_slot + 1) * rows]
            if self._quant:
                for ng, ns_, os_ in zip(self._sg_cols, new_sg, old_sg):
                    self._embed_scale[ns_ + new_slot * ng:
                                      ns_ + (new_slot + 1) * ng] = \
                        old_scale[os_ + old_slot * ng:
                                  os_ + (old_slot + 1) * ng]
            self._tau[new_slot] = old_tau[old_slot]
            self._m_bits[new_slot] = old_mb[old_slot]
            length = int(old_len[old_slot])
            src = int(old_base[old_slot])
            self._bits[cursor:cursor + length] = \
                old_bits[src:src + length]
            self._word_base[new_slot] = cursor
            self._word_len[new_slot] = length
            self._slots[tenant] = new_slot
            cursor += length
        self._touch()
