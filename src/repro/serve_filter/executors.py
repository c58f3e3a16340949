"""One executor core, two orthogonal axes: grouping x placement.

The executor layer used to be three sibling classes each owning a whole
compilation recipe. It is now ONE composed core with two independent
axes, and the classes are thin facades over it:

* the **grouping axis** decides the program *signature* and how model
  weights / fixup geometry are bound — per-tenant operands
  (``params, bits, tau``) for a single-tenant program, arena operands
  (stacked params, concatenated bitsets, per-row ``tenant_idx`` +
  geometry vectors) for a megabatch program;
* the **placement axis** decides where each array's elements live and
  how a stage rebuilds a full answer — plain gathers/probes on one
  device, or masked local gathers / word-slice probes + ONE ``psum``
  under ``shard_map`` over a mesh axis.

The four combinations share the same pipeline body
(``existence.query_stages``) and the same placement ingredients:

===============  ==========================  ===========================
                 local                       sharded
===============  ==========================  ===========================
single-tenant    :class:`LocalExecutor`      :class:`ShardedExecutor`
                 (plain jit)                 (tables row-sharded, bitset
                                             word-sharded, one psum per
                                             stage)
grouped          :class:`GroupedExecutor`    :class:`GroupedExecutor`
                 (arena operands)            with a sharded
                                             :class:`~repro.serve_filter
                                             .plan.GroupKey`: the
                                             COMBINED embedding matrix is
                                             row-sharded, the
                                             CONCATENATED bitsets are
                                             word-sharded (per-slot word
                                             bases rebased per shard),
                                             probes combine with ONE psum
===============  ==========================  ===========================

Program builders: :func:`_tenant_program` (grouping off) and
:func:`_grouped_program` (grouping on), each taking the placement from
the plan / group key and reusing ``bloom.shard_miss_count`` /
``bloom.grouped_shard_miss_count`` and the word-offset Pallas probes.
Answers are bit-identical to :class:`LocalExecutor` by construction on
every leg: gathers/one-hots/probe rebasing are integer-exact, every
table row and probe word is owned by exactly one shard (the psum adds
one real term and zeros), and the output layer shares the
multiply+reduce form of ``lmbf.mlp_head`` — property-tested in
tests/test_serve_sharded.py, tests/test_serve_grouped.py, and
tests/test_serve_grouped_sharded.py.

Executors are cached per (plan, mesh) — grouped ones per (group key,
mesh) — so heterogeneous tenants whose filters share a plan share
compiled programs; the registry's eviction hooks (:func:`release_plan`,
:func:`release_grouped_executor`) drop cache entries once no tenant
references them. :func:`compiled_program_count` sums live XLA programs
across all cached executors for the stats surface.

Hot-reload contract: executors are STATELESS with respect to tenant
arrays — every dispatch binds the arrays it was handed (a
:class:`PlacedFilter`, or an arena's device views) at call time, and
JAX arrays are immutable. A tenant reload therefore never touches the
executor or its compiled programs: the registry installs a fresh
``PlacedFilter`` (or swaps the arena slot) and batches already
dispatched keep computing against the arrays they captured — which is
what lets ``TenantHandle.reload`` swap a re-fitted index with no drain
and no misanswered in-flight rows, on every placement.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import bloom, existence, lmbf
from repro.kernels.bloom_query import ops as bloom_ops
from repro.kernels.qr_embed import ops as qr_ops
from repro.nn.spec import is_spec
from repro.serve_filter.plan import (GroupKey, PROBE_KERNEL, QueryPlan,
                                     quantize_index)
from repro.sharding import rules


# ================================================================ telemetry
# Process-global (like the executor caches themselves): compile events
# per (plan/group-key label, bucket) and cache hit/miss counters. A
# compile is detected as a jit-cache growth across one dispatch — jit
# traces + compiles synchronously inside the first call per shape, so
# that call's wall time ~ the compile cost (the answer itself is
# returned as an unrealized async array).

_COMPILES: Dict[Tuple[str, int], list] = {}   # (label, bucket) -> [n, sec]
_CACHE_HITS = 0
_CACHE_MISSES = 0

# Process-global compile-site fault hook (parallels the process-global
# compile telemetry: the jit caches are shared across servers, so the
# injection point must be too). ``None`` unless a chaos-configured
# server installed its injector via :func:`set_fault_injector`; the
# fault fires AFTER the program landed in the jit cache — modeling
# "compile succeeded but blew its budget", so the retry that follows
# hits the cache instead of recompiling.
_FAULT_INJECTOR = None


def set_fault_injector(injector) -> None:
    """Install (or with ``None`` uninstall) the compile-site fault
    injector. Only fault-enabled servers call this; disabled servers
    leave the hot path untouched."""
    global _FAULT_INJECTOR
    _FAULT_INJECTOR = injector


def _record_compile(label: str, bucket: int, seconds: float) -> None:
    ev = _COMPILES.setdefault((label, int(bucket)), [0, 0.0])
    ev[0] += 1
    ev[1] += seconds


def _timed_call(ex, label: str, bucket: int, *operands):
    """Run ``ex.fn(*operands)``, charging the wall time to compile
    telemetry when the call grew the jit cache. Returns
    ``(outputs, compiled)``."""
    before = ex.program_count()
    t0 = time.perf_counter()
    out = ex.fn(*operands)
    dt = time.perf_counter() - t0
    compiled = ex.program_count() > before
    if compiled:
        _record_compile(label, bucket, dt)
        if _FAULT_INJECTOR is not None:
            _FAULT_INJECTOR.check("compile", label)
    return out, compiled


def compile_stats() -> Dict[Tuple[str, int], Tuple[int, float]]:
    """Snapshot: (plan/group label, bucket) -> (compiles, total secs)."""
    return {k: (v[0], v[1]) for k, v in _COMPILES.items()}


def compile_count() -> int:
    return sum(v[0] for v in _COMPILES.values())


def compile_time_total() -> float:
    return sum(v[1] for v in _COMPILES.values())


def cache_stats() -> Tuple[int, int]:
    """(executor-cache hits, misses) across both executor caches."""
    return _CACHE_HITS, _CACHE_MISSES


def reset_telemetry() -> None:
    """Zero the compile/cache counters (tests, bench windows)."""
    global _CACHE_HITS, _CACHE_MISSES
    _COMPILES.clear()
    _CACHE_HITS = 0
    _CACHE_MISSES = 0


@dataclasses.dataclass
class PlacedFilter:
    """One tenant's device-resident arrays, laid out per the plan.

    For local placement these are plain single-device arrays; for
    sharded placement the embedding tables / bitset are padded to
    divide the shard count and carry ``NamedSharding`` over the plan's
    mesh axis.  Under a quantized plan ``params`` is the int8 qparams
    tree (tables + dense int8, per-row-group / per-channel fp32 scales)
    and ``tau`` carries the tenant's calibrated serving threshold —
    lowered by the admit-time logit margin so quantized scores never
    flip an fp32-accepted key into a false negative.
    """
    params: object              # model params pytree (int8 qparams if quant)
    bits: jax.Array             # packed fixup bitset
    tau: Optional[float] = None  # calibrated threshold override (quant)


class Executor:
    """Interface: a compiled query path for one :class:`QueryPlan`."""

    plan: QueryPlan
    fn: Callable                # (params, bits, tau, raw_ids) -> 3-tuple

    def place(self, index: existence.ExistenceIndex) -> PlacedFilter:
        raise NotImplementedError

    def __call__(self, placed: PlacedFilter, tau, raw_ids):
        if placed.tau is not None:
            tau = placed.tau
        out, _ = _timed_call(self, self.plan.describe(),
                             raw_ids.shape[0], placed.params,
                             placed.bits, tau, raw_ids)
        return out

    def program_count(self) -> int:
        """Live jit-cache entries (plan-shape x bucket XLA programs)."""
        return self.fn._cache_size()


# ===================================================================== core
# placement-axis ingredients, shared by the single-tenant and grouped
# program builders

def _shard_wrap(mesh: Mesh, body, in_specs, out_specs, *,
                check_vma: bool):
    """The sharded placement's program wrapper: ``jit(shard_map(...))``
    (``check_vma=False`` for the Pallas probe flavor — pallas_call has
    no replication rule)."""
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=check_vma))


def _tenant_param_specs(plan: QueryPlan, mesh: Mesh):
    """PartitionSpec tree for a single tenant's (padded) param pytree,
    resolved through sharding/rules.py: 'vocab' (table rows) -> the
    shard axis, every other logical axis replicated."""
    axis = plan.placement.axis
    table = {"vocab": (axis,)}
    spec_tree = lmbf.params_spec(plan.cfg)

    def one(s):
        shape = list(s.shape)
        if s.axes and s.axes[0] == "vocab":
            shape[0] = (plan.table_rows_per_shard(shape[0])
                        * plan.placement.n_shards)
        return rules.spec_for(shape, s.axes, mesh, table)

    return jax.tree.map(one, spec_tree, is_leaf=is_spec)


def _sharded_tenant_predict(cfg, axis: str):
    """lmbf.predict over vocab-sharded per-tenant tables: masked local
    gathers, ONE psum to rebuild the feature row, replicated MLP head.
    One-hot columns have no table — compute them on shard 0 only so
    the psum is exact (no 1/n rescaling)."""

    def predict_fn(params, cfg_, enc):
        shard = jax.lax.axis_index(axis)
        feats = []
        for i, (rows, e) in enumerate(cfg_.column_encodings):
            ids = enc[..., i]
            if e is None:
                oh = jax.nn.one_hot(ids, rows, dtype=cfg_.dtype)
                feats.append(jnp.where(shard == 0, oh,
                                       jnp.zeros_like(oh)))
            else:
                tbl = params["embed"][f"col{i}"]    # (rows_local, e)
                rl = tbl.shape[0]
                lid = ids - shard * rl
                ok = (lid >= 0) & (lid < rl)
                g = jnp.take(tbl, jnp.clip(lid, 0, rl - 1), axis=0)
                feats.append(jnp.where(ok[..., None], g,
                                       jnp.zeros_like(g)))
        x = jax.lax.psum(jnp.concatenate(feats, axis=-1), axis)
        return jax.nn.sigmoid(lmbf.mlp_head(params, cfg_, x))

    return predict_fn


def _sharded_quant_predict(cfg, axis: str, row_group: int,
                           bits: int = 8, grid: str = "linear"):
    """The quantized flavor of :func:`_sharded_tenant_predict`: int8 (or
    packed-int4 uint8) tables row-sharded, fp32 scale vectors replicated
    (they are tiny).  The owning shard dequantizes its row in place —
    unpack + ``value * scale``, the reference ``lmbf.q_gather`` math —
    and the psum adds exact zeros from everyone else, so
    quantized-sharded scores are bit-identical to quantized-local.
    Feature-axis packing means row ownership (and therefore the
    sharding) is unchanged at 4 bits.  One-hot columns run through the
    bit-packed mask form (``lmbf.onehot_feature``), identical {0, 1}
    floats to ``jax.nn.one_hot``.  Out-of-vocab ids wrap/NaN-fill
    exactly like the local gather, applied post-psum."""

    def predict_fn(params, cfg_, enc):
        shard = jax.lax.axis_index(axis)
        pieces, masks = [], []
        for i, (rows, e) in enumerate(cfg_.column_encodings):
            ids = enc[..., i]
            if e is None:
                oh = lmbf.onehot_feature(ids, rows, cfg_.dtype)
                pieces.append(jnp.where(shard == 0, oh,
                                        jnp.zeros_like(oh)))
                masks.append(None)
            else:
                q = params["embed"][f"col{i}"]     # (rows_local, e|pk)
                s = params["embed_scale"][f"col{i}"]    # (ng,) f32, repl
                rl = q.shape[0]
                wrapped = jnp.where(ids < 0, ids + rows, ids)
                valid = (wrapped >= 0) & (wrapped < rows)
                safe = jnp.clip(wrapped, 0, rows - 1)
                lid = safe - shard * rl
                ok = (lid >= 0) & (lid < rl)
                g = jnp.take(q, jnp.clip(lid, 0, rl - 1), axis=0)
                if bits == 4:
                    g = lmbf.nibble_values(
                        lmbf.unpack_nibbles(g, axis=-1), grid,
                        cfg_.dtype)[..., :e]
                else:
                    g = g.astype(cfg_.dtype)
                g = g * jnp.take(s, safe // row_group)[..., None] \
                    .astype(cfg_.dtype)
                pieces.append(jnp.where(ok[..., None], g,
                                        jnp.zeros_like(g)))
                masks.append(valid)
        x = jax.lax.psum(jnp.concatenate(pieces, axis=-1), axis)
        segs, off = [], 0
        for i, (rows, e) in enumerate(cfg_.column_encodings):
            w = e if e is not None else rows
            seg = x[..., off:off + w]
            if masks[i] is not None:
                seg = jnp.where(masks[i][..., None], seg,
                                jnp.asarray(jnp.nan, cfg_.dtype))
            segs.append(seg)
            off += w
        x = jnp.concatenate(segs, axis=-1)
        dense = lmbf.dequantize_dense(params, cfg_.dtype, cfg_,
                                      bits=bits, grid=grid)
        return jax.nn.sigmoid(lmbf.mlp_head({"dense": dense}, cfg_, x))

    return predict_fn


def _quantize_index(plan: QueryPlan, index: existence.ExistenceIndex):
    """Admit/reload-time quantization of one tenant: qparams tree +
    calibrated serving threshold, via the ONE shared (index-cached)
    entry point — deterministic in (params, QuantConfig), so grouped /
    ungrouped / sharded placements of the same index agree exactly and
    a v3-checkpoint hydration skips the work entirely."""
    return quantize_index(index, plan.quant)


# ------------------------------------------- single-tenant (grouping off)

def _tenant_program(plan: QueryPlan, mesh: Optional[Mesh]):
    """One compiled program for one tenant's arrays, on either
    placement: the grouping-OFF leg of the composed core."""
    cfg, fp = plan.cfg, plan.fixup_params
    quant = plan.quant.enabled
    rg = plan.quant.row_group
    qbits, qgrid = plan.quant.bits, plan.quant.grid

    if not plan.placement.sharded:
        if plan.probe == PROBE_KERNEL:
            def probe(bits, ids):
                return bloom_ops.bloom_query(ids, bits, fp,
                                             block_n=plan.block_n,
                                             interpret=plan.interpret)
        else:
            probe = None

        if quant:
            # fused dequant: the program binds the quantized qparams
            # tree and applies unpack + value * scale inside the
            # gather/GEMM body (predict_q also routes one-hot columns
            # through the bit-packed mask form)
            def local_predict(p, cfg_, enc):
                return lmbf.predict_q(p, cfg_, enc, row_group=rg,
                                      bits=qbits, grid=qgrid)
        else:
            local_predict = None

        @jax.jit
        def fused(params, bits, tau, raw_ids):
            return existence.query_stages(params, cfg, tau, bits, fp,
                                          raw_ids, probe_fn=probe,
                                          predict_fn=local_predict)

        return fused

    axis = plan.placement.axis
    wl = plan.words_per_shard()
    predict_fn = (_sharded_quant_predict(cfg, axis, rg, qbits, qgrid)
                  if quant else _sharded_tenant_predict(cfg, axis))

    if plan.probe == PROBE_KERNEL:
        def local_miss(bits_local, ids):
            off = (jax.lax.axis_index(axis) * wl).astype(jnp.int32)
            return bloom_ops.bloom_query_shard(
                ids, bits_local, off[None], fp,
                block_n=plan.block_n, interpret=plan.interpret)
    else:
        def local_miss(bits_local, ids):
            off = jax.lax.axis_index(axis) * wl
            return bloom.shard_miss_count(bits_local, ids, fp, off)

    def probe_fn(bits_local, ids):
        # each probe word is owned by exactly one shard: zero
        # misses across all shards <=> every probed bit is set
        miss = jax.lax.psum(local_miss(bits_local, ids), axis)
        return miss == 0

    def body(params, bits_local, tau, raw_ids):
        return existence.query_stages(params, cfg, tau, bits_local,
                                      fp, raw_ids, probe_fn=probe_fn,
                                      predict_fn=predict_fn)

    if quant:
        # qparams tree: int8 tables row-sharded like their fp32
        # counterparts; scale vectors and the (int8) dense stack are
        # tiny, so they replicate (pytree-prefix specs)
        param_specs = {"embed": P(axis, None), "embed_scale": P(),
                       "dense": P(), "dense_scale": P()}
    else:
        param_specs = _tenant_param_specs(plan, mesh)
    return _shard_wrap(mesh, body,
                       (param_specs, P(axis), P(), P()),
                       (P(), P(), P()),
                       check_vma=plan.probe != PROBE_KERNEL)


def _place_local(plan: QueryPlan,
                 index: existence.ExistenceIndex) -> PlacedFilter:
    if not plan.quant.enabled:
        return PlacedFilter(params=index.params,
                            bits=jnp.asarray(index.fixup_filter.bits))
    qp, tau_q = _quantize_index(plan, index)
    return PlacedFilter(params=jax.tree.map(jnp.asarray, qp),
                        bits=jnp.asarray(index.fixup_filter.bits),
                        tau=tau_q)


def _place_sharded(plan: QueryPlan, mesh: Mesh,
                   index: existence.ExistenceIndex) -> PlacedFilter:
    """Pad + scatter a fitted index onto the mesh: each shard gets its
    table-row and bitset-word slice directly (no full-size replica
    materializes on any one device).  Quantized plans scatter the int8
    tables (4x fewer bytes per shard) and replicate the fp32 scale
    vectors alongside the dense stack."""
    cfg = plan.cfg
    n = plan.placement.n_shards
    axis = plan.placement.axis
    shard1d = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    quant = plan.quant.enabled
    src, tau_q = ((index.params, None) if not quant
                  else _quantize_index(plan, index))

    embed = {}
    for i, (rows, e) in enumerate(cfg.column_encodings):
        if e is None:
            continue
        tbl = np.asarray(src["embed"][f"col{i}"])
        rl = plan.table_rows_per_shard(rows)
        padded = np.zeros((rl * n,) + tbl.shape[1:], tbl.dtype)
        padded[:rows] = tbl
        embed[f"col{i}"] = jax.device_put(
            padded, NamedSharding(mesh, P(axis, None)))
    dense = {k: jax.device_put(np.asarray(v), repl)
             for k, v in src["dense"].items()}
    params = {"embed": embed, "dense": dense}
    if quant:
        params["embed_scale"] = {k: jax.device_put(v, repl)
                                 for k, v in src["embed_scale"].items()}
        params["dense_scale"] = {k: jax.device_put(v, repl)
                                 for k, v in src["dense_scale"].items()}

    bits = np.asarray(index.fixup_filter.bits)
    padded_bits = np.zeros(plan.words_per_shard() * n, np.uint32)
    padded_bits[:bits.size] = bits
    return PlacedFilter(params=params,
                        bits=jax.device_put(padded_bits, shard1d),
                        tau=tau_q)


# ------------------------------------------------- grouped (grouping on)

def _grouped_program(key: GroupKey, mesh: Optional[Mesh]):
    """The megabatch program for a whole plan group, on either
    placement: the grouping-ON leg of the composed core. Returns
    ``(fused, gather_tiles)``.

    Signature (all but the group key traced, so one program serves any
    tenant mix)::

        fused(params, tiles, bits, tau_vec, m_bits_vec, base_vec,
              tenant_idx, raw_ids) -> (answers, model_yes, backup_yes)

    ``params`` is the arena's stacked pytree (combined embedding matrix
    + dense stacks), ``bits`` the concatenated fixup bitsets, and the
    three vectors are indexed by each row's ``tenant_idx``: its
    threshold, its filter's modulo, and its bitset's first word. Under
    a sharded placement the combined embedding matrix arrives
    row-sharded and the concatenated bitsets word-sharded over the mesh
    axis; the gather and the probe each rebase their global index into
    the local slice, mask what the shard does not own, and combine with
    ONE ``psum`` — exactly the single-tenant sharded recipe, applied to
    arena-global indices.
    """
    cfg, nh, tile = key.cfg, key.n_hashes, key.tile_rows
    n_hidden = len(cfg.hidden)
    sharded = key.placement.sharded
    axis = key.placement.axis
    quant = key.quant.enabled
    rg = key.quant.row_group
    bits4 = quant and key.quant.bits == 4
    qgrid = key.quant.grid
    # input-axis widths the packed dense stacks unpack back to
    dense_dims = lmbf.dense_in_dims(cfg) if bits4 else None
    # combined-embedding layout (must mirror PlanGroupArena's):
    # embedded columns' tables live back to back in one row-padded
    # matrix so ONE gather serves every subcolumn
    emb_cols = [(i, rows, e)
                for i, (rows, e) in enumerate(cfg.column_encodings)
                if e is not None]
    # per-column scale-group counts: the arena's flat scale vector is
    # laid out [column block][slot][row group], so a scale group never
    # straddles a tenant boundary
    sg_cols = [-(-rows // rg) for _, rows, _ in emb_cols]

    @jax.jit
    def gather_tiles(params, tile_idx):
        """Per-tile dense-stack weights: {w{li}: (g, i, o), b{li}:
        (g, o), w_out: (g, prev), b_out: (g,)}. Indices are
        scheduler-controlled live slots, so the bounds check is
        safely skipped. Dense stacks are replicated on every
        placement (tables + bitsets carry the bytes), so the tiles
        are too.  Quantized arenas dequantize HERE — int8 / packed
        uint8 stacks stay compressed in device memory; only the (tiny,
        memoized) gathered tiles widen to fp32, via the same
        per-channel unpack + value * scale as the ungrouped path.  At
        bits=4 with the kernel probe flavor the nibble split + LUT
        decode runs in-tile (kernels/qr_embed q_dense) so the unpacked
        code tensor never round-trips through HBM; the pure-jnp form
        is the same math elementwise, so both are bit-identical."""

        def deq4(w, s, prev):
            # (g, pk, width) packed + (g, width) scales -> (g, prev,
            # width) floats, matching lmbf.dequantize_dense per tile
            if key.probe == PROBE_KERNEL and not sharded:
                return qr_ops.q4_dense_dequant(
                    w, s, prev=prev, grid=qgrid,
                    interpret=key.interpret)
            codes = lmbf.unpack_nibbles(w, axis=1)[:, :prev]
            return (lmbf.nibble_values(codes, qgrid, cfg.dtype)
                    * s[:, None, :])

        tiles = {}
        for li in range(n_hidden):
            w = params["dense"][f"w{li}"] \
                .at[tile_idx].get(mode="promise_in_bounds")
            if quant:
                s = params["dense_scale"][f"w{li}"] \
                    .at[tile_idx].get(mode="promise_in_bounds")
                with jax.named_scope("dequant_dense"):
                    w = deq4(w, s, dense_dims[f"w{li}"]) if bits4 \
                        else w.astype(cfg.dtype) * s[:, None, :]
            tiles[f"w{li}"] = w
            tiles[f"b{li}"] = params["dense"][f"b{li}"] \
                .at[tile_idx].get(mode="promise_in_bounds")
        w_out = params["dense"]["w_out"] \
            .at[tile_idx].get(mode="promise_in_bounds")
        if quant:
            s = params["dense_scale"]["w_out"] \
                .at[tile_idx].get(mode="promise_in_bounds")  # (g, 1)
            with jax.named_scope("dequant_dense"):
                if bits4:
                    w_out = deq4(w_out, s, dense_dims["w_out"])[..., 0]
                else:
                    w_out = w_out[..., 0].astype(cfg.dtype) * s
        else:
            w_out = w_out[..., 0]
        tiles["w_out"] = w_out
        tiles["b_out"] = params["dense"]["b_out"] \
            .at[tile_idx].get(mode="promise_in_bounds")[..., 0]
        return tiles

    # probe flavor x placement: whole-arena probe locally, word-slice
    # miss counts (per-slot bases rebased by the shard's offset) +
    # ONE psum when sharded
    if key.probe == PROBE_KERNEL:
        if sharded:
            def slice_miss(bits_local, ids, mb_rows, base_rows, off):
                return bloom_ops.bloom_query_grouped_shard(
                    ids, bits_local, base_rows, mb_rows, off[None],
                    n_hashes=nh, block_n=key.block_n,
                    interpret=key.interpret)
        else:
            def whole_probe(bits, ids, mb_rows, base_rows):
                return bloom_ops.bloom_query_grouped(
                    ids, bits, base_rows, mb_rows, n_hashes=nh,
                    block_n=key.block_n, interpret=key.interpret)
    else:
        if sharded:
            def slice_miss(bits_local, ids, mb_rows, base_rows, off):
                return bloom.grouped_shard_miss_count(
                    bits_local, ids, nh, mb_rows, base_rows, off)
        else:
            def whole_probe(bits, ids, mb_rows, base_rows):
                return bloom.grouped_query(bits, ids, nh, mb_rows,
                                           base_rows)

    def fused_body(params, tiles, bits, tau_vec, m_bits_vec, base_vec,
                   tenant_idx, raw_ids):
        def predict_fn(p, cfg_, enc):
            gathered = None
            valids = []
            if emb_cols:
                flat = p["embed_flat"]
                # the per-slot vectors are replicated and slot-indexed,
                # so their length IS the arena capacity — the combined
                # matrix itself may carry shard-padding rows
                cap = tau_vec.shape[0]
                parts, sparts, prefix, sprefix = [], [], 0, 0
                for (i, rows, _), ng in zip(emb_cols, sg_cols):
                    # reproduce the local path's jnp.take semantics
                    # EXACTLY — negative ids wrap pythonically,
                    # out-of-bounds ids become NaN rows — while
                    # keeping the combined-matrix index inside THIS
                    # tenant's block (an out-of-vocab id must never
                    # read a neighbor tenant's rows)
                    ids = enc[..., i]
                    wrapped = jnp.where(ids < 0, ids + rows, ids)
                    valids.append((wrapped >= 0) & (wrapped < rows))
                    safe = jnp.clip(wrapped, 0, rows - 1)
                    parts.append(cap * prefix + tenant_idx * rows
                                 + safe)
                    if quant:
                        sparts.append(cap * sprefix + tenant_idx * ng
                                      + safe // rg)
                    prefix += rows
                    sprefix += ng
                idx = jnp.stack(parts, axis=-1)     # (n, C) global rows
                sidx = jnp.stack(sparts, axis=-1) if quant else None

                def dequant(g, shape):
                    # fused dequant: the replicated flat scale vector
                    # is slot-blocked, so sidx never reads a neighbor
                    # tenant's scales; unpack + value * scale is the
                    # reference lmbf.q_gather math, bit-identical on
                    # every placement (at bits=4 the gathered packed
                    # bytes double to 2*pk code columns here — the
                    # per-column e-slice below trims the pad)
                    with jax.named_scope("q_gather"):
                        sc = p["embed_scale"].at[sidx.reshape(-1)] \
                            .get(mode="promise_in_bounds").reshape(shape)
                    with jax.named_scope("dequant"):
                        if bits4:
                            g = lmbf.nibble_values(
                                lmbf.unpack_nibbles(g, axis=-1), qgrid,
                                cfg_.dtype)
                        else:
                            g = g.astype(cfg_.dtype)
                        return g * sc[..., None]

                if sharded:
                    # row-sharded combined matrix: every global row is
                    # owned by exactly one shard — masked local gather,
                    # ONE psum (adds the owned row + zeros, exact)
                    rl = flat.shape[0]
                    local = idx - jax.lax.axis_index(axis) * rl
                    owned = (local >= 0) & (local < rl)
                    g = flat.at[jnp.clip(local, 0, rl - 1).reshape(-1)] \
                        .get(mode="promise_in_bounds") \
                        .reshape(idx.shape[0], len(emb_cols), -1)
                    if quant:
                        g = dequant(g, idx.shape[:1] + (len(emb_cols),))
                    gathered = jax.lax.psum(
                        jnp.where(owned[..., None], g,
                                  jnp.zeros_like(g)), axis)
                elif quant and key.probe == PROBE_KERNEL:
                    # Pallas gather: compressed rows never widen in
                    # HBM, scales (and at bits=4 the nibble split +
                    # LUT decode) applied in-tile — same elementwise
                    # math as the jnp path
                    if bits4:
                        gathered = qr_ops.q4_embed_lookup(
                            idx, sidx, flat, p["embed_scale"],
                            grid=qgrid, block_n=key.block_n,
                            interpret=key.interpret)
                    else:
                        gathered = qr_ops.q8_embed_lookup(
                            idx, sidx, flat, p["embed_scale"],
                            block_n=key.block_n, interpret=key.interpret)
                else:
                    # a quantized arena's packed (or int8) row gather
                    # runs under its own scope, then its dequant
                    with (jax.named_scope("q_gather") if quant
                          else contextlib.nullcontext()):
                        gathered = flat.at[idx.reshape(-1)] \
                            .get(mode="promise_in_bounds") \
                            .reshape(idx.shape[0], len(emb_cols), -1)
                    if quant:
                        gathered = dequant(
                            gathered, idx.shape[:1] + (len(emb_cols),))
            feats, gi = [], 0
            for i, (rows, e) in enumerate(cfg_.column_encodings):
                if e is None:
                    # no table: the one-hot depends only on the
                    # (replicated) encoded ids, so every shard computes
                    # it identically — no psum term needed. Quantized
                    # groups stream it through the bit-packed uint32
                    # mask form (identical {0, 1} floats), so the fp32
                    # one-hot never materializes as a stored activation
                    if quant:
                        feats.append(lmbf.onehot_feature(
                            enc[..., i], rows, cfg_.dtype))
                    else:
                        feats.append(jax.nn.one_hot(enc[..., i], rows,
                                                    dtype=cfg_.dtype))
                else:               # exact table rows, e_max-padded
                    feats.append(jnp.where(
                        valids[gi][..., None], gathered[:, gi, :e],
                        jnp.asarray(jnp.nan, cfg_.dtype)))
                    gi += 1
            x = jnp.concatenate(feats, axis=-1)
            # hidden stack on TILES: the scheduler guarantees every
            # tile_rows-row tile is single-tenant, so weights come
            # pre-gathered per tile (``tiles``, memoized by the
            # arena) and each tile runs a real (tile, i) @ (i, o)
            # GEMM — bit-equal to the local matmul (row count does
            # not change the k-reduction order; property-tested),
            # and ~10x faster than per-row weight gathers, which
            # turn the dense stack into pure memory traffic. Full fp32
            # precision, as in lmbf.mlp_head: the fixup filter only
            # covers the scores the fit computed
            for li in range(len(cfg_.hidden)):
                w = tiles[f"w{li}"]                 # (g, prev, width)
                b = tiles[f"b{li}"]                 # (g, width)
                x = x.reshape(-1, tile, x.shape[-1])
                x = jax.nn.relu(
                    jnp.einsum("gti,gio->gto", x, w,
                               precision=jax.lax.Precision.HIGHEST)
                    + b[:, None, :])
                x = x.reshape(-1, x.shape[-1])
            # output layer: the same multiply+reduce as
            # lmbf.mlp_head. The weight row is gathered per TILE
            # and broadcast to rows — each row still multiplies its
            # own tenant's w_out and the (n, prev) -> (n,) reduce is
            # unchanged, so this stays bit-identical while gathering
            # 1/tile_rows as many weight rows
            w_out = jnp.repeat(tiles["w_out"], tile, axis=0)  # (n, prev)
            b_out = jnp.repeat(tiles["b_out"], tile, axis=0)  # (n,)
            return jax.nn.sigmoid(
                jnp.sum(x * w_out, axis=-1) + b_out)

        def probe_fn(bits_, ids):
            mb_rows = jnp.take(m_bits_vec, tenant_idx)
            base_rows = jnp.take(base_vec, tenant_idx)
            if sharded:
                # word-sharded concatenated bitsets: rebase each row's
                # word base into this shard's slice, count the misses
                # the slice owns, combine with ONE psum
                wl = bits_.shape[0]
                off = (jax.lax.axis_index(axis) * wl).astype(jnp.int32)
                miss = slice_miss(bits_, ids, mb_rows, base_rows, off)
                return jax.lax.psum(miss, axis) == 0
            return whole_probe(bits_, ids, mb_rows, base_rows)

        tau_rows = jnp.take(tau_vec, tenant_idx)
        return existence.query_stages(params, cfg, tau_rows, bits,
                                      None, raw_ids,
                                      probe_fn=probe_fn,
                                      predict_fn=predict_fn)

    if not sharded:
        return jax.jit(fused_body), gather_tiles

    if quant:
        # int8 combined matrix row-sharded; flat scale vector + int8
        # dense stacks (and their channel scales) replicated
        param_specs = {"dense": P(), "dense_scale": P(),
                       "embed_flat": P(axis, None), "embed_scale": P()}
    else:
        param_specs = {"dense": P(), "embed_flat": P(axis, None)}
    in_specs = (param_specs,                                  # params
                P(),                                          # tiles
                P(axis),                                      # bits
                P(), P(), P(), P(), P())
    fused = _shard_wrap(mesh, fused_body, in_specs, (P(), P(), P()),
                        check_vma=key.probe != PROBE_KERNEL)
    return fused, gather_tiles


# ================================================================= facades

class LocalExecutor(Executor):
    """Facade: grouping OFF x local placement (the pre-planner fused
    path, behavior-preserving)."""

    def __init__(self, plan: QueryPlan):
        if plan.placement.sharded:
            raise ValueError("LocalExecutor needs a local placement")
        self.plan = plan
        self.fn = _tenant_program(plan, None)

    def place(self, index: existence.ExistenceIndex) -> PlacedFilter:
        return _place_local(self.plan, index)


class ShardedExecutor(Executor):
    """Facade: grouping OFF x sharded placement (tables + bitset split
    over one mesh axis)."""

    def __init__(self, plan: QueryPlan, mesh: Mesh):
        if not plan.placement.sharded:
            raise ValueError("ShardedExecutor needs a sharded placement")
        if mesh.shape.get(plan.placement.axis, 1) != plan.placement.n_shards:
            raise ValueError(
                f"mesh axis {plan.placement.axis!r} has size "
                f"{mesh.shape.get(plan.placement.axis)} but the plan "
                f"expects {plan.placement.n_shards} shards")
        self.plan = plan
        self.mesh = mesh
        self.fn = _tenant_program(plan, mesh)

    def place(self, index: existence.ExistenceIndex) -> PlacedFilter:
        return _place_sharded(self.plan, self.mesh, index)


class GroupedExecutor:
    """Facade: grouping ON x either placement — one compiled megabatch
    program for a whole plan group (see :func:`_grouped_program` for
    the signature and the sharded composition).

    Contract: the row count is a multiple of ``key.tile_rows`` and
    ``tenant_idx`` is constant within every tile (the scheduler aligns
    tenant regions to tiles; ``PlanGroupArena.run`` pads stragglers) —
    that is what lets the hidden-layer weight gather happen per tile.

    The per-tile hidden-layer weight gather is split out as
    :attr:`gather_tiles` so the arena can MEMOIZE it on the batch's
    tile signature: XLA's CPU gather costs as much as the GEMM it
    feeds, and in the steady state consecutive megabatches carry the
    same tenant layout, so the gather amortizes to ~zero and the
    grouped dispatch runs at plain-local-GEMM speed.
    """

    def __init__(self, key: GroupKey, mesh: Optional[Mesh] = None):
        if key.placement.sharded:
            if mesh is None:
                raise ValueError("sharded group key needs a mesh")
            if mesh.shape.get(key.placement.axis, 1) \
                    != key.placement.n_shards:
                raise ValueError(
                    f"mesh axis {key.placement.axis!r} has size "
                    f"{mesh.shape.get(key.placement.axis)} but the "
                    f"group key expects {key.placement.n_shards} shards")
            self.mesh: Optional[Mesh] = mesh
        else:
            self.mesh = None
        self.key = key
        self.fn, self.gather_tiles = _grouped_program(key, self.mesh)

    def call(self, *operands):
        """Dispatch the megabatch program through compile telemetry
        (``operands`` = the :func:`_grouped_program` signature; the last
        one is ``raw_ids``, whose leading dim is the bucket)."""
        out, _ = _timed_call(self, self.key.describe(),
                             operands[-1].shape[0], *operands)
        return out

    def program_count(self) -> int:
        """Live jit-cache entries ((arena-shape x bucket) programs)."""
        return self.fn._cache_size()


# --------------------------------------------------------------- registry
# of compiled executors: (plan, mesh-or-None) -> Executor. Local plans
# key on (plan, None) so every registry/server in the process shares
# compiled programs, exactly like the old fused-fn _CACHE. Tenants
# REF-COUNT their key (acquire on register, release on evict), so one
# registry evicting its last tenant on a plan cannot invalidate the
# shared cache entry while another registry still serves that plan.

_EXECUTORS: Dict[Tuple[QueryPlan, Optional[Mesh]], Executor] = {}
_REFS: Dict[Tuple[QueryPlan, Optional[Mesh]], int] = {}


def _key(plan: QueryPlan, mesh: Optional[Mesh]):
    return (plan, mesh if plan.placement.sharded else None)


def executor_for(plan: QueryPlan, mesh: Optional[Mesh] = None) -> Executor:
    """Build-or-fetch the executor for a plan (cached, no ref taken)."""
    global _CACHE_HITS, _CACHE_MISSES
    key = _key(plan, mesh)
    ex = _EXECUTORS.get(key)
    if ex is None:
        _CACHE_MISSES += 1
        if plan.placement.sharded:
            if mesh is None:
                raise ValueError("sharded plan needs a mesh")
            ex = ShardedExecutor(plan, mesh)
        else:
            ex = LocalExecutor(plan)
        _EXECUTORS[key] = ex
    else:
        _CACHE_HITS += 1
    return ex


def acquire_executor(plan: QueryPlan,
                     mesh: Optional[Mesh] = None) -> Executor:
    """:func:`executor_for` + take one reference on the cache entry."""
    ex = executor_for(plan, mesh)
    key = _key(plan, mesh)
    _REFS[key] = _REFS.get(key, 0) + 1
    return ex


def release_executor(plan: QueryPlan,
                     mesh: Optional[Mesh] = None) -> bool:
    """Drop one reference; on the last one, forget the cached executor
    (and its compiled programs). Live objects holding the executor keep
    working — only the cache forgets it. Returns True when dropped."""
    key = _key(plan, mesh)
    n = _REFS.get(key, 0) - 1
    if n > 0:
        _REFS[key] = n
        return False
    _REFS.pop(key, None)
    return _EXECUTORS.pop(key, None) is not None


def release_plan(plan: QueryPlan) -> int:
    """Force-drop cached executors for a plan regardless of references
    (tests / explicit cache hygiene). Returns the number released."""
    victims = [k for k in _EXECUTORS if k[0] == plan]
    for k in victims:
        del _EXECUTORS[k]
        _REFS.pop(k, None)
    return len(victims)


# Grouped executors key on (GroupKey, mesh-or-None) — local group keys
# on (key, None), mirroring the per-plan cache — and ref-count the same
# way: each live arena holds ONE reference, released when its last
# tenant leaves.

_GROUPED: Dict[Tuple[GroupKey, Optional[Mesh]], GroupedExecutor] = {}
_GREFS: Dict[Tuple[GroupKey, Optional[Mesh]], int] = {}


def _gkey(key: GroupKey, mesh: Optional[Mesh]):
    return (key, mesh if key.placement.sharded else None)


def grouped_executor_for(key: GroupKey,
                         mesh: Optional[Mesh] = None) -> GroupedExecutor:
    """Build-or-fetch the megabatch executor for a plan group (cached,
    no ref taken)."""
    global _CACHE_HITS, _CACHE_MISSES
    k = _gkey(key, mesh)
    ex = _GROUPED.get(k)
    if ex is None:
        _CACHE_MISSES += 1
        ex = _GROUPED[k] = GroupedExecutor(key, mesh)
    else:
        _CACHE_HITS += 1
    return ex


def acquire_grouped_executor(key: GroupKey,
                             mesh: Optional[Mesh] = None
                             ) -> GroupedExecutor:
    """:func:`grouped_executor_for` + take one reference."""
    ex = grouped_executor_for(key, mesh)
    k = _gkey(key, mesh)
    _GREFS[k] = _GREFS.get(k, 0) + 1
    return ex


def release_grouped_executor(key: GroupKey,
                             mesh: Optional[Mesh] = None) -> bool:
    """Drop one reference; the last one forgets the cached executor
    (and its compiled programs). Returns True when dropped."""
    k = _gkey(key, mesh)
    n = _GREFS.get(k, 0) - 1
    if n > 0:
        _GREFS[k] = n
        return False
    _GREFS.pop(k, None)
    return _GROUPED.pop(k, None) is not None


def compiled_program_count() -> int:
    """Live (plan-shape x bucket) XLA programs across cached executors,
    per-tenant and grouped."""
    return (sum(ex.program_count() for ex in _EXECUTORS.values())
            + sum(ex.program_count() for ex in _GROUPED.values()))


def clear_executors() -> None:
    """Drop every cached executor (tests / tenant-churn hygiene)."""
    _EXECUTORS.clear()
    _REFS.clear()
    _GROUPED.clear()
    _GREFS.clear()
