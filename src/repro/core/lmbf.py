"""Learned (multidimensional) Bloom filter models: LMBF and C-LMBF.

Architecture (Macke et al. [9], as used by the paper): per-(sub)column
embedding -> concat -> dense hidden layer(s) (ReLU) -> sigmoid logit.

* LMBF   = plan with no compression (theta = inf).
* C-LMBF = plan from ``repro.core.compression`` (theta, ns); inputs are the
  losslessly-compressed subcolumn ids; subcolumn tables carry a ``+1``
  wildcard row.

Embedding dims follow ``floor(rows ** 0.25)`` (min 1), which reproduces the
paper's Table 1 "NN params" column exactly for the airplane dataset (all
four rows) and within 0.1% for DMV — see core/memory.py.

Columns whose table has at most ``onehot_max`` rows may use one-hot encoding
instead of an embedding matrix (§3.2 "we also allow a one-hot encoding").
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression as comp
from repro.nn import ParamSpec, abstract_params, axes_tree, build_params
from repro.nn import layers as L


def embed_dim_for(rows: int) -> int:
    """The paper's (reverse-engineered) embedding-size heuristic."""
    return max(1, int(math.floor(rows ** 0.25)))


@dataclasses.dataclass(frozen=True)
class LMBFConfig:
    plan: comp.CompressionPlan
    hidden: Tuple[int, ...] = (64,)      # paper Table 1: one layer of 64
    onehot_max: int = 0                  # 0 disables the one-hot path
    dtype: object = jnp.float32

    def __post_init__(self):
        # canonicalize so configs built from a checkpoint (np.dtype) and
        # from code (jnp.float32 scalar type) hash identically — the
        # serving fused-path cache keys on this config
        object.__setattr__(self, "dtype", jnp.dtype(self.dtype))

    @property
    def column_encodings(self):
        """[(rows, embed_dim_or_None)] per subcolumn; None = one-hot."""
        out = []
        for rows in self.plan.table_rows:
            if rows <= self.onehot_max:
                out.append((rows, None))
            else:
                out.append((rows, embed_dim_for(rows)))
        return out

    @property
    def concat_dim(self) -> int:
        return sum(e if e is not None else r
                   for r, e in self.column_encodings)


def params_spec(cfg: LMBFConfig):
    spec = {"embed": {}, "dense": {}}
    for i, (rows, e) in enumerate(cfg.column_encodings):
        if e is not None:
            spec["embed"][f"col{i}"] = ParamSpec(
                (rows, e), cfg.dtype, init="embedding",
                axes=("vocab", "embed"), init_scale=0.05)
    prev = cfg.concat_dim
    for li, width in enumerate(cfg.hidden):
        spec["dense"][f"w{li}"] = ParamSpec(
            (prev, width), cfg.dtype, init="scaled_normal",
            axes=("embed", "mlp"))
        spec["dense"][f"b{li}"] = ParamSpec((width,), cfg.dtype, init="zeros",
                                            axes=(None,))
        prev = width
    spec["dense"]["w_out"] = ParamSpec((prev, 1), cfg.dtype,
                                       init="scaled_normal",
                                       axes=("embed", None))
    spec["dense"]["b_out"] = ParamSpec((1,), cfg.dtype, init="zeros",
                                       axes=(None,))
    return spec


def init(cfg: LMBFConfig, key: jax.Array):
    return build_params(params_spec(cfg), key)


def features(params, cfg: LMBFConfig, encoded_ids) -> jax.Array:
    """encoded_ids: (..., n_subcolumns) int32 -> (..., concat_dim) input
    features (per-subcolumn embedding gathers / one-hots, concatenated)."""
    feats = []
    for i, (rows, e) in enumerate(cfg.column_encodings):
        ids = encoded_ids[..., i]
        if e is None:
            feats.append(jax.nn.one_hot(ids, rows, dtype=cfg.dtype))
        else:
            feats.append(L.take_embedding(params["embed"][f"col{i}"], ids))
    return jnp.concatenate(feats, axis=-1)


def mlp_head(params, cfg: LMBFConfig, x) -> jax.Array:
    """(..., concat_dim) features -> (...,) logits (hidden ReLU stack).

    The output layer is a broadcast multiply + last-axis reduce rather
    than ``x @ w_out``: a (prev, 1) GEMV has its own accumulation order
    that no per-row batched form reproduces, while multiply+reduce
    lowers identically whether the weight row is shared (here) or
    gathered per row (the serving ``GroupedExecutor`` stacks many
    tenants' heads and indexes them with a per-row tenant id) — so
    grouped serving stays bit-identical to this reference.

    The hidden GEMMs run at ``Precision.HIGHEST``: at default precision a
    TPU multiplies fp32 operands in one bf16 pass, so a served score
    could differ from the fit-time score the fixup filter was built from
    and a member just above ``tau`` could fall below it (a false
    negative). On CPU this changes nothing.
    """
    for li in range(len(cfg.hidden)):
        x = jax.nn.relu(
            jnp.matmul(x, params["dense"][f"w{li}"],
                       precision=jax.lax.Precision.HIGHEST)
            + params["dense"][f"b{li}"])
    return (jnp.sum(x * params["dense"]["w_out"][:, 0], axis=-1)
            + params["dense"]["b_out"][0])


def apply(params, cfg: LMBFConfig, encoded_ids) -> jax.Array:
    """encoded_ids: (..., n_subcolumns) int32 -> (...,) logits."""
    return mlp_head(params, cfg, features(params, cfg, encoded_ids))


def predict(params, cfg: LMBFConfig, encoded_ids) -> jax.Array:
    return jax.nn.sigmoid(apply(params, cfg, encoded_ids))


def bce_loss(params, cfg: LMBFConfig, encoded_ids, labels) -> jax.Array:
    """Binary cross-entropy with logits; labels float in {0, 1}."""
    logits = apply(params, cfg, encoded_ids)
    z = logits.astype(jnp.float32)
    y = labels.astype(jnp.float32)
    # numerically-stable BCE-with-logits
    loss = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    return jnp.mean(loss)


# ---------------------------------------------------------------------------
# compressed storage (serving "compressed arenas"): int8 and packed int4/NF4
#
# Symmetric absmax quantization: embedding tables carry one fp32 scale per
# ``row_group`` rows, dense weights one fp32 scale per output channel;
# biases stay fp32.  ``bits=8`` stores plain int8; ``bits=4`` stores TWO
# codes per uint8 byte — embedding tables packed along the feature axis
# (row indexing, and therefore row sharding, is unchanged), dense weights
# packed along the input axis — on either a linear grid (value =
# ``(code - 8) * scale``, scale = absmax/7) or the NF4 normal-float grid
# (value = ``NF4_TABLE[code] * scale``, scale = absmax).  Every consumer —
# the reference ``apply_q`` here, the per-tenant jit/shard_map programs,
# the grouped arena program, and the Pallas gather kernels — dequantizes
# with the SAME elementwise unpack-then-``value * scale`` before reusing
# the fp32 math, so quantized scores are bit-identical across placements
# by construction (a psum of masked shards only ever adds exact zeros).
# ---------------------------------------------------------------------------

# the NF4 code book (QLoRA's 16 normal-float levels, zero at code 7):
# quantiles of N(0, 1) rescaled to [-1, 1], the information-theoretically
# better grid for the roughly-normal weight distributions an init like
# scaled_normal produces
NF4_TABLE = np.array(
    [-1.0, -0.6961928009986877, -0.5250730514526367,
     -0.39491748809814453, -0.28444138169288635, -0.18477343022823334,
     -0.09105003625154495, 0.0, 0.07958029955625534, 0.15955357253551483,
     0.2461123913526535, 0.33791524171829224, 0.44070982933044434,
     0.5626170039176941, 0.7229568362236023, 1.0], np.float32)

QUANT_BITS = (8, 4)
QUANT_GRIDS = ("linear", "nf4")


def nibble_lut(grid: str, dtype=np.float32) -> np.ndarray:
    """The 16-entry code -> unit-value table for a 4-bit grid: linear
    codes decode to ``code - 8`` (so 8 is exact zero), NF4 codes to the
    normal-float levels. Integer values -8..7 are exact in f32, so LUT
    lookup and ``(code - 8)`` arithmetic produce bit-identical floats —
    the Pallas kernels use the LUT form for both grids."""
    if grid == "nf4":
        return NF4_TABLE.astype(dtype)
    return (np.arange(16, dtype=np.float32) - 8.0).astype(dtype)


def pack_nibbles(u: np.ndarray, axis: int) -> np.ndarray:
    """Host-side: uint8 codes in [0, 16) -> two-per-byte packed uint8
    along ``axis`` (odd lengths zero-pad; even positions land in the low
    nibble, odd in the high — the layout :func:`unpack_nibbles` inverts)."""
    u = np.asarray(u, np.uint8)
    axis = axis % u.ndim
    if u.shape[axis] % 2:
        pad = [(0, 0)] * u.ndim
        pad[axis] = (0, 1)
        u = np.pad(u, pad)
    lo = np.take(u, np.arange(0, u.shape[axis], 2), axis=axis)
    hi = np.take(u, np.arange(1, u.shape[axis], 2), axis=axis)
    return (lo | (hi << 4)).astype(np.uint8)


def unpack_nibbles(p, axis: int):
    """In-program inverse of :func:`pack_nibbles`: packed uint8 ->
    interleaved uint8 codes, doubling ``axis`` (includes any pad code)."""
    axis = axis % p.ndim
    lo = p & jnp.uint8(0xF)
    hi = p >> jnp.uint8(4)
    st = jnp.stack([lo, hi], axis=axis + 1)
    shape = list(p.shape)
    shape[axis] *= 2
    return st.reshape(shape)


def nibble_values(codes, grid: str, dtype):
    """uint8 codes in [0, 16) -> unit grid values in ``dtype``."""
    if grid == "nf4":
        return jnp.take(jnp.asarray(NF4_TABLE, dtype),
                        codes.astype(jnp.int32))
    return codes.astype(dtype) - jnp.asarray(8, dtype)


def packed_dim(n: int) -> int:
    """Bytes needed to hold ``n`` nibble codes (two per byte)."""
    return -(-n // 2)


def dense_in_dims(cfg: LMBFConfig) -> dict:
    """Input (axis-0) dim of each dense weight — what a packed stack
    must be unpacked back to."""
    dims, prev = {}, cfg.concat_dim
    for li, width in enumerate(cfg.hidden):
        dims[f"w{li}"] = prev
        prev = width
    dims["w_out"] = prev
    return dims


def _encode_grid(t: np.ndarray, scale_bcast: np.ndarray,
                 grid: str) -> np.ndarray:
    """fp32 values + broadcastable per-element scale -> uint8 codes."""
    if grid == "nf4":
        x = np.clip(t / scale_bcast, -1.0, 1.0).astype(np.float32)
        return np.abs(x[..., None] - NF4_TABLE).argmin(-1).astype(np.uint8)
    return (np.clip(np.rint(t / scale_bcast), -7, 7) + 8).astype(np.uint8)


def quantize_params(params, cfg: LMBFConfig, row_group: int = 32,
                    bits: int = 8, grid: str = "linear"):
    """fp32 param tree -> quantized qparams tree (host numpy arrays).

    ``bits=8``: ``{"embed": {col_i: int8 (rows, e)},
    "embed_scale": {col_i: f32 (ceil(rows / row_group),)},
    "dense": {w*: int8, b*: f32}, "dense_scale": {w*: f32 (out_ch,)}}``.
    ``bits=4``: same tree with embedding tables packed along the feature
    axis — uint8 ``(rows, ceil(e / 2))`` — and dense weights packed along
    the input axis — uint8 ``(ceil(in, 2), out)`` — on the requested grid.
    Zero rows/channels get scale 1.0 so dequant never divides by zero.
    """
    if bits not in QUANT_BITS:
        raise ValueError(f"bits must be one of {QUANT_BITS}, got {bits}")
    if grid not in QUANT_GRIDS:
        raise ValueError(f"grid must be one of {QUANT_GRIDS}, got {grid!r}")
    qmax = 127.0 if bits == 8 else (7.0 if grid == "linear" else 1.0)
    qp = {"embed": {}, "embed_scale": {}, "dense": {}, "dense_scale": {}}
    for i, (rows, e) in enumerate(cfg.column_encodings):
        if e is None:
            continue
        t = np.asarray(params["embed"][f"col{i}"], np.float32)
        ng = -(-rows // row_group)
        pad = ng * row_group - rows
        absmax = np.abs(np.pad(t, ((0, pad), (0, 0)))) \
            .reshape(ng, row_group, -1).max(axis=(1, 2))
        scale = np.where(absmax > 0, absmax / qmax, 1.0).astype(np.float32)
        per_row = np.repeat(scale, row_group)[:rows]
        if bits == 8:
            qp["embed"][f"col{i}"] = np.clip(
                np.rint(t / per_row[:, None]), -127, 127).astype(np.int8)
        else:
            codes = _encode_grid(t, per_row[:, None], grid)
            qp["embed"][f"col{i}"] = pack_nibbles(codes, axis=-1)
        qp["embed_scale"][f"col{i}"] = scale
    for name, w in params["dense"].items():
        w = np.asarray(w, np.float32)
        if name.startswith("b"):
            qp["dense"][name] = w
            continue
        absmax = np.abs(w).max(axis=0)
        scale = np.where(absmax > 0, absmax / qmax, 1.0).astype(np.float32)
        if bits == 8:
            qp["dense"][name] = np.clip(
                np.rint(w / scale), -127, 127).astype(np.int8)
        else:
            qp["dense"][name] = pack_nibbles(
                _encode_grid(w, scale, grid), axis=0)
        qp["dense_scale"][name] = scale
    return qp


def q_gather(q, scale, ids, rows: int, row_group: int, dtype,
             bits: int = 8, grid: str = "linear",
             out_dim: Optional[int] = None):
    """Fused quantized row gather + per-row-group dequant, any bit width.

    Mirrors ``jnp.take``'s embedding semantics exactly — negative ids
    wrap pythonically, out-of-bounds rows become NaN — so quantized
    features degrade identically to the fp32 gather on bad ids.  For
    ``bits=4`` the table rows are packed nibbles: they are unpacked (and,
    when ``out_dim`` is given, sliced back to the true feature width)
    after the gather, so only packed bytes move through the gather.
    """
    wrapped = jnp.where(ids < 0, ids + rows, ids)
    valid = (wrapped >= 0) & (wrapped < rows)
    safe = jnp.clip(wrapped, 0, rows - 1)
    g = jnp.take(q, safe, axis=0)
    if bits == 4:
        g = nibble_values(unpack_nibbles(g, axis=-1), grid, dtype)
        if out_dim is not None:
            g = g[..., :out_dim]
    else:
        g = g.astype(dtype)
    g = g * jnp.take(scale, safe // row_group)[..., None].astype(dtype)
    return jnp.where(valid[..., None], g, jnp.asarray(jnp.nan, dtype))


def q8_gather(q, scale, ids, rows: int, row_group: int, dtype):
    """Back-compat alias: the int8 flavor of :func:`q_gather`."""
    return q_gather(q, scale, ids, rows, row_group, dtype, bits=8)


def pack_onehot_ids(ids, rows: int):
    """Encoded id column -> bit-packed one-hot: ``(..., ceil(rows/32))``
    uint32 words where bit ``id % 32`` of word ``id // 32`` is set iff
    ``0 <= id < rows`` (out-of-range ids — including negatives — pack to
    all-zero words, matching ``jax.nn.one_hot``'s all-zero rows)."""
    nw = -(-rows // 32)
    ids = ids.astype(jnp.int32)
    valid = (ids >= 0) & (ids < rows)
    word = jnp.where(valid, ids // 32, -1)
    bit = jnp.where(valid, ids % 32, 0).astype(jnp.uint32)
    hit = word[..., None] == jnp.arange(nw, dtype=jnp.int32)
    return jnp.where(hit, jnp.uint32(1) << bit[..., None], jnp.uint32(0))


def expand_onehot_mask(words, rows: int, dtype):
    """Inverse of :func:`pack_onehot_ids`: ``(..., nw)`` uint32 ->
    ``(..., rows)`` exact {0, 1} activations in ``dtype`` — bit-identical
    to ``jax.nn.one_hot`` on every input, so swapping the packed form
    into a quantized program never changes an answer."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    out = bits.reshape(words.shape[:-1] + (words.shape[-1] * 32,))
    return out[..., :rows].astype(dtype)


def onehot_feature(ids, rows: int, dtype):
    """The quantized paths' one-hot: pack to uint32 mask words, expand
    via bit tests inside the program — the fp32 one-hot row never
    materializes as a stored activation, only as the first layer's
    streamed input."""
    return expand_onehot_mask(pack_onehot_ids(ids, rows), rows, dtype)


def dequantize_dense(qparams, dtype, cfg: Optional[LMBFConfig] = None,
                     bits: int = 8, grid: str = "linear"):
    """Quantized dense stack -> fp32 dict for :func:`mlp_head` (biases
    pass through; weights are elementwise ``value * per_channel_scale``,
    nibble-unpacked along the input axis first when ``bits=4``)."""
    dims = dense_in_dims(cfg) if bits == 4 else None
    dense = {}
    for name, w in qparams["dense"].items():
        if name.startswith("b"):
            dense[name] = jnp.asarray(w, dtype)
        elif bits == 4:
            codes = unpack_nibbles(jnp.asarray(w), axis=0)[:dims[name]]
            dense[name] = (nibble_values(codes, grid, dtype)
                           * jnp.asarray(qparams["dense_scale"][name], dtype))
        else:
            dense[name] = (jnp.asarray(w).astype(dtype)
                           * jnp.asarray(qparams["dense_scale"][name], dtype))
    return dense


def apply_q(qparams, cfg: LMBFConfig, encoded_ids, row_group: int = 32,
            bits: int = 8, grid: str = "linear") -> jax.Array:
    """Quantized-reference logits: fused gather→dequant features into the
    standard :func:`mlp_head` on dequantized dense weights. One-hot
    columns go through the bit-packed mask form (:func:`onehot_feature`)."""
    feats = []
    for i, (rows, e) in enumerate(cfg.column_encodings):
        ids = encoded_ids[..., i]
        if e is None:
            feats.append(onehot_feature(ids, rows, cfg.dtype))
        else:
            feats.append(q_gather(
                jnp.asarray(qparams["embed"][f"col{i}"]),
                jnp.asarray(qparams["embed_scale"][f"col{i}"]),
                ids, rows, row_group, cfg.dtype,
                bits=bits, grid=grid, out_dim=e))
    x = jnp.concatenate(feats, axis=-1)
    return mlp_head({"dense": dequantize_dense(qparams, cfg.dtype, cfg,
                                               bits=bits, grid=grid)},
                    cfg, x)


def predict_q(qparams, cfg: LMBFConfig, encoded_ids, row_group: int = 32,
              bits: int = 8, grid: str = "linear") -> jax.Array:
    return jax.nn.sigmoid(apply_q(qparams, cfg, encoded_ids, row_group,
                                  bits=bits, grid=grid))


# Calibration-draw memo (serving satellite): hydrating a quantized plan
# from an fp32 checkpoint re-runs calibrated_tau on every reload, and the
# deterministic sample draws — a pure function of (table rows, n_samples,
# seed) — were regenerated every time. Plans sharing a shape share one
# cached draw matrix; bounded FIFO so long-lived fleets cannot grow it.
_CALIB_DRAWS: dict = {}
_CALIB_DRAWS_MAX = 64
# cumulative calibration telemetry: the bench's reload_calibration_ms
# column reads deltas of this across its churn window (a v3-checkpoint
# hydration skips calibration entirely, which is the point)
_CALIB_STATS = {"count": 0, "seconds": 0.0, "draw_hits": 0}


def calibration_draws(cfg: LMBFConfig, n_samples: int,
                      seed: int = 0) -> np.ndarray:
    """Deterministic ``(n_samples, n_subcolumns)`` int32 calibration
    probes from the plan's encoded domain, memoized per
    (table rows, n_samples, seed) across reloads."""
    key = (tuple(r for r, _e in cfg.column_encodings),
           int(n_samples), int(seed))
    enc = _CALIB_DRAWS.get(key)
    if enc is None:
        rng = np.random.default_rng(seed)
        cols = [rng.integers(0, rows, size=n_samples)
                for rows, _e in cfg.column_encodings]
        enc = np.stack(cols, axis=-1).astype(np.int32)
        if len(_CALIB_DRAWS) >= _CALIB_DRAWS_MAX:
            _CALIB_DRAWS.pop(next(iter(_CALIB_DRAWS)))
        _CALIB_DRAWS[key] = enc
    else:
        _CALIB_STATS["draw_hits"] += 1
    return enc


def calibration_stats() -> dict:
    """Cumulative (process-global) calibration telemetry: ``count`` runs,
    ``seconds`` wall time, ``draw_hits`` memoized sample reuses."""
    return dict(_CALIB_STATS)


def reset_calibration_stats() -> None:
    _CALIB_STATS.update(count=0, seconds=0.0, draw_hits=0)


def calibrated_tau(params, qparams, cfg: LMBFConfig, tau: float, *,
                   row_group: int = 32, n_samples: int = 512,
                   safety: float = 2.0, floor: float = 1e-3,
                   seed: int = 0, bits: int = 8,
                   grid: str = "linear") -> float:
    """Serving threshold for a quantized tenant.

    Quantization perturbs logits, so a key the fp32 model accepted at
    ``tau`` could flip below it and — because the fixup filter only
    covers fp32-model FNs from fit time — become a false negative.  We
    close that hole empirically: measure the max |fp32 − quantized|
    logit gap over ``n_samples`` deterministic draws from the tenant's
    own encoded domain, then serve at ``sigmoid(logit(tau) − safety·gap
    − floor)``.  The gap is measured ON THE SERVING GRID — ``bits=4``
    calibrates against the nibble-grid ``apply_q``, whose coarser levels
    produce a proportionally larger margin — so any fp32-accepted key
    stays model-positive under quantization as long as its own gap is
    within the calibrated margin; keys the fp32 model rejected stay
    covered by the bit-exact fixup probe either way.  The same (params,
    seed) always yields the same threshold, so grouped, ungrouped, and
    sharded placements of one tenant agree exactly.
    """
    t0 = time.perf_counter()
    enc = jnp.asarray(calibration_draws(cfg, n_samples, seed))
    z = apply(params, cfg, enc)
    zq = apply_q(qparams, cfg, enc, row_group=row_group, bits=bits,
                 grid=grid)
    gap = float(jnp.max(jnp.abs(z - zq)))
    if not math.isfinite(gap):      # defensive: never serve a NaN threshold
        gap = 0.0
    t = min(max(float(tau), 1e-6), 1.0 - 1e-6)
    margin = safety * gap + floor
    _CALIB_STATS["count"] += 1
    _CALIB_STATS["seconds"] += time.perf_counter() - t0
    return 1.0 / (1.0 + math.exp(-(math.log(t / (1.0 - t)) - margin)))


def count_params(cfg: LMBFConfig) -> int:
    """NN parameter count matching the paper's Table 1 accounting."""
    total = 0
    for rows, e in cfg.column_encodings:
        if e is not None:
            total += rows * e
    prev = cfg.concat_dim
    for width in cfg.hidden:
        total += prev * width + width
        prev = width
    total += prev * 1 + 1
    return total
