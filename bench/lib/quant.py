"""The benchmark's own quantizer, for configurations served from
quantized arenas (``serving.dtype`` ``"int8"`` or ``"int4-nf4"``).

A copy of the published scheme, written from its description and not
imported from the program:

* embedding tables: one float32 scale per ``row_group`` rows (the
  group's absolute maximum over all its values, over the grid's largest
  level), dense weights: one per output channel (over the input axis);
  biases stay float32; a group or channel that is all zero gets scale 1;
* ``int8``: ``value = code * scale``, codes in -127..127;
* ``int4-nf4``: ``value = NF4[code] * scale`` with the 16 levels below,
  codes 0..15 packed two to a byte, the even position in the low
  nibble: embedding tables along the feature axis, dense weights along
  the input axis, an odd length padded with code 0 that no row reads.

The program receives these codes, scales and ``tau_q`` in
``existence_index_v3`` checkpoints (``program.save_filter``) and
dequantizes them itself, so a disagreement in layout, code book or
scale shows as mismatched rows. The reference dequantizes the same
codes in float32, as the program does (``value * scale``), and runs
its float64 forward pass on the result (``filters.logits64``).

``tau_q`` follows the published calibration: the served threshold is
lowered, as a logit, by ``margin_safety`` times the largest gap between
the float32 and the quantized model's logits over ``calib_samples``
draws from the relation's encoded-id domain, plus ``margin_floor``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

from bench.lib import filters

# The NF4 code book: the 16 normal-float levels of QLoRA (Dettmers et
# al. 2023, arXiv:2305.14314), quantiles of N(0, 1) scaled to [-1, 1]
# with an exact zero at code 7.
NF4 = np.array(
    [-1.0, -0.6961928009986877, -0.5250730514526367,
     -0.39491748809814453, -0.28444138169288635, -0.18477343022823334,
     -0.09105003625154495, 0.0, 0.07958029955625534, 0.15955357253551483,
     0.2461123913526535, 0.33791524171829224, 0.44070982933044434,
     0.5626170039176941, 0.7229568362236023, 1.0], np.float32)

# serving.dtype -> (bits, 4-bit grid) as the program's QuantConfig names them
MODES = {"int8": (8, "linear"), "int4-nf4": (4, "nf4")}
QUANT_KEYS = ("row_group", "calib_samples", "margin_safety", "margin_floor")


def mode(config: Dict) -> Optional[Dict]:
    """The quantization a configuration serves under, as the dict that
    ``existence_index_v3`` checkpoints carry, or None for float32."""
    serving = config["serving"]
    dtype = serving["dtype"]
    if dtype == "float32":
        return None
    if dtype not in MODES:
        raise ValueError(f"serving.dtype {dtype!r} is none of float32, "
                         f"{', '.join(MODES)}")
    q = serving.get("quant", {})
    missing = [k for k in QUANT_KEYS if k not in q]
    if missing:
        raise ValueError(f"a {dtype} configuration states serving.quant "
                         f"{', '.join(QUANT_KEYS)}; missing {missing}")
    bits, grid = MODES[dtype]
    return {"bits": bits, "grid": grid, "row_group": int(q["row_group"]),
            "calib_samples": int(q["calib_samples"]),
            "margin_safety": float(q["margin_safety"]),
            "margin_floor": float(q["margin_floor"])}


def _codes(x: np.ndarray, qm: Dict) -> np.ndarray:
    """Values already divided by their scale -> codes: int8, or the
    nearest NF4 level's index."""
    if qm["bits"] == 8:
        return np.clip(np.rint(x), -127, 127).astype(np.int8)
    x = np.clip(x, -1.0, 1.0)
    return np.abs(x[..., None] - NF4).argmin(-1).astype(np.uint8)


def pack(codes: np.ndarray, axis: int) -> np.ndarray:
    """Two 4-bit codes a byte along ``axis``: the even position in the
    low nibble, the odd one in the high; an odd length pads code 0."""
    codes = np.moveaxis(np.asarray(codes, np.uint8), axis, 0)
    if len(codes) % 2:
        codes = np.concatenate([codes, np.zeros_like(codes[:1])])
    return np.moveaxis(codes[0::2] | (codes[1::2] << 4), 0, axis)


def unpack(packed: np.ndarray, axis: int, n: int) -> np.ndarray:
    """The first ``n`` codes along ``axis`` of :func:`pack`'s bytes."""
    p = np.moveaxis(np.asarray(packed, np.uint8), axis, 0)
    codes = np.empty((2 * len(p),) + p.shape[1:], np.uint8)
    codes[0::2], codes[1::2] = p & 0xF, p >> 4
    return np.moveaxis(codes[:n], 0, axis)


def _scale(absmax: np.ndarray, qm: Dict) -> np.ndarray:
    """Absolute maximum -> scale: it maps to 127 (int8) or to NF4's 1."""
    top = 127.0 if qm["bits"] == 8 else 1.0
    return np.where(absmax > 0, absmax / top, 1.0).astype(np.float32)


def quantize(params: Dict, qm: Dict) -> Dict:
    """Float32 params -> ``{"embed", "embed_scale", "dense",
    "dense_scale"}``, the tree an ``existence_index_v3`` checkpoint
    holds."""
    rg = qm["row_group"]
    out = {"embed": {}, "embed_scale": {}, "dense": {}, "dense_scale": {}}
    for name, t in params["embed"].items():
        t = np.asarray(t, np.float32)
        rows = len(t)
        groups = -(-rows // rg)
        padded = np.zeros((groups * rg, t.shape[1]), np.float32)
        padded[:rows] = np.abs(t)
        scale = _scale(padded.reshape(groups, -1).max(axis=1), qm)
        codes = _codes(t / np.repeat(scale, rg)[:rows, None], qm)
        out["embed"][name] = codes if qm["bits"] == 8 else pack(codes, 1)
        out["embed_scale"][name] = scale
    for name, w in params["dense"].items():
        w = np.asarray(w, np.float32)
        if name.startswith("b"):
            out["dense"][name] = w
            continue
        scale = _scale(np.abs(w).max(axis=0), qm)
        codes = _codes(w / scale, qm)
        out["dense"][name] = codes if qm["bits"] == 8 else pack(codes, 0)
        out["dense_scale"][name] = scale
    return out


def dequantize(qparams: Dict, shapes: Dict, qm: Dict) -> Dict:
    """The served weights in float32, ``level * scale`` as the program
    computes them, in the shapes of ``filters.param_shapes``."""
    rg = qm["row_group"]

    def values(codes, axis, n):
        if qm["bits"] == 8:
            return np.asarray(codes).astype(np.float32)
        return NF4[unpack(codes, axis, n)]

    out = {"embed": {}, "dense": {}}
    for name, (rows, e) in shapes["embed"].items():
        per_row = np.repeat(qparams["embed_scale"][name], rg)[:rows]
        out["embed"][name] = values(qparams["embed"][name], 1, e) \
            * per_row[:, None]
    for name, shape in shapes["dense"].items():
        w = qparams["dense"][name]
        if name.startswith("b"):
            out["dense"][name] = np.asarray(w, np.float32)
        else:
            out["dense"][name] = values(w, 0, shape[0]) \
                * qparams["dense_scale"][name]
    return out


def tau_q(params: Dict, served: Dict, cols: List[filters.Column],
          qm: Dict, tau: float, seed: int) -> float:
    """The served threshold (a probability): ``tau`` lowered, as a
    logit, by ``margin_safety`` x the largest float32-to-quantized
    logit gap over ``calib_samples`` seeded draws of encoded ids, plus
    ``margin_floor``."""
    rng = np.random.default_rng(seed)
    enc = np.stack([rng.integers(0, r, qm["calib_samples"])
                    for r in filters.table_rows(cols)], axis=-1)
    gap = float(np.abs(filters.logits64_encoded(params, enc)
                       - filters.logits64_encoded(served, enc)).max())
    margin = qm["margin_safety"] * gap + qm["margin_floor"]
    return 1.0 / (1.0 + math.exp(-(math.log(tau / (1.0 - tau)) - margin)))


def threshold(tau_served: float) -> float:
    """The logit the program's float32 comparison ``sigmoid(z) >=
    float32(tau_q)`` thresholds at."""
    t = float(np.float32(tau_served))
    return math.log(t / (1.0 - t))
