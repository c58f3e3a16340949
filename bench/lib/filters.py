"""The tenants' filters, made by the benchmark, and their plain reference.

A tenant's filter is the paper's C-LMBF: each column split into
subcolumns by repeated division (section 3.2), one embedding table per
subcolumn, the embeddings concatenated, a ReLU hidden layer, a sigmoid
score against ``tau``, and a fixup Bloom filter holding every indexed
key the model scores below ``tau``. The filter answers True when the
model or the fixup filter says so, so no indexed key ever answers
False.

Everything here is independent of the code under test: the plan
arithmetic, the training step, the forward pass and the Bloom hash are
written from the paper and from the checkpoint's documented semantics,
and the benchmark makes the weights and the bitset itself. The program
only receives them as checkpoints (``program.save_filter``).

* :func:`train` fits the model in ONE jitted call on the device
  (``lax.scan`` over the Adam steps), from the seed.
* :func:`logits64` is the plain reference: NumPy in float64.
* :func:`build_fixup` inserts every indexed key whose reference logit
  lies below ``+BORDER_LOGIT`` (a margin: a float32 program rounds the
  logit by far less, so it never loses a key to rounding).
* :func:`logits_at` is the control's forward pass on the device, in a
  chosen matmul precision.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.lib.relation import WILDCARD

# |logit| under which a float32 program and the float64 reference may
# legitimately disagree on ``score >= tau`` (tau = 0.5 is logit 0; a
# quantized filter's distance from its served threshold). The
# fixup filter covers indexed keys up to this margin, and the
# correctness check leaves rows within it (and not in the fixup filter)
# out of the exact comparison. See PERF.md for the readings behind it.
BORDER_LOGIT = 1e-4

_C1, _C2, _GOLDEN = 0xCC9E2D51, 0x1B873593, 0x9E3779B9
_M32 = 0xFFFFFFFF
PROBE_SEEDS = (0x0000A5A5, 0x00005EED)


# ------------------------------------------------------------------ plan

@dataclasses.dataclass(frozen=True)
class Column:
    v: int                      # cardinality, wildcard id included
    divisors: Tuple[int, ...]   # low to high; empty when not split
    sub_cards: Tuple[int, ...]  # quotient first

    @property
    def table_rows(self) -> Tuple[int, ...]:
        if not self.divisors:
            return (self.v,)
        return tuple(c + 1 for c in self.sub_cards)   # + wildcard slot


def plan(cards: Sequence[int], theta: int, ns: int) -> List[Column]:
    """Split a column iff ``v > theta``, divisor ``ceil(cur ** (1/k))``
    for the ``k`` subcolumns still to make."""
    out = []
    for v in cards:
        v = int(v)
        if ns < 2 or v <= theta:
            out.append(Column(v, (), ()))
            continue
        divisors, rems, cur = [], [], v
        for remaining in range(ns, 1, -1):
            d = max(int(math.ceil(cur ** (1.0 / remaining))), 2)
            divisors.append(d)
            rems.append(d)
            cur = int(math.ceil(cur / d))
        out.append(Column(v, tuple(divisors), tuple([cur] + rems[::-1])))
    return out


def table_rows(cols: List[Column]) -> List[int]:
    return [r for c in cols for r in c.table_rows]


def embed_dims(cols: List[Column]) -> List[int]:
    """``floor(rows ** 0.25)``, at least 1, per subcolumn table."""
    return [max(1, int(math.floor(r ** 0.25))) for r in table_rows(cols)]


def concat_dim(cols: List[Column]) -> int:
    return sum(embed_dims(cols))


def encode(ids: np.ndarray, cols: List[Column]) -> np.ndarray:
    """(n, n_cols) raw ids -> (n, n_subcolumns) table rows; a wildcard
    maps to each subcolumn's extra slot."""
    outs = []
    for i, c in enumerate(cols):
        x = ids[:, i].astype(np.int64)
        if not c.divisors:
            outs.append(x)
            continue
        wild = x == WILDCARD
        subs, cur = [], x
        for k, d in enumerate(c.divisors):
            subs.append(np.where(wild, c.sub_cards[len(c.divisors) - k],
                                 cur % d))
            cur = cur // d
        subs.append(np.where(wild, c.sub_cards[0], cur))
        outs.extend(subs[::-1])
    return np.stack(outs, axis=-1).astype(np.int32)


# ------------------------------------------------------------- the model

def param_shapes(cols: List[Column], hidden: int) -> Dict[str, Dict]:
    shapes = {"embed": {}, "dense": {}}
    for i, (r, e) in enumerate(zip(table_rows(cols), embed_dims(cols))):
        shapes["embed"][f"col{i}"] = (r, e)
    d = concat_dim(cols)
    shapes["dense"] = {"w0": (d, hidden), "b0": (hidden,),
                       "w_out": (hidden, 1), "b_out": (1,)}
    return shapes


def logits64(params, cols: List[Column], ids: np.ndarray,
             block: int = 65536) -> np.ndarray:
    """The plain reference: (n, n_cols) raw ids -> (n,) float64
    logits, ``relu(x @ w0 + b0) . w_out + b_out``."""
    p = _float64(params)
    out = np.empty(len(ids), np.float64)
    for s in range(0, len(ids), block):
        out[s:s + block] = _forward64(p, encode(ids[s:s + block], cols))
    return out


def logits64_encoded(params, enc: np.ndarray) -> np.ndarray:
    """:func:`logits64` of (n, n_subcolumns) encoded ids."""
    return _forward64(_float64(params), enc)


def _float64(params):
    return {g: {k: np.asarray(v, np.float64) for k, v in d.items()}
            for g, d in params.items()}


def _forward64(p, enc: np.ndarray) -> np.ndarray:
    x = np.concatenate([p["embed"][f"col{i}"][enc[:, i]]
                        for i in range(enc.shape[1])], axis=-1)
    h = np.maximum(x @ p["dense"]["w0"] + p["dense"]["b0"], 0.0)
    return h @ p["dense"]["w_out"][:, 0] + p["dense"]["b_out"][0]


def _jax_forward(params, enc, n_sub: int, precision: str):
    import jax
    import jax.numpy as jnp
    x = jnp.concatenate([params["embed"][f"col{i}"][enc[:, i]]
                         for i in range(n_sub)], axis=-1)
    w0 = params["dense"]["w0"]

    def mm(a, b):
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)

    if precision == "highest":
        h = jnp.matmul(x, w0, precision=jax.lax.Precision.HIGHEST)
    elif precision == "high":
        # three bfloat16 passes on a TPU (a split into bfloat16 high
        # and low parts written out by hand read exactly the one-pass
        # numbers on a TPU v5e, so the precision flag it is)
        h = jnp.matmul(x, w0, precision=jax.lax.Precision.HIGH)
    elif precision == "bf16":
        h = mm(x.astype(jnp.bfloat16), w0.astype(jnp.bfloat16))
    else:
        raise ValueError(f"unknown precision {precision!r}")
    h = jax.nn.relu(h + params["dense"]["b0"])
    return (jnp.sum(h * params["dense"]["w_out"][:, 0], axis=-1)
            + params["dense"]["b_out"][0])


def logits_at(params, cols: List[Column], ids: np.ndarray,
              precision: str) -> np.ndarray:
    """The control's forward pass on the default device, float32
    weights, hidden GEMM in ``precision``: "highest" (float32), "high"
    (three bfloat16 passes) or "bf16" (one bfloat16 pass), each with
    float32 accumulation."""
    import jax
    fwd = jax.jit(_jax_forward, static_argnums=(2, 3))
    return np.asarray(fwd(params, encode(ids, cols),
                          len(table_rows(cols)), precision), np.float64)


def fit_program(cols: List[Column], hidden: int, *, steps: int,
                batch: int, lr: float, clip: float):
    """The jitted ``fit(key, encoded_ids, labels) -> params``: Adam with
    global-norm clipping on binary cross-entropy over the logits, all
    ``steps`` in one ``lax.scan``."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(cols, hidden)
    n_sub = len(table_rows(cols))

    def init(key):
        leaves = {}
        keys = jax.random.split(key, len(shapes["embed"]) + 2)
        for k, (name, shp) in zip(keys, shapes["embed"].items()):
            leaves[name] = 0.05 * jax.random.normal(k, shp, jnp.float32)
        d, h = shapes["dense"]["w0"]
        dense = {
            "w0": jax.random.normal(keys[-2], (d, h)) / np.sqrt(d),
            "b0": jnp.zeros((h,)),
            "w_out": jax.random.normal(keys[-1], (h, 1)) / np.sqrt(h),
            "b_out": jnp.zeros((1,))}
        return {"embed": leaves, "dense": dense}

    def loss_fn(p, enc, y):
        z = _jax_forward(p, enc, n_sub, "highest")
        return jnp.mean(jnp.maximum(z, 0) - z * y
                        + jnp.log1p(jnp.exp(-jnp.abs(z))))

    @jax.jit
    def fit(key, enc, y):
        k_init, k_batch = jax.random.split(key)
        p = init(k_init)
        zeros = jax.tree.map(jnp.zeros_like, p)
        sel = jax.random.randint(k_batch, (steps, batch), 0, enc.shape[0])

        def step(carry, idx):
            p, m, v, t = carry
            g = jax.grad(loss_fn)(p, enc[idx], y[idx])
            norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
            g = jax.tree.map(lambda x: x * jnp.minimum(
                1.0, clip / jnp.maximum(norm, 1e-12)), g)
            t = t + 1.0
            m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
            v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
            c1, c2 = 1 - 0.9 ** t, 1 - 0.999 ** t
            p = jax.tree.map(
                lambda w, a, b: w - lr * (a / c1) / (jnp.sqrt(b / c2)
                                                     + 1e-8), p, m, v)
            return (p, m, v, t), None

        (p, _, _, _), _ = jax.lax.scan(step, (p, zeros, zeros, 0.0), sel)
        return p

    return fit


def train(cols: List[Column], hidden: int, ids: np.ndarray,
          labels: np.ndarray, *, steps: int, batch: int, lr: float,
          clip: float, seed: int):
    """Fit the model on ``(ids, labels)`` on the device from ``seed``;
    returns float32 NumPy params."""
    import jax
    import jax.numpy as jnp
    fit = fit_program(cols, hidden, steps=steps, batch=batch, lr=lr,
                      clip=clip)
    out = fit(jax.random.key(seed & 0x7FFFFFFF),
              jnp.asarray(encode(ids, cols)), jnp.asarray(labels))
    return jax.tree.map(lambda x: np.asarray(x, np.float32), out)


# ----------------------------------------------------------------- bloom

def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def hash_rows(ids: np.ndarray, seed: int) -> np.ndarray:
    """Murmur3-style 32-bit hash of each (n, n_cols) row."""
    with np.errstate(over="ignore"):
        x = np.ascontiguousarray(ids).astype(np.uint32)
        h = np.full(len(x), seed, np.uint32)
        n = x.shape[1]
        for i in range(n):
            k = x[:, i] ^ np.uint32(((i + 1) * _GOLDEN) & _M32)
            k = _rotl(k * np.uint32(_C1), 15) * np.uint32(_C2)
            h = _rotl(h ^ k, 13) * np.uint32(5) + np.uint32(0xE6546B64)
        h = h ^ np.uint32(n)
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(0x85EBCA6B)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(0xC2B2AE35)
        return h ^ (h >> np.uint32(16))


def bloom_size(n_keys: int, fpr: float) -> Tuple[int, int]:
    """Optimal ``(m_bits, n_hashes)`` for ``n_keys`` at ``fpr``."""
    m = max(int(math.ceil(-n_keys * math.log(fpr) / math.log(2) ** 2)), 64)
    return m, max(1, int(round(m / max(n_keys, 1) * math.log(2))))


def probe(ids: np.ndarray, m_bits: int, n_hashes: int
          ) -> Tuple[np.ndarray, np.ndarray]:
    """Double hashing: (n, n_hashes) word indices and bit masks."""
    with np.errstate(over="ignore"):
        h1 = hash_rows(ids, PROBE_SEEDS[0])
        h2 = hash_rows(ids, PROBE_SEEDS[1]) | np.uint32(1)
        ks = np.arange(n_hashes, dtype=np.uint32)
        pos = (h1[:, None] + ks[None, :] * h2[:, None]) % np.uint32(m_bits)
    return (pos >> np.uint32(5)).astype(np.int64), \
        np.uint32(1) << (pos & np.uint32(31))


@dataclasses.dataclass(frozen=True)
class Filter:
    """One fitted filter: its plan (``theta``, ``ns``), float32 params,
    the fixup bitset and its geometry, and ``tau`` (a probability;
    logit 0 at 0.5). A quantized configuration's filter also holds the
    codes and scales the program is given (``qparams``), the threshold
    it serves them at (``tau_q``, a probability; ``threshold``, the
    logit the reference compares with) and the weights they dequantize
    to in float32 (``dequantized``)."""
    theta: int
    ns: int
    cols: List[Column]
    hidden: int
    params: Dict
    bits: np.ndarray
    m_bits: int
    n_hashes: int
    n_keys: int
    tau: float
    qparams: Optional[Dict] = None
    tau_q: Optional[float] = None
    threshold: float = 0.0
    dequantized: Optional[Dict] = None

    @property
    def served(self) -> Dict:
        """The float32 weights the program serves."""
        return self.params if self.dequantized is None else self.dequantized


def build_fixup(keys: np.ndarray, key_logits: np.ndarray, fpr: float,
                capacity: int) -> Tuple[np.ndarray, int, int, int]:
    """Bitset holding every key with logit below ``+BORDER_LOGIT`` (for
    a quantized filter, the lower of its float32 logit and its
    quantized logit's distance above ``threshold``), sized for
    ``capacity`` keys at ``fpr`` whatever the count, so that
    every seed's filter has the same size (more keys than that are
    still held, at a higher false-positive rate): ``(bits, m_bits,
    n_hashes, n_inserted)``."""
    fn = keys[key_logits < BORDER_LOGIT]
    m_bits, n_hashes = bloom_size(capacity, fpr)
    bits = np.zeros((m_bits + 31) // 32, np.uint32)
    if len(fn):
        words, masks = probe(fn, m_bits, n_hashes)
        np.bitwise_or.at(bits, words.reshape(-1), masks.reshape(-1))
    return bits, m_bits, n_hashes, len(fn)
