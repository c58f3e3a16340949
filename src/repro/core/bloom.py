"""Classic Bloom filter over multidimensional tuples, JAX-native.

The bit array is packed ``uint32``; hashing is murmur3-style 32-bit mixing
with double hashing (Kirsch–Mitzenmacher) for the ``h`` probe positions.
Insertion happens host-side (``np.bitwise_or.at`` — a build-time operation);
querying is the hot path and runs in JAX (and in the ``kernels/bloom_query``
Pallas kernel, which keeps the packed bitset VMEM-resident on TPU).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
_GOLDEN = np.uint32(0x9E3779B9)


def _rotl32(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def fmix32(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def hash_tuples(ids, seed: int) -> jax.Array:
    """ids: (..., n_cols) int32 -> (...,) uint32 murmur3-style tuple hash."""
    ids = jnp.asarray(ids).astype(jnp.uint32)
    h = jnp.full(ids.shape[:-1], jnp.uint32(seed))
    n = ids.shape[-1]
    for i in range(n):
        k = ids[..., i] ^ (jnp.uint32(i + 1) * _GOLDEN)
        k = k * _C1
        k = _rotl32(k, 15)
        k = k * _C2
        h = h ^ k
        h = _rotl32(h, 13)
        h = h * jnp.uint32(5) + jnp.uint32(0xE6546B64)
    return fmix32(h ^ jnp.uint32(n))


@dataclasses.dataclass(frozen=True)
class BloomParams:
    m_bits: int
    n_hashes: int

    @property
    def n_words(self) -> int:
        return (self.m_bits + 31) // 32

    @property
    def size_bytes(self) -> int:
        return self.n_words * 4

    @property
    def size_mb(self) -> float:
        return self.size_bytes / (1024.0 * 1024.0)


def params_for(n_keys: int, fpr: float) -> BloomParams:
    """Optimal sizing: m = -n ln p / ln2^2 ; h = (m/n) ln 2."""
    m = int(math.ceil(-n_keys * math.log(fpr) / (math.log(2) ** 2)))
    m = max(m, 64)
    h = max(1, int(round((m / max(n_keys, 1)) * math.log(2))))
    return BloomParams(m_bits=m, n_hashes=h)


def empty(params: BloomParams) -> np.ndarray:
    return np.zeros(params.n_words, dtype=np.uint32)


def probe_positions(ids, params: BloomParams) -> jax.Array:
    """(..., n_cols) -> (..., h) uint32 bit positions (double hashing)."""
    h1 = hash_tuples(ids, seed=0x0000A5A5)
    h2 = hash_tuples(ids, seed=0x00005EED) | jnp.uint32(1)
    ks = jnp.arange(params.n_hashes, dtype=jnp.uint32)
    pos = (h1[..., None] + ks * h2[..., None]) % jnp.uint32(params.m_bits)
    return pos


def probe_words(ids, params: BloomParams) -> Tuple[jax.Array, jax.Array]:
    """(..., n_cols) -> ((..., h) int32 word index, (..., h) uint32 mask).

    The word-level decomposition of :func:`probe_positions`: probe ``k``
    of a tuple tests ``bits[word[k]] & mask[k]``. Exposed so a sharded
    executor holding words ``[offset, offset + n_local)`` can probe only
    its slice (each global word index belongs to exactly one shard).
    """
    pos = probe_positions(ids, params)
    words = (pos >> jnp.uint32(5)).astype(jnp.int32)
    masks = jnp.uint32(1) << (pos & jnp.uint32(31))
    return words, masks


def add(bits: np.ndarray, ids, params: BloomParams) -> np.ndarray:
    """Host-side insertion (build-time). Returns the mutated array."""
    pos = np.asarray(probe_positions(ids, params)).reshape(-1)
    words = (pos >> 5).astype(np.int64)
    masks = (np.uint32(1) << (pos & 31).astype(np.uint32))
    np.bitwise_or.at(bits, words, masks)
    return bits


def query(bits, ids, params: BloomParams) -> jax.Array:
    """(..., n_cols) -> (...,) bool. JAX reference implementation."""
    bits = jnp.asarray(bits)
    words, masks = probe_words(ids, params)
    hit = (jnp.take(bits, words, axis=0) & masks) != jnp.uint32(0)
    return jnp.all(hit, axis=-1)


def grouped_query(bits, ids, n_hashes: int, m_bits, word_base) -> jax.Array:
    """Per-row probe against a CONCATENATION of many filters' bitsets.

    ``bits`` holds several tenants' packed bitsets back to back;
    ``m_bits`` (uint32) and ``word_base`` (int32) give each row its own
    filter geometry: row ``r`` probes the ``m_bits[r]``-bit filter whose
    words start at ``bits[word_base[r]]``. ``n_hashes`` is static (the
    probe-loop bound) and must be uniform across the group — it is part
    of the serving layer's plan-group key.

    Integer-exact: for any row, the result equals :func:`query` against
    that row's own filter sliced out of ``bits`` (same hash family, same
    double-hashing schedule, same word/mask decomposition — only the
    word index is rebased). The serving ``GroupedExecutor`` relies on
    this to answer many tenants from ONE device dispatch.
    """
    bits = jnp.asarray(bits)
    ids = jnp.asarray(ids)
    m_bits = jnp.asarray(m_bits).astype(jnp.uint32)
    word_base = jnp.asarray(word_base).astype(jnp.int32)
    h1 = hash_tuples(ids, seed=0x0000A5A5)
    h2 = hash_tuples(ids, seed=0x00005EED) | jnp.uint32(1)
    ks = jnp.arange(n_hashes, dtype=jnp.uint32)
    pos = (h1[..., None] + ks * h2[..., None]) % m_bits[..., None]
    words = (pos >> jnp.uint32(5)).astype(jnp.int32) + word_base[..., None]
    masks = jnp.uint32(1) << (pos & jnp.uint32(31))
    hit = (jnp.take(bits, words, axis=0) & masks) != jnp.uint32(0)
    return jnp.all(hit, axis=-1)


def grouped_shard_miss_count(bits_local, ids, n_hashes: int, m_bits,
                             word_base, word_offset) -> jax.Array:
    """Misses among the probes a shard of a CONCATENATED arena owns.

    The grouping x sharding composition of :func:`grouped_query` and
    :func:`shard_miss_count`: ``bits_local`` is the contiguous word
    slice ``bits[word_offset : word_offset + n_local]`` of a combined
    multi-filter arena, and each row carries its own filter geometry
    (``m_bits``, ``word_base``) exactly as in :func:`grouped_query` —
    the per-slot word base is rebased per shard by subtracting
    ``word_offset``. Probes landing outside the slice are skipped.
    Every probe word belongs to exactly one shard, so

        psum(grouped_shard_miss_count(...)) == 0
            <=>  grouped_query(...)
            <=>  per-filter query(...)   (row by row, bit-for-bit)

    which is what lets a mesh-sharded plan-group arena answer a
    megabatch with ONE cross-shard combine.
    """
    bits_local = jnp.asarray(bits_local)
    n_local = bits_local.shape[0]
    ids = jnp.asarray(ids)
    m_bits = jnp.asarray(m_bits).astype(jnp.uint32)
    word_base = jnp.asarray(word_base).astype(jnp.int32)
    h1 = hash_tuples(ids, seed=0x0000A5A5)
    h2 = hash_tuples(ids, seed=0x00005EED) | jnp.uint32(1)
    ks = jnp.arange(n_hashes, dtype=jnp.uint32)
    pos = (h1[..., None] + ks * h2[..., None]) % m_bits[..., None]
    words = (pos >> jnp.uint32(5)).astype(jnp.int32) + word_base[..., None]
    masks = jnp.uint32(1) << (pos & jnp.uint32(31))
    local = words - word_offset
    owned = (local >= 0) & (local < n_local)
    w = jnp.take(bits_local, jnp.clip(local, 0, n_local - 1), axis=0)
    miss = owned & ((w & masks) == jnp.uint32(0))
    return jnp.sum(miss, axis=-1).astype(jnp.int32)


def shard_miss_count(bits_local, ids, params: BloomParams,
                     word_offset) -> jax.Array:
    """Misses among the probes owned by one bitset slice.

    ``bits_local`` is the shard's contiguous word slice
    ``bits[word_offset : word_offset + n_local]`` (zero-padded past the
    global ``n_words`` is fine — no probe lands there). Returns
    ``(...,) int32`` counts; summing over all shards and comparing to
    zero reproduces :func:`query` bit-for-bit, since every probe word
    belongs to exactly one shard:

        psum(shard_miss_count(...)) == 0  <=>  query(...)
    """
    bits_local = jnp.asarray(bits_local)
    n_local = bits_local.shape[0]
    words, masks = probe_words(ids, params)
    local = words - word_offset
    owned = (local >= 0) & (local < n_local)
    w = jnp.take(bits_local, jnp.clip(local, 0, n_local - 1), axis=0)
    miss = owned & ((w & masks) == jnp.uint32(0))
    return jnp.sum(miss, axis=-1).astype(jnp.int32)


def fpr_estimate(params: BloomParams, n_keys: int) -> float:
    """Theoretical FPR after inserting n_keys."""
    return (1.0 - math.exp(-params.n_hashes * n_keys / params.m_bits)
            ) ** params.n_hashes
