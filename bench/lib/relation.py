"""Relations and query pools, made from the seed.

The generator and the negative sampler follow the paper's section 4
protocol as ``src/repro/data/tuples.py`` implements it (Zipf-skewed ids
per column, correlated through a shared latent rank; negatives are
rejection-sampled value combinations, some with a wildcard). They are
copied here so that the data a cell serves, and the membership the
reference checks, never depend on the code under test.

Id 0 of every column is the wildcard; record ids lie in ``[1, v)``.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import numpy as np

WILDCARD = 0


@dataclasses.dataclass
class Relation:
    cards: Tuple[int, ...]
    records: np.ndarray            # (n, n_cols) int32, ids in [1, v)

    def __post_init__(self):
        self._keys = {r.tobytes() for r in self.records}

    @property
    def n_cols(self) -> int:
        return len(self.cards)

    def contains(self, rows: np.ndarray) -> np.ndarray:
        rows = np.ascontiguousarray(rows, np.int32)
        keys = self._keys
        return np.fromiter((r.tobytes() in keys for r in rows), bool,
                           len(rows))


def synthesize(cards: Sequence[int], n_records: int, seed: int,
               zipf_a: float = 1.3, noise: float = 0.35) -> Relation:
    """Zipf-distributed ids per column, correlated across columns."""
    rng = np.random.default_rng(seed)
    latent = rng.random(n_records)
    cols = []
    for v in cards:
        usable = max(int(v) - 1, 1)
        col_noise = rng.random(n_records) * noise
        rank = np.clip(latent * (1.0 - noise) + col_noise, 0, 1 - 1e-9)
        idx = np.floor((rank ** zipf_a) * usable).astype(np.int64)
        cols.append((idx % usable) + 1)
    recs = np.stack(cols, axis=-1).astype(np.int32)
    return Relation(cards=tuple(int(c) for c in cards), records=recs)


def _wildcard(rows: np.ndarray, rng, prob: float) -> None:
    """Replace ids by the wildcard with ``prob``, never a whole row."""
    if prob <= 0 or not len(rows):
        return
    mask = rng.random(rows.shape) < prob
    keep = rng.integers(0, rows.shape[1], size=len(rows))
    mask[np.arange(len(rows)), keep] = False
    rows[mask] = WILDCARD


def sample_positives(rel: Relation, n: int, seed: int,
                     wildcard_prob: float) -> np.ndarray:
    rng = np.random.default_rng(seed)
    rows = rel.records[rng.integers(0, len(rel.records), size=n)].copy()
    _wildcard(rows, rng, wildcard_prob)
    return rows


def sample_negatives(rel: Relation, n: int, seed: int,
                     wildcard_prob: float, max_tries: int = 20
                     ) -> np.ndarray:
    """Random value combinations that are not records (rejection
    sampled), then wildcarded with ``wildcard_prob``."""
    rng = np.random.default_rng(seed)
    out = np.zeros((n, rel.n_cols), np.int32)
    filled = 0
    for _ in range(max_tries):
        if filled >= n:
            break
        m = n - filled
        cand = np.stack([rng.integers(1, max(v, 2), size=m)
                         for v in rel.cards], axis=-1).astype(np.int32)
        take = cand[~rel.contains(cand)][:m]
        out[filled:filled + len(take)] = take
        filled += len(take)
    out = out[:filled]
    _wildcard(out, rng, wildcard_prob)
    return out


def training_set(rel: Relation, n_pos: int, n_neg: int, seed: int,
                 wildcard_prob: float) -> Tuple[np.ndarray, np.ndarray]:
    """Shuffled ``(ids, labels)``: positives wildcarded with
    ``wildcard_prob``, negatives with half of it."""
    pos = sample_positives(rel, n_pos, seed, wildcard_prob)
    neg = sample_negatives(rel, n_neg, seed + 1, wildcard_prob * 0.5)
    ids = np.concatenate([pos, neg])
    labels = np.concatenate([np.ones(len(pos), np.float32),
                             np.zeros(len(neg), np.float32)])
    perm = np.random.default_rng(seed + 2).permutation(len(ids))
    return ids[perm], labels[perm]


def query_pool(rel: Relation, n_rows: int, seed: int, member_share: float,
               wildcard_prob: float) -> Tuple[np.ndarray, np.ndarray]:
    """``(rows, is_record)``: each row is an indexed record with
    probability ``member_share``, otherwise a sampled negative."""
    rng = np.random.default_rng(seed)
    is_record = rng.random(n_rows) < member_share
    n_mem = int(is_record.sum())
    rows = np.empty((n_rows, rel.n_cols), np.int32)
    rows[is_record] = rel.records[rng.integers(0, len(rel.records), n_mem)]
    neg = sample_negatives(rel, n_rows - n_mem, seed + 1, wildcard_prob)
    if len(neg) != n_rows - n_mem:
        raise RuntimeError("the negative sampler fell short of the pool")
    rows[~is_record] = neg
    return rows, is_record
