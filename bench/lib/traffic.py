"""The one traffic generator: every mix is a data file it reads.

A mix (``bench/traffic/<name>.json``, with per-configuration values in
``bench/traffic/<name>/<config>.json`` layered on top) gives:

* ``loop``: ``"open"`` (a fixed schedule of arrivals, sent whether or
  not earlier requests finished) or ``"closed"`` (``clients`` callers,
  each with one request outstanding, drawing from a sequence of
  ``requests`` requests round and round);
* ``rate_rows_per_s`` (open): offered rows per second;
* ``rows``: request sizes, ``{"dist": "loguniform", "min", "max"}`` or
  ``{"dist": "fixed", "n"}``;
* ``tenants``: ``{"dist": "zipf", "constant"}`` over the configuration's
  tenants (YCSB's zipfian: P(rank k) ~ 1 / k**constant);
* ``member_share``, ``wildcard_prob``, ``pool_rows``: the row pools
  (``relation.query_pool``) that requests slice their rows from.

Every seed gets the same work in another order: sizes, gaps and tenant
ranks are the distribution's quantiles at evenly spaced points, in one
fixed shuffled sequence that the seed enters at a point of its own (a
rotation, so every seed meets the same bursts); which tenant holds
which rank and where each request slices its pool are drawn from the
seed.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Optional

import numpy as np


def load(bench_dir: str, name: str, config: str) -> Dict:
    """The mix ``name`` as served under ``config``."""
    with open(os.path.join(bench_dir, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    overlay = os.path.join(bench_dir, "traffic", name, f"{config}.json")
    if os.path.exists(overlay):
        with open(overlay) as f:
            mix.update(json.load(f))
    return mix


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def sizes(spec: Dict, n: int) -> np.ndarray:
    """``n`` request sizes at evenly spaced quantiles (unshuffled)."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["n"]), np.int64)
    if spec["dist"] == "loguniform":
        lo, hi = int(spec["min"]), int(spec["max"])
        x = np.exp(np.log(lo) + _midpoints(n) * (np.log(hi + 1)
                                                  - np.log(lo)))
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown size distribution {spec['dist']!r}")


def mean_size(spec: Dict) -> float:
    return float(sizes(spec, 1 << 16).mean())


def tenant_ranks(spec: Dict, n_tenants: int, n: int) -> np.ndarray:
    """``n`` tenant ranks (0 = hottest) at evenly spaced quantiles."""
    if spec["dist"] != "zipf":
        raise ValueError(f"unknown tenant distribution {spec['dist']!r}")
    w = 1.0 / np.arange(1, n_tenants + 1) ** float(spec["constant"])
    cdf = np.cumsum(w) / w.sum()
    return np.minimum(np.searchsorted(cdf, _midpoints(n)), n_tenants - 1)


@dataclasses.dataclass
class Schedule:
    """Requests in the order they are sent. ``due`` (open loop) is each
    request's send time in seconds from the window's start."""
    tenant: np.ndarray        # tenant index
    rows: np.ndarray          # rows per request
    offset: np.ndarray        # first pool row
    due: Optional[np.ndarray]

    def __len__(self) -> int:
        return len(self.rows)


PATTERN = 0     # the seed of the request pattern that every seed shares


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, stream])


def schedule(mix: Dict, n_tenants: int, seed: int,
             seconds: float) -> Schedule:
    """The open loop's whole window, or the closed loop's sequence of
    requests that its clients cycle through."""
    if mix["loop"] == "open":
        n = max(1, int(round(float(mix["rate_rows_per_s"]) * seconds
                             / mean_size(mix["rows"]))))
    else:
        n = int(mix["requests"])
    # the pattern (sizes, tenant ranks and gaps in sequence) is the same
    # for every seed, which starts it at a point of its own: a queue's
    # tail hangs on how the bursts fall, so a shuffle per seed would
    # make the tail the seed's
    shift = int(_rng(seed, 1).integers(n))
    rows = np.roll(sizes(mix["rows"], n)[_rng(PATTERN, 1).permutation(n)],
                   -shift)
    ranks = tenant_ranks(mix["tenants"], n_tenants, n)
    ranks = np.roll(ranks[_rng(PATTERN, 2).permutation(n)], -shift)
    tenant = _rng(seed, 3).permutation(n_tenants)[ranks]
    pool = int(mix["pool_rows"])
    offset = _rng(seed, 4).integers(0, pool - rows + 1)
    due = None
    if mix["loop"] == "open":
        gaps = -np.log1p(-_midpoints(n))[_rng(PATTERN, 5).permutation(n)]
        gaps = np.roll(gaps, -shift)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        due *= seconds / gaps.sum()
    return Schedule(tenant=tenant, rows=rows, offset=offset, due=due)
