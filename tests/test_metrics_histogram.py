"""Measurement primitives: Histogram vs NumPy, LatencyWindow ranks,
MetricsLogger lifecycle.

The histogram's contract is *bounded relative error*: any percentile
it reports is within a factor of ``growth`` of the exact nearest-rank
percentile of the recorded samples, for any sample distribution. The
deterministic seeded sweeps here pin that against NumPy; the
Hypothesis-driven versions live in ``test_metrics_property.py`` (the
repo convention keeping a missing ``hypothesis`` install a skip, not a
collection error). Merging two histograms must be indistinguishable
from recording every sample into one.
"""
import json
import math

import numpy as np
import pytest

from repro.runtime.metrics import Histogram, LatencyWindow, MetricsLogger


def _exact_nearest_rank(data, q):
    """Reference nearest-rank percentile: value at rank ceil(q/100*n)."""
    data = sorted(data)
    rank = max(1, math.ceil(q / 100.0 * len(data)))
    return data[min(len(data), rank) - 1]


def _random_samples(rng, n):
    """Latency-ish positive samples spanning ~9 decades."""
    return np.exp(rng.uniform(np.log(1e-6), np.log(1e3), n)).tolist()


# -------------------------------------------------------------- histogram

@pytest.mark.parametrize("seed", range(8))
def test_histogram_percentile_within_growth_of_exact(seed):
    rng = np.random.default_rng(seed)
    values = _random_samples(rng, int(rng.integers(1, 400)))
    growth = 1.1
    h = Histogram(growth=growth)
    for v in values:
        h.record(v)
    for q in (1.0, 25.0, 50.0, 90.0, 99.0, 100.0):
        got = h.percentile(q)
        exact = _exact_nearest_rank(values, q)
        # log-bucketing guarantee: off by at most one bucket width, and
        # the clamp keeps the answer inside the observed range
        assert min(values) <= got <= max(values)
        assert got <= exact * growth + 1e-12
        assert got >= exact / growth - 1e-12


@pytest.mark.parametrize("seed", range(6))
def test_histogram_merge_equals_combined_recording(seed):
    rng = np.random.default_rng(100 + seed)
    a = _random_samples(rng, int(rng.integers(1, 120)))
    b = _random_samples(rng, int(rng.integers(1, 120)))
    ha, hb, hc = Histogram(), Histogram(), Histogram()
    for v in a:
        ha.record(v)
        hc.record(v)
    for v in b:
        hb.record(v)
        hc.record(v)
    merged = ha.merge(hb)
    assert merged is ha                       # in place, chainable
    assert merged.count == hc.count
    assert merged.total == pytest.approx(hc.total)
    assert merged.min == hc.min and merged.max == hc.max
    for q in (1, 50, 99, 100):
        assert merged.percentile(q) == pytest.approx(hc.percentile(q))


def test_histogram_merge_mismatch_raises():
    with pytest.raises(ValueError, match="growth"):
        Histogram(growth=1.1).merge(Histogram(growth=1.5))
    with pytest.raises(ValueError, match="min_value"):
        Histogram(min_value=1e-9).merge(Histogram(min_value=1e-6))


def test_histogram_vs_numpy_on_lognormal():
    """A realistic latency-shaped distribution, checked against
    np.percentile's 'inverted_cdf' (exact nearest-rank) within the
    one-bucket growth factor."""
    rng = np.random.default_rng(7)
    samples = np.exp(rng.normal(-7.0, 1.0, 5000))     # ~0.9ms median
    growth = 1.05
    h = Histogram(growth=growth)
    for v in samples:
        h.record(float(v))
    for q in (10, 50, 90, 99, 99.9):
        ref = float(np.percentile(samples, q, method="inverted_cdf"))
        assert ref / growth <= h.percentile(q) <= ref * growth


def test_histogram_empty_and_underflow():
    h = Histogram()
    assert h.percentile(50) == 0.0
    assert h.min == 0.0 and h.max == 0.0 and h.mean == 0.0
    h.record(0.0)                    # underflow bucket, no math.log crash
    assert h.count == 1
    assert h.percentile(99) == 0.0   # clamped to observed max
    assert h.summary("queue_", scale=1e3) == {
        "queue_p50_ms": 0.0, "queue_p99_ms": 0.0, "queue_max_ms": 0.0}


def test_histogram_summary_key_shape():
    h = Histogram()
    for v in (0.001, 0.002, 0.010):
        h.record(v)
    s = h.summary("queue_", scale=1e3)
    assert set(s) == {"queue_p50_ms", "queue_p99_ms", "queue_max_ms"}
    assert s["queue_max_ms"] == pytest.approx(10.0)
    assert s["queue_p50_ms"] <= s["queue_p99_ms"] <= s["queue_max_ms"]


@pytest.mark.parametrize("seed", range(4))
def test_histogram_since_is_the_window(seed):
    """``since`` of an earlier ``copy`` holds exactly the samples
    recorded after the copy: bucket counts equal to a histogram of
    those samples alone, percentiles within ``growth`` of theirs."""
    rng = np.random.default_rng(seed)
    before = _random_samples(rng, int(rng.integers(0, 300)))
    window = _random_samples(rng, int(rng.integers(1, 300)))
    h = Histogram(growth=1.1)
    for v in before:
        h.record(v)
    mark = h.copy()
    for v in window:
        h.record(v)
    alone = Histogram(growth=1.1)
    for v in window:
        alone.record(v)
    got = h.since(mark)
    assert got._counts == alone._counts
    assert got.count == len(window)
    assert got.total == pytest.approx(sum(window))
    for q in (1, 50, 90, 99, 100):
        exact = _exact_nearest_rank(window, q)
        assert exact / 1.1 <= got.percentile(q) <= exact * 1.1
    assert mark.count == len(before)          # the copy is independent


def test_histogram_since_refuses_a_later_or_foreign_copy():
    h = Histogram()
    h.record(0.5)
    later = h.copy()
    later.record(2.0)
    with pytest.raises(ValueError):
        h.since(later)
    with pytest.raises(ValueError):
        h.since(Histogram(growth=1.2))
    assert h.since(h.copy()).count == 0
    assert h.since(h.copy()).percentile(90) == 0.0


def test_histogram_validates_parameters():
    with pytest.raises(ValueError):
        Histogram(growth=1.0)
    with pytest.raises(ValueError):
        Histogram(min_value=0.0)


# -------------------------------------------------------- latency window

def test_latency_window_nearest_rank():
    """The banker's-rounding regression: p50 of [1,2,3,4] must be the
    2nd sample (rank ceil(0.5*4)=2), not the 3rd — and a window of one
    returns that one for every q."""
    w = LatencyWindow()
    for v in (4.0, 1.0, 3.0, 2.0):
        w.record(v)
    assert w.percentile(50) == 2.0
    assert w.percentile(75) == 3.0
    assert w.percentile(99) == 4.0
    assert w.percentile(100) == 4.0
    assert w.percentile(0) == 1.0
    one = LatencyWindow()
    one.record(5.0)
    for q in (0, 50, 99, 100):
        assert one.percentile(q) == 5.0


@pytest.mark.parametrize("seed", range(6))
def test_latency_window_matches_reference(seed):
    rng = np.random.default_rng(200 + seed)
    values = rng.uniform(0.0, 1e3, int(rng.integers(1, 200))).tolist()
    w = LatencyWindow()
    for v in values:
        w.record(v)
    for q in (0.0, 7.3, 50.0, 75.0, 99.0, 100.0):
        assert w.percentile(q) == _exact_nearest_rank(values, q)


def test_latency_window_empty():
    assert LatencyWindow().percentile(50) == 0.0


# -------------------------------------------------------- metrics logger

def test_metrics_logger_context_manager_closes(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with MetricsLogger(path, echo=False) as m:
        m.log(0, qps=100.0)
        m.log(1, qps=200.0)
        f = m._f
        assert f is not None and not f.closed
    assert f.closed and m._f is None
    m.close()                                 # idempotent
    m.log(2, qps=300.0)                       # post-close logs don't crash
    with open(path) as fh:
        rows = [json.loads(line) for line in fh]
    assert [r["step"] for r in rows] == [0, 1]
    assert rows[1]["qps"] == 200.0


def test_metrics_logger_pathless_is_inert(tmp_path):
    with MetricsLogger(None, echo=False) as m:
        assert m._f is None
        m.log(0, x=1)
