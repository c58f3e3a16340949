"""The host split (``bench/lib/hostsplit.py``) and the readers of the
program's stages, on traces whose numbers are worked out by hand.

``HAND`` is a trace written event by event (times in ns). The device's
clock reads 1000 ns less than the host's.

host ``bench.window`` [0, 92000):

* ``bench.step`` [0, 40000): ``serve.prepare`` [1000, 5000),
  ``serve.dispatch`` [5000, 12000) holding ``serve.tiles`` [6000, 8000)
  and ``serve.launch`` [9000, 11000), ``serve.device_block`` [12000,
  30000), ``serve.scatter_retire`` [30000, 38000) holding
  ``serve.stats`` [32000, 36000);
* ``bench.submit`` [40000, 45000): ``serve.submit`` [41000, 44000);
* ``bench.step`` [50000, 91000): ``serve.prepare`` [50000, 52000),
  ``serve.dispatch`` [52000, 60000) holding ``serve.tiles`` [52500,
  53000) and ``serve.launch`` [54000, 58000), ``serve.device_block``
  [60000, 80000), ``serve.scatter_retire`` [80000, 90000) holding
  ``serve.stats`` [85000, 88000).

device (its own clock): ``jit_gather_tiles`` [6000, 7000) (one op),
``jit_fused_body`` [12000, 25000) and [57000, 75000) (one op each).

By hand. Offset: module start - launch start is 3000 for both pairs,
module end - block end is -5000 for both, so every offset in [-5000,
3000] keeps the pairs causal and the middle, -1000 ns, is the true one.

Self times: prepare 4000 + 2000, dispatch (1000 + 1000 + 1000) + (500 +
1000 + 2000) = 6500, tiles 2000 + 500, launch 2000 + 4000, device_block
18000 + 20000, scatter_retire (2000 + 2000) + (5000 + 2000) = 11000,
stats 4000 + 3000, submit 3000.

Idle gaps on the device's clock: [0, 6000), [7000, 12000), [25000,
57000), [75000, 92000): 60000 ns. With the host shifted by -1000: the
first under prepare 4000, dispatch 1000, tiles 1000; the second under
dispatch 2000, launch 2000, device_block 1000; the third under
device_block 4000, scatter_retire 4000, stats 4000, submit 3000,
prepare 2000, dispatch 1500, tiles 500, launch 4000 and outside 9000
([37000, 40000) and [43000, 49000)); the fourth under device_block
4000, scatter_retire 7000, stats 3000, outside 3000. Totals: prepare
6000, dispatch 4500, tiles 1500, launch 6000, device_block 9000,
scatter_retire 11000, stats 7000, submit 3000, outside 12000.
"""
import math
import os

import numpy as np
import pytest

from bench.lib import hostsplit, spec, xtrace
from bench.tests.test_trace_reduction import _events, _metadata

SERVE = [
    ("serve.prepare", 1000, 5000), ("serve.dispatch", 5000, 12000),
    ("serve.tiles", 6000, 8000), ("serve.launch", 9000, 11000),
    ("serve.device_block", 12000, 30000),
    ("serve.scatter_retire", 30000, 38000), ("serve.stats", 32000, 36000),
    ("serve.submit", 41000, 44000),
    ("serve.prepare", 50000, 52000), ("serve.dispatch", 52000, 60000),
    ("serve.tiles", 52500, 53000), ("serve.launch", 54000, 58000),
    ("serve.device_block", 60000, 80000),
    ("serve.scatter_retire", 80000, 90000), ("serve.stats", 85000, 88000)]
BENCH = [("bench.window", 0, 92000), ("bench.step", 0, 40000),
         ("bench.submit", 40000, 45000), ("bench.step", 50000, 91000)]
HOST = {n: i + 1 for i, n in enumerate(dict.fromkeys(
    n for n, _, _ in BENCH + SERVE))}
DEV = {"gather.1": 1, "fusion.2": 2, "jit_gather_tiles(3)": 3,
       "jit_fused_body(4)": 4}


def _trace(serve=SERVE):
    return (
        'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" '
        'timestamp_ns: 0\n' + _events(HOST, BENCH + serve)
        + "}\n" + _metadata(HOST) + "}\n"
        'planes { id: 2 name: "/device:TPU:0" lines { id: 1 '
        'name: "XLA Ops" timestamp_ns: 0\n'
        + _events(DEV, [("gather.1", 6000, 7000), ("fusion.2", 12000, 25000),
                        ("fusion.2", 57000, 75000)])
        + '}\nlines { id: 2 name: "XLA Modules" timestamp_ns: 0\n'
        + _events(DEV, [("jit_gather_tiles(3)", 6000, 7000),
                        ("jit_fused_body(4)", 12000, 25000),
                        ("jit_fused_body(4)", 57000, 75000)])
        + "}\n" + _metadata(DEV) + "}\n")


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData
    return hostsplit.reduce_xspace(ProfileData.from_text_proto(_trace()),
                                   stall_s=15e-6)


def test_offset_from_causal_pairs(hand):
    assert hand["clock_offset_us"] == pytest.approx(-1.0)
    assert hand["causal_pairs"] == 2
    assert hand["causal_broken_us"] == 0.0


def test_host_self_times(hand):
    got = {n: (pytest.approx(s * 1e9), c)
           for n, (s, c) in hand["host_self_s"].items()}
    assert got == {
        "serve.prepare": (6000, 2), "serve.dispatch": (6500, 2),
        "serve.tiles": (2500, 2), "serve.launch": (6000, 2),
        "serve.device_block": (38000, 2),
        "serve.scatter_retire": (11000, 2), "serve.stats": (7000, 2),
        "serve.submit": (3000, 1)}


def test_idle_split_by_innermost_span(hand):
    assert {n: v * 1e9 for n, v in hand["idle_by_span"].items()} == \
        pytest.approx({
            "serve.prepare": 6000, "serve.dispatch": 4500,
            "serve.tiles": 1500, "serve.launch": 6000,
            "serve.device_block": 9000, "serve.scatter_retire": 11000,
            "serve.stats": 7000, "serve.submit": 3000,
            "outside program": 12000})
    # the split adds up to the idle time the existing reduction finds
    from jax.profiler import ProfileData
    red = xtrace.reduce_xspace(ProfileData.from_text_proto(_trace()))
    assert sum(hand["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    shares = hostsplit.idle_shares(hand)
    assert shares["idle_in_block"] == pytest.approx(100 * 9000 / 92000)
    assert shares["idle_in_program"] == pytest.approx(100 * 39000 / 92000)


def test_stalls_named_by_span_and_annotation(hand):
    assert [s[2:] for s in hand["stalls"]] == [
        ["outside program", "bench.step"],
        ["serve.scatter_retire", "bench.step"]]
    assert [s[:2] for s in hand["stalls"]] == [
        [pytest.approx(25e-6), pytest.approx(32e-6)],
        [pytest.approx(75e-6), pytest.approx(17e-6)]]


def test_phases_per_krow(hand):
    # 2000 valid rows: 2 krows
    assert hostsplit.phase_us_per_krow(hand, 2000) == pytest.approx({
        "submit": 1.5, "prepare": 3.0, "dispatch": 7.5, "block": 19.0,
        "retire": 9.0})


@pytest.mark.parametrize("launch,block,want", [
    # every pair causal at -1000 +- 4000: the middle, nothing broken
    ([9000, 54000], [30000, 80000], (-1000, 2, 0)),
    # the second launch after its module's start (an offset of at most
    # -2000) and its block before its end (at least 3000): the range is
    # empty, and its middle, 500, leaves both sides broken by 2500
    ([9000, 59000], [30000, 72000], (500, 2, 2500)),
    # no launch: the block side alone
    ([], [30000, 80000], (-5000, 2, 0)),
])
def test_offset_cases(launch, block, want):
    serve = ([("serve.launch", t, t + 100) for t in launch]
             + [("serve.device_block", t - 100, t) for t in block])
    mods = [("jit_fused_body", 12000, 25000), ("jit_fused_body", 57000,
                                                75000)]
    off, pairs, broken = hostsplit.offset(serve, mods)
    assert (off, pairs, broken) == pytest.approx(want)


def test_innermost_segments():
    segs = hostsplit.innermost([("a", 0, 10), ("b", 2, 5), ("c", 3, 4),
                                ("d", 6, 8), ("e", 12, 13)])
    assert segs == [("a", 0, 2), ("b", 2, 3), ("c", 3, 4), ("b", 4, 5),
                    ("a", 5, 6), ("d", 6, 8), ("a", 8, 10), ("e", 12, 13)]


def test_a_program_without_annotations_reads_nothing():
    """The parent program writes no ``serve.*`` events: the split is
    ``None`` and the trace's other numbers are untouched."""
    from jax.profiler import ProfileData
    bare = ProfileData.from_text_proto(_trace(serve=[]))
    assert hostsplit.reduce_xspace(bare) is None
    assert xtrace.reduce_xspace(bare)["busy_s"] == pytest.approx(32e-6)


STAGES = [("prepare_us_per_krow", "prepare"),
          ("dispatch_us_per_krow", "dispatch"),
          ("block_us_per_krow", "device_block"),
          ("retire_us_per_krow", "scatter_retire")]


def _ring():
    """The ``serve`` stages of ``HAND`` as the ring hands them to the
    readers (seconds on the benchmark's clock; the ``detail`` spans
    are not there)."""
    stages = {stage for _, stage in STAGES}
    return [(n[6:], s * 1e-9, e * 1e-9) for n, s, e in SERVE
            if n[6:] in stages]


@pytest.mark.parametrize("metric,stage", STAGES)
def test_stage_readers_read_the_ring(metric, stage):
    ctx = {"spans": _ring(), "spans_dropped": 0, "t0": 0.0, "t1": 92e-6,
           "serve": {"valid_rows": 2000}}
    inclusive = {"prepare": 6000, "dispatch": 15000,
                 "device_block": 38000, "scatter_retire": 18000}
    for suffix in ("fleet", "bulk"):
        got = spec.reader(f"{metric}.{suffix}")(ctx)
        assert got == pytest.approx(inclusive[stage] / 1e3 / 2)
    # where the ring dropped spans, the kept ones (from 50000 ns on)
    # stand for the window
    late = [s for s in _ring() if s[1] >= 50e-6]
    ctx = dict(ctx, spans=late, spans_dropped=5)
    kept = sum(e - s for n, s, e in late if n == stage)
    assert spec.reader(f"{metric}.fleet")(ctx) == pytest.approx(
        kept * 92 / 42 * 1e9 / 2000)
    assert spec.reader(f"{metric}.bulk")(dict(ctx, spans=[])) is None


def test_stages_add_up_to_the_host_union():
    """The four stages are disjoint, so their us per krow times the
    krows is ``host_busy_share`` of the window."""
    ctx = {"spans": _ring(), "spans_dropped": 0, "t0": 0.0, "t1": 92e-6,
           "serve": {"valid_rows": 2000}}
    total = sum(spec.reader(f"{m}.fleet")(ctx) for m, _ in STAGES) * 2
    busy = spec.reader("host_busy_share.fleet")(ctx)
    assert total * 1e-6 == pytest.approx(busy / 100 * 92e-6)
    assert np.isclose(total, 77.0)


# A trace recorded on one TPU v5e by ``bench/record_serve_trace.py``:
# the small cell of ``tiny.py``, four rounds of ``bench.submit`` (16
# rows for each of three tenants, or for one) and ``bench.step``, so
# four grouped dispatches, the last two finding their tile layout
# cached. Kept as the text ``XSpace`` the script writes: the host's
# ``bench.*``/``serve.*`` events and the device's op and module lines.
V5E = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "v5e_serve.xplane.txt")
SERVE_NAMES = {"serve.submit", "serve.prepare", "serve.dispatch",
               "serve.tiles", "serve.launch", "serve.device_block",
               "serve.scatter_retire", "serve.stats"}


@pytest.fixture(scope="module")
def chip():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(open(V5E).read())


def test_chip_trace_finds_the_spans_and_an_offset(chip):
    split = hostsplit.reduce_xspace(chip)
    assert set(split["host_self_s"]) == SERVE_NAMES
    assert {c for _, c in split["host_self_s"].values()} == {4}
    assert math.isfinite(split["clock_offset_us"])
    assert split["causal_pairs"] == 4
    assert split["causal_broken_us"] == 0.0
    red = xtrace.reduce_xspace(chip)
    assert set(red["module_s"]) == {"jit_fused_body", "jit_gather_tiles"}
    assert sum(split["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_chip_trace_pairs_are_causal_after_the_shift(chip):
    """Each dispatch's program starts after its ``serve.launch`` began
    and ends before its ``serve.device_block`` ended, on the device's
    clock, once the host's events are shifted by the offset."""
    split = hostsplit.reduce_xspace(chip)
    off = split["clock_offset_us"] * 1e3
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in chip.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events]
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns)
                  for p in chip.planes if p.name == "/device:TPU:0"
                  for line in p.lines if line.name == "XLA Modules"
                  for e in line.events if e.name.startswith("jit_fused_body"))
    launch = sorted(s for n, s, _ in host if n == "serve.launch")
    block = sorted(e for n, _, e in host if n == "serve.device_block")
    assert len(mods) == len(launch) == len(block) == 4
    for (m0, m1), l0, b1 in zip(mods, launch, block):
        assert l0 + off <= m0 and m1 <= b1 + off
