"""The trace reduction and the metrics that read it, on traces whose
numbers are worked out by hand.

``HAND`` is a trace written out event by event (times in ns):

host ``bench.window`` [0, 100000): ``bench.step`` [0, 40000),
``bench.wait`` [40000, 60000), ``bench.step`` [60000, 90000),
``bench.poll`` [90000, 100000).

device ops: fusion.1 [5000, 20000), gather.2 [15000, 35000),
fusion.1 [62000, 70000), fusion.1 [70000, 88000), gather.2
[95000, 110000) (cut at the window's end), fusion.1 [150000, 160000)
(outside). Modules: jit_fused_body [5000, 35000) and [70000, 88000),
jit_gather_tiles [62000, 70000), jit_fused_body [150000, 160000)
(outside).

By hand: busy = [5000, 35000) + [62000, 88000) + [95000, 100000) =
30000 + 26000 + 5000 = 61000 ns; idle share 39%. Idle gaps: [35000,
62000) 27000 ns, most under bench.wait (20000 of it); [88000, 95000)
7000 ns, most under bench.poll (5000); [0, 5000) 5000 ns under
bench.step. Per op: fusion.1 15000 + 8000 + 18000 = 41000 ns, gather.2
20000 + 5000 = 25000 ns. Modules: jit_fused_body 30000 + 18000 =
48000 ns, jit_gather_tiles 8000 ns.
"""
import os

import pytest

from bench.lib import spec, xtrace

HERE = os.path.dirname(os.path.abspath(__file__))


def _events(meta, spans):
    return "".join(
        f"events {{ metadata_id: {meta[n]} offset_ps: {s * 1000} "
        f"duration_ps: {(e - s) * 1000} }}\n" for n, s, e in spans)


def _metadata(meta):
    return "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in meta.items())


HOST = {"bench.window": 1, "bench.step": 2, "bench.wait": 3,
        "bench.poll": 4}
DEV = {"fusion.1": 1, "gather.2": 2, "jit_fused_body(7)": 3,
       "jit_gather_tiles(3)": 4}
HAND = (
    'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "main" '
    'timestamp_ns: 0\n'
    + _events(HOST, [("bench.window", 0, 100000),
                     ("bench.step", 0, 40000), ("bench.wait", 40000, 60000),
                     ("bench.step", 60000, 90000),
                     ("bench.poll", 90000, 100000)])
    + "}\n" + _metadata(HOST) + "}\n"
    'planes { id: 2 name: "/device:TPU:0" lines { id: 1 name: "XLA Ops" '
    'timestamp_ns: 0\n'
    + _events(DEV, [("fusion.1", 5000, 20000), ("gather.2", 15000, 35000),
                    ("fusion.1", 62000, 70000), ("fusion.1", 70000, 88000),
                    ("gather.2", 95000, 110000),
                    ("fusion.1", 150000, 160000)])
    + '}\nlines { id: 2 name: "XLA Modules" timestamp_ns: 0\n'
    + _events(DEV, [("jit_fused_body(7)", 5000, 35000),
                    ("jit_gather_tiles(3)", 62000, 70000),
                    ("jit_fused_body(7)", 70000, 88000),
                    ("jit_fused_body(7)", 150000, 160000)])
    + "}\n" + _metadata(DEV) + "}\n")


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData
    return xtrace.reduce_xspace(ProfileData.from_text_proto(HAND))


def test_window_busy_and_idle(hand):
    assert hand["window_s"] == pytest.approx(100e-6)
    assert hand["busy_s"] == pytest.approx(61e-6)
    assert hand["chips"] == 1


def test_idle_gaps_named_by_host(hand):
    assert [g[0] for g in hand["idle_gaps"]] == ["bench.wait", "bench.poll",
                                                 "bench.step"]
    assert [g[1] for g in hand["idle_gaps"]] == pytest.approx(
        [27e-6, 7e-6, 5e-6])


def test_ops_and_modules(hand):
    assert [op[0] for op in hand["top_ops"]] == ["fusion.1", "gather.2"]
    assert [op[1] for op in hand["top_ops"]] == pytest.approx([41e-6,
                                                               25e-6])
    assert hand["module_s"] == pytest.approx(
        {"jit_fused_body": 48e-6, "jit_gather_tiles": 8e-6})


def test_metrics_read_from_the_trace(hand):
    ctx = {"trace": hand, "serve": {"valid_rows": 2000},
           "work": {"flops": 5888.0, "bytes": 239.0},
           "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9}}
    assert spec.reader("device_idle_share.fleet")(ctx) == pytest.approx(39.0)
    # (48 + 8) us over 2 krows
    assert spec.reader("program_us_per_krow.bulk")(ctx) == pytest.approx(28.0)
    # bytes bind: 2000 * 239 B / 819 GB/s over 56 us
    assert spec.reader("program_roofline.fleet")(ctx) == pytest.approx(
        100 * (2000 * 239 / 819e9) / 56e-6)


def test_no_window_or_no_device_reads_nothing():
    from jax.profiler import ProfileData
    host_only = HAND.split('planes { id: 2')[0]
    assert xtrace.reduce_xspace(ProfileData.from_text_proto(host_only)) \
        is None
    ctx = {"trace": None, "serve": {"valid_rows": 10}}
    assert spec.reader("program_roofline.bulk")(ctx) is None
    assert spec.reader("device_idle_share.bulk")(ctx) is None


def test_union_and_gaps():
    import numpy as np
    iv = np.array([[0, 10], [5, 20], [30, 40], [35, 36]], float)
    assert xtrace.union(iv).tolist() == [[0, 20], [30, 40]]
    assert xtrace.gaps(xtrace.union(iv), -5, 50).tolist() == [
        [-5, 0], [20, 30], [40, 50]]


# A trace recorded on one TPU v5e by ``bench/record_trace.py``: three
# ``bench.step`` calls of a jitted 1024 x 1024 matmul, each followed by
# a ``bench.wait``. Its events (ns, on the trace's own clock):
#
# host ``bench.window`` [48664086, 58370035); ``bench.step`` [48669026,
# 49348136), [51810686, 52674795), [55081145, 55896365); ``bench.wait``
# [49351376, 51801026), [52681535, 55076795), [55900845, 58367075).
#
# device ``XLA Ops``, per call a copy-start (13 ns), a copy-done (3, 2,
# 3 ns) and the fusion (11768, 11768, 11767 ns), starting at 47730311,
# 50871436 and 54058362; ``XLA Modules`` ``jit__lambda`` 11790, 11790
# and 11791 ns from 47730309, 50871434 and 54058359. The device's clock
# runs about 1 ms ahead of the host's here, so the first call's ops
# fall before the window and are left out.
#
# By hand: busy = (13 + 2 + 11768) + (13 + 3 + 11767) = 23566 ns; the
# module 11790 + 11791 = 23581 ns; the fusion 23535 ns. The three long
# idle gaps: [54070148, 58370035) 4299887 ns, most under the third wait
# (2466230 ns); [50883222, 54058362) 3175140 ns, most under the second
# wait (1376827); [48664086, 50871436) 2207350 ns, most under the first
# wait (1520060).
V5E = os.path.join(HERE, "data", "v5e_small.xplane.pb")


@pytest.fixture(scope="module")
def v5e():
    return xtrace.reduce_file(V5E)


def test_chip_trace_window_and_busy(v5e):
    assert v5e["chips"] == 1
    assert v5e["window_s"] == pytest.approx(9705949e-9)
    assert v5e["busy_s"] == pytest.approx(23566e-9)
    assert v5e["module_s"] == pytest.approx({"jit__lambda": 23581e-9})


def test_chip_trace_ops_and_gaps(v5e):
    top = v5e["top_ops"][0]
    assert top[0].startswith("%fusion = f32[1024]")
    assert top[1] == pytest.approx(23535e-9)
    assert [g[0] for g in v5e["idle_gaps"][:3]] == ["bench.wait"] * 3
    assert [g[1] for g in v5e["idle_gaps"][:3]] == pytest.approx(
        [4299887e-9, 3175140e-9, 2207350e-9])
