"""Span tracer unit tests: nesting, ring bounding, Chrome export.

The tracer is the substrate of the serving observability layer, so its
contracts are pinned here independently of any server: spans nest
per-thread (depth + parent), the ring buffer bounds memory and counts
drops, synthetic tracks get stable metadata tids, and the exported
file is valid Chrome trace-event JSON (``ph``/``ts``/``dur``) straight
through ``json.loads``.
"""
import json
import threading

import pytest

from repro.runtime.trace import _TRACK_BASE, NULL_TRACER, Span, Tracer


# ------------------------------------------------------------- recording

def test_span_records_wall_time():
    tr = Tracer()
    with tr.span("work", cat="test", rows=7):
        pass
    (s,) = tr.events()
    assert s.name == "work" and s.cat == "test"
    assert s.t_end >= s.t_start
    assert s.duration == s.t_end - s.t_start
    assert s.args == {"rows": 7}
    assert s.depth == 0 and s.parent is None
    assert s.tid == threading.get_ident()


def test_span_nesting_depth_and_parent():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            with tr.span("leaf"):
                pass
        with tr.span("sibling"):
            pass
    by_name = {s.name: s for s in tr.events()}
    assert by_name["outer"].depth == 0 and by_name["outer"].parent is None
    assert by_name["inner"].depth == 1 and by_name["inner"].parent == "outer"
    assert by_name["leaf"].depth == 2 and by_name["leaf"].parent == "inner"
    # the stack pops correctly: a sibling after `inner` closed is depth 1
    assert (by_name["sibling"].depth == 1
            and by_name["sibling"].parent == "outer")
    # inner spans record before outer ones (exit order)
    assert [s.name for s in tr.events()] == ["leaf", "inner", "sibling",
                                             "outer"]


def test_span_args_mutable_until_exit():
    """The instrumentation idiom: open the span, compute, then attach
    result args on the yielded object before __exit__ records it."""
    tr = Tracer()
    with tr.span("prepare") as sp:
        assert sp                        # truthy when enabled
        sp.args.update(bucket=256, tenant="a")
    (s,) = tr.events()
    assert s.args == {"bucket": 256, "tenant": "a"}


def test_nesting_is_per_thread():
    tr = Tracer()
    seen = {}

    def worker():
        with tr.span("t2"):
            seen["depth_in_thread"] = len(tr._stack())

    with tr.span("main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    # the worker's span never saw main's stack: depth 0, no parent
    t2 = next(s for s in tr.events() if s.name == "t2")
    assert t2.depth == 0 and t2.parent is None
    assert t2.tid != threading.get_ident()
    assert seen["depth_in_thread"] == 1


# --------------------------------------------------------- ring bounding

def test_ring_buffer_bounds_and_counts_drops():
    tr = Tracer(maxlen=8)
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 8
    assert tr.dropped == 12
    # the survivors are the NEWEST spans
    assert [s.name for s in tr.events()] == [f"s{i}" for i in range(12, 20)]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


# ------------------------------------------------------ synthetic tracks

def test_add_synthetic_track():
    tr = Tracer()
    t0 = tr.t_origin
    tr.add("device_compute", t0 + 0.001, t0 + 0.003, track="device",
           cat="device", args={"seq": 1})
    tr.add("device_compute", t0 + 0.004, t0 + 0.005, track="device")
    tr.add("h2d", t0 + 0.001, t0 + 0.002, track="copies")
    spans = tr.events()
    dev = [s for s in spans if s.name == "device_compute"]
    assert dev[0].tid == dev[1].tid == _TRACK_BASE
    copies = next(s for s in spans if s.name == "h2d")
    assert copies.tid == _TRACK_BASE + 1     # second track, next tid


# --------------------------------------------------------- disabled mode

def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    with tr.span("x") as sp:
        assert sp is None                   # the `if sp:` guard works
    tr.add("y", 0.0, 1.0, track="device")
    assert len(tr) == 0 and tr.dropped == 0
    assert tr.chrome_events() == []
    # the module-level shared null tracer is disabled too
    assert not NULL_TRACER.enabled and len(NULL_TRACER) == 0


def test_disabled_span_is_shared_singleton():
    tr = Tracer(enabled=False)
    assert tr.span("a") is tr.span("b")     # no per-call allocation


# ------------------------------------------------------- profiler sink

@pytest.mark.parametrize("enabled", [True, False])
def test_span_opens_one_profiler_annotation_iff_enabled(enabled):
    """Each span of an enabled tracer holds ``serve.<name>`` open for
    its duration (bare name: args stay out of the profiler's event
    name); a disabled tracer opens none, and ``add`` never does."""
    log = []

    class Note:
        def __init__(self, name):
            self.name = name
            log.append(("open", name))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            log.append(("close", self.name))
            return False

    tr = Tracer(enabled=enabled)
    tr._note_cls = Note
    with tr.span("prepare", rows=3):
        with tr.span("tiles", cat="detail"):
            pass
    tr.add("device_compute", 0.0, 1.0, track="device")
    if enabled:
        assert log == [("open", "serve.prepare"), ("open", "serve.tiles"),
                       ("close", "serve.tiles"), ("close", "serve.prepare")]
        assert [s.name for s in tr.events()] == ["tiles", "prepare",
                                                 "device_compute"]
    else:
        assert log == [] and len(tr) == 0


def test_annotation_closes_when_the_span_raises():
    closed = []

    class Note:
        def __init__(self, name):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            closed.append(exc[0])
            return False

    tr = Tracer()
    tr._note_cls = Note
    with pytest.raises(KeyError):
        with tr.span("dispatch"):
            raise KeyError("x")
    assert closed == [KeyError] and len(tr) == 1


def test_profiler_annotation_is_imported_by_the_first_span():
    from jax.profiler import TraceAnnotation
    tr = Tracer()
    assert tr._note_cls is None
    with tr.span("x"):
        pass
    assert tr._note_cls is TraceAnnotation


# --------------------------------------------------------- Chrome export

def test_chrome_trace_round_trip(tmp_path):
    tr = Tracer()
    with tr.span("outer", cat="serve", seq=3):
        with tr.span("inner"):
            pass
    t0 = tr.t_origin
    tr.add("device_compute", t0 + 0.01, t0 + 0.02, track="device",
           cat="device", args={"seq": 3})
    path = str(tmp_path / "trace.json")
    assert tr.to_chrome_trace(path) == path

    with open(path) as f:
        payload = json.loads(f.read())
    assert set(payload) == {"traceEvents", "displayTimeUnit"}
    events = payload["traceEvents"]

    meta = [e for e in events if e["ph"] == "M"]
    assert {"device"} == {e["args"]["name"] for e in meta}
    assert all(e["name"] == "thread_name" for e in meta)

    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"outer", "inner", "device_compute"}
    for e in xs:
        # well-formed complete events: µs offsets from the origin
        assert isinstance(e["ts"], float) and e["ts"] >= 0.0
        assert isinstance(e["dur"], float) and e["dur"] >= 0.0
        assert e["pid"] == 0 and isinstance(e["tid"], int)
        assert e["cat"] in ("serve", "device")

    by_name = {e["name"]: e for e in xs}
    assert by_name["outer"]["args"]["seq"] == 3
    assert by_name["inner"]["args"]["parent"] == "outer"
    dev = by_name["device_compute"]
    assert dev["tid"] == _TRACK_BASE
    assert dev["dur"] == pytest.approx(10_000.0, rel=1e-6)   # 10ms in µs
    # nesting consistency: inner sits inside outer on the timeline
    assert by_name["outer"]["ts"] <= by_name["inner"]["ts"]
    assert (by_name["inner"]["ts"] + by_name["inner"]["dur"]
            <= by_name["outer"]["ts"] + by_name["outer"]["dur"] + 1e-6)


def test_chrome_export_with_fake_clock():
    """Deterministic export: drive the tracer with a fake clock and pin
    the exact µs arithmetic."""
    t = [0.0]

    def clock():
        t[0] += 0.5
        return t[0]

    tr = Tracer(clock=clock)               # origin = 0.5
    with tr.span("a"):                     # start = 1.0, end = 1.5
        pass
    (ev,) = [e for e in tr.chrome_events() if e["ph"] == "X"]
    assert ev["ts"] == pytest.approx(0.5e6)
    assert ev["dur"] == pytest.approx(0.5e6)


def test_empty_args_omitted_from_export():
    tr = Tracer()
    with tr.span("idle"):
        pass
    (ev,) = [e for e in tr.chrome_events() if e["ph"] == "X"]
    assert "args" not in ev
