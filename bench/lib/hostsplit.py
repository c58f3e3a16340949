"""Split the device's idle time by what the program's host code was doing.

An enabled tracer of the program (``runtime/trace.py``) writes each of
its spans into a running profiler trace as the host annotation
``serve.<name>``: the stages ``serve.prepare``, ``serve.dispatch``
(holding ``serve.tiles`` and ``serve.launch``), ``serve.device_block``
and ``serve.scatter_retire`` (holding ``serve.stats``), and
``serve.submit`` beside them. This module reduces a trace
(``.xplane.pb``) holding them, within the benchmark's ``bench.window``
(``xtrace``), to:

* ``clock_offset_us``: what to add to a host timestamp to put it on the
  device's clock. The ``k``-th ``jit_fused_body`` module cannot start
  before the ``k``-th ``serve.launch`` began, and the ``k``-th
  ``serve.device_block`` cannot end before that module ended, so every
  offset in ``[max(module end - block end), min(module start - launch
  start)]`` keeps every pair causal; the offset is that range's middle.
  ``causal_pairs`` counts the pairs and ``causal_broken_us`` is the
  largest amount by which a pair is still broken after the shift (0
  when the range is not empty).
* ``host_self_s``: ``{name: [self seconds, spans]}`` per ``serve.*``
  name over the window, a span's self time being its duration less the
  part its nested spans cover.
* ``idle_by_span``: the device's idle seconds in the window (the gaps
  ``xtrace`` finds on the first busy chip, so they add up to its idle
  time) under the innermost ``serve.*`` span of the shifted host
  events, and ``"outside program"`` for the rest.
* ``stalls``: every idle gap of ``STALL_S`` or more as ``[start (s from
  the window's start), seconds, span, bench.* annotation]``: the label
  of ``idle_by_span`` that holds most of the gap, and the annotation
  (shifted too) that overlaps it most.

Spans are taken from the host thread holding the most ``serve.*``
events: the server is single-threaded. A trace without ``serve.*``
events (a program that does not annotate) reduces to ``None``.

``ring_us_per_krow`` reads the same stages from the tracer's ring
instead, as the harness hands them to its metric readers.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.lib import xtrace

STALL_S = 0.05
OUTSIDE = "outside program"
FUSED = "jit_fused_body"

Event = Tuple[str, float, float]            # name, start, end (ns)


def innermost(events: Sequence[Event]) -> List[Event]:
    """Properly nested ``events`` cut into disjoint segments, each
    labelled with the innermost event covering it, in time order."""
    segs: List[Event] = []
    stack: List[Tuple[str, float]] = []     # (name, end)
    cur = 0.0
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            n, end = stack.pop()
            segs.append((n, cur, end))
            cur = end
        if stack:
            segs.append((stack[-1][0], cur, s))
        stack.append((name, e))
        cur = s
    while stack:
        n, end = stack.pop()
        segs.append((n, cur, end))
        cur = end
    return [seg for seg in segs if seg[2] > seg[1]]


def _clip(segs: Sequence[Event], t0: float, t1: float) -> List[Event]:
    return [(n, max(s, t0), min(e, t1)) for n, s, e in segs
            if s < t1 and e > t0]


def _most(segs: Sequence[Event], g0: float, g1: float,
          default: str) -> str:
    best, name = 0.0, default
    for n, s, e in segs:
        ov = min(e, g1) - max(s, g0)
        if ov > best:
            best, name = ov, n
    return name


def offset(serve: Sequence[Event], modules: Sequence[Event]):
    """``(offset ns, pairs, broken ns)`` from the causal pairs, in
    order; ``(0.0, 0, 0.0)`` when there is no pair."""
    fused = sorted((s, e) for n, s, e in modules if n == FUSED)
    launch = sorted(s for n, s, _ in serve if n == "serve.launch")
    block = sorted((s, e) for n, s, e in serve
                   if n == "serve.device_block")
    ups = [f[0] - t for f, t in zip(fused, launch)]
    lows = [f[1] - b[1] for f, b in zip(fused, block)]
    if not ups and not lows:
        return 0.0, 0, 0.0
    up = min(ups) if ups else max(lows)
    low = max(lows) if lows else min(ups)
    off = (up + low) / 2
    return off, max(len(ups), len(lows)), max(0.0, low - off, off - up)


def reduce_xspace(pd, stall_s: float = STALL_S) -> Optional[Dict]:
    """``None`` when the trace has no window, no device ops in it, or
    no ``serve.*`` host events."""
    lines = [xtrace._events(line) for p in pd.planes
             if p.name == "/host:CPU" for line in p.lines]
    win = [ev for evs in lines for ev in evs if ev[0] == xtrace.WINDOW]
    counts = [sum(n.startswith("serve.") for n, _, _ in evs)
              for evs in lines]
    if not win or not any(counts):
        return None
    _, w0, wd = win[0]
    w1 = w0 + wd
    main = lines[int(np.argmax(counts))]
    serve = [(n, s, s + d) for n, s, d in main if n.startswith("serve.")]
    notes = [(n, s, s + d) for evs in lines for n, s, d in evs
             if n.startswith("bench.") and n != xtrace.WINDOW]
    device = None
    for plane in pd.planes:
        if not xtrace._DEVICE.match(plane.name):
            continue
        got = {line.name: xtrace._events(line) for line in plane.lines}
        ops = xtrace._intervals(
            [(s, d) for _, s, d in got.get("XLA Ops", [])], w0, w1)
        if len(ops):
            device = (ops, [(n.split("(", 1)[0], s, s + d)
                            for n, s, d in got.get("XLA Modules", [])])
            break
    if device is None:
        return None
    ops, modules = device
    idle = xtrace.gaps(xtrace.union(ops), w0, w1)
    off, pairs, broken = offset(serve, modules)

    segs = innermost(serve)
    host_self: Dict[str, List[float]] = {}
    for n, s, e in _clip(segs, w0, w1):
        host_self.setdefault(n, [0.0, 0])[0] += (e - s) * 1e-9
    for n, s, e in serve:
        if s < w1 and e > w0:
            host_self.setdefault(n, [0.0, 0])[1] += 1

    shifted = [(n, s + off, e + off) for n, s, e in segs]
    notes = [(n, s + off, e + off) for n, s, e in notes]
    by_span: Dict[str, float] = {}
    stalls = []
    j = 0
    for g0, g1 in idle:
        while j < len(shifted) and shifted[j][2] <= g0:
            j += 1
        gap = {OUTSIDE: g1 - g0}
        k = j
        while k < len(shifted) and shifted[k][1] < g1:
            n, s, e = shifted[k]
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                gap[n] = gap.get(n, 0.0) + ov
                gap[OUTSIDE] -= ov
            k += 1
        for n, v in gap.items():
            if v > 0:
                by_span[n] = by_span.get(n, 0.0) + v * 1e-9
        if g1 - g0 >= stall_s * 1e9:
            stalls.append([(g0 - w0) * 1e-9, (g1 - g0) * 1e-9,
                           max(gap, key=gap.get),
                           _most(notes, g0, g1, "host: none")])
    return {
        "window_s": wd * 1e-9,
        "clock_offset_us": off * 1e-3 if pairs else None,
        "causal_pairs": pairs,
        "causal_broken_us": broken * 1e-3,
        "host_self_s": host_self,
        "idle_by_span": by_span,
        "stalls": stalls,
    }


def reduce_file(path: str, stall_s: float = STALL_S) -> Optional[Dict]:
    from jax.profiler import ProfileData
    return reduce_xspace(ProfileData.from_file(path), stall_s)


# the host's phases, each with the ``detail`` spans nested in it: the four
# stages ``host_busy_share`` unites, and ``submit`` beside them
PHASES = {"submit": ("serve.submit",),
          "prepare": ("serve.prepare",),
          "dispatch": ("serve.dispatch", "serve.tiles", "serve.launch"),
          "block": ("serve.device_block",),
          "retire": ("serve.scatter_retire", "serve.stats")}


def phase_us_per_krow(split: Dict, valid_rows: int) -> Dict[str, float]:
    """Self time of each phase (with its nested spans) per 1000 valid
    rows, in us."""
    own = split["host_self_s"]
    return {p: sum(own.get(n, (0.0, 0))[0] for n in names) * 1e9
            / valid_rows for p, names in PHASES.items()}


def idle_shares(split: Dict) -> Dict[str, float]:
    """Of the window, in %: the device idle while the host is in a
    ``serve.*`` span other than ``serve.device_block``
    (``idle_in_program``), and while it is in ``serve.device_block``
    (``idle_in_block``)."""
    by = split["idle_by_span"]
    block = by.get("serve.device_block", 0.0)
    program = sum(v for n, v in by.items() if n != OUTSIDE) - block
    w = split["window_s"]
    return {"idle_in_program": 100.0 * program / w,
            "idle_in_block": 100.0 * block / w}


def ring_us_per_krow(ctx: Dict, stage: str) -> Optional[float]:
    """The ring buffer's ``serve``-category span ``stage`` (the spans
    ``host_busy_share`` reads; a stage's ring span holds its nested
    spans) per 1000 valid rows of the window, in us. Where the ring
    dropped spans, the kept spans' time is scaled from the part of the
    window they cover to the whole, as ``host_busy_share`` measures
    over that part."""
    spans, rows = ctx["spans"], ctx["serve"]["valid_rows"]
    if not spans or not rows:
        return None
    t0 = ctx["t0"] if not ctx["spans_dropped"] else min(
        s for _, s, _ in spans)
    covered = ctx["t1"] - t0
    if covered <= 0:
        return None
    total = sum(e - s for n, s, e in spans if n == stage)
    return total * (ctx["t1"] - ctx["t0"]) / covered * 1e9 / rows
