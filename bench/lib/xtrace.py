"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The window is the host annotation ``bench.window``. Within it:

* ``busy_s``: the union of the device's op intervals (the ``XLA Ops``
  line of each ``/device:TPU:<n>`` plane), averaged over the chips that
  ran anything;
* ``module_s``: device seconds per compiled module (the ``XLA Modules``
  line), keyed by module name without its ``(id)`` suffix;
* ``top_ops``: device seconds per op name, largest first;
* ``idle_gaps``: the stretches of the window in which no op ran on the
  first busy chip, longest first, each named by the ``bench.*`` host
  annotation that overlaps it most (``"host: none"`` when none does).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def options():
    """Profiler options of a traced run: the host tracer at the level of
    ``TraceAnnotation``, Python's own calls left out, so the trace holds
    the benchmark's annotations and the device's ops, not every function
    call of the window."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _intervals(events, t0: float, t1: float) -> np.ndarray:
    """(n, 2) [start, end) in ns, clipped to the window, sorted."""
    iv = [(max(s, t0), min(s + d, t1)) for s, d in events
          if s < t1 and s + d > t0]
    if not iv:
        return np.zeros((0, 2))
    a = np.asarray(iv, np.float64)
    return a[np.argsort(a[:, 0], kind="stable")]


def union(iv: np.ndarray) -> np.ndarray:
    """Merge sorted intervals: (m, 2) disjoint spans."""
    if not len(iv):
        return iv
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(iv) - 1)
    return np.stack([iv[first, 0], ends[last]], axis=1)


def gaps(busy: np.ndarray, t0: float, t1: float) -> np.ndarray:
    """The complement of disjoint ``busy`` spans within [t0, t1)."""
    edges = np.concatenate([[t0], busy.reshape(-1), [t1]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def reduce_xspace(pd, top: int = 10) -> Optional[Dict]:
    """``None`` when the trace has no window or no device ops in it."""
    host = [p for p in pd.planes if p.name == "/host:CPU"]
    notes = [ev for p in host for line in p.lines for ev in _events(line)
             if ev[0].startswith("bench.")]
    win = [ev for ev in notes if ev[0] == WINDOW]
    if not win:
        return None
    _, w0, wd = win[0]
    w1 = w0 + wd
    chips = []
    for plane in pd.planes:
        if not _DEVICE.match(plane.name):
            continue
        lines = {line.name: _events(line) for line in plane.lines}
        ops = _intervals([(s, d) for _, s, d in lines.get("XLA Ops", [])],
                         w0, w1)
        if not len(ops):
            continue
        per_op: Dict[str, float] = {}
        for name, s, d in lines.get("XLA Ops", []):
            c = min(s + d, w1) - max(s, w0)
            if c > 0:
                per_op[name] = per_op.get(name, 0.0) + c
        per_mod: Dict[str, float] = {}
        for name, s, d in lines.get("XLA Modules", []):
            c = min(s + d, w1) - max(s, w0)
            if c > 0:
                key = name.split("(", 1)[0]
                per_mod[key] = per_mod.get(key, 0.0) + c
        chips.append((union(ops), per_op, per_mod))
    if not chips:
        return None
    busy = [float((u[:, 1] - u[:, 0]).sum()) for u, _, _ in chips]
    per_op: Dict[str, float] = {}
    per_mod: Dict[str, float] = {}
    for _, ops, mods in chips:
        for k, v in ops.items():
            per_op[k] = per_op.get(k, 0.0) + v / len(chips)
        for k, v in mods.items():
            per_mod[k] = per_mod.get(k, 0.0) + v / len(chips)
    idle = gaps(chips[0][0], w0, w1)
    acts = [(max(s, w0), min(s + d, w1), n) for n, s, d in notes
            if n != WINDOW and s < w1 and s + d > w0]
    spans = np.asarray([(a, b) for a, b, _ in acts]).reshape(-1, 2)
    named = []
    for g0, g1 in idle[np.argsort(idle[:, 0] - idle[:, 1],
                                  kind="stable")][:top]:
        name = "host: none"
        if len(acts):
            ov = np.minimum(spans[:, 1], g1) - np.maximum(spans[:, 0], g0)
            j = int(np.argmax(ov))
            if ov[j] > 0:
                name = acts[j][2]
        named.append([name, (g1 - g0) * 1e-9])
    return {
        "window_s": wd * 1e-9,
        "busy_s": float(np.mean(busy)) * 1e-9,
        "chips": len(chips),
        "module_s": {k: v * 1e-9 for k, v in per_mod.items()},
        "top_ops": [[k, v * 1e-9] for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
    }


def reduce_file(path: str, top: int = 10) -> Optional[Dict]:
    from jax.profiler import ProfileData
    return reduce_xspace(ProfileData.from_file(path), top)
