"""The measured window: one single-threaded loop, as the server is.

Each pass submits what is due, calls ``server.step()`` once and stamps,
on the benchmark's clock (``time.perf_counter``), every request whose
future resolved in that step. An open-loop request is timed from when
it was due; a closed-loop client sends its next request as soon as the
last one resolved.

With ``annotate`` the loop's own calls are wrapped in
``jax.profiler.TraceAnnotation`` (``bench.submit``, ``bench.step``,
``bench.poll``, ``bench.wait``), so a device trace can say what the
host was doing in each idle gap.
"""
from __future__ import annotations

import bisect
import dataclasses
import time
from typing import List, Optional

import numpy as np

from bench.lib.traffic import Schedule

clock = time.perf_counter


class _Null:
    def __init__(self, name):
        pass

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


def _annotation(annotate: bool):
    if not annotate:
        return _Null
    import jax
    return jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Result:
    """What the window produced. Sent request ``k`` is schedule entry
    ``sched_idx[k]``; its answers are ``answers[start[k]:start[k] +
    rows]``; ``t_done`` on the benchmark's clock (NaN if it never
    resolved); ``failed`` if it resolved with an error."""
    t0: float
    t_end: float                   # when the window's accounting closed
    sent: int                      # requests sent
    sched_idx: np.ndarray
    start: np.ndarray
    answers: np.ndarray
    t_done: np.ndarray
    failed: np.ndarray
    late: Optional[np.ndarray]     # open loop: submit - due (s)
    latency: Optional[np.ndarray]  # open loop: resolved - due (s)
    counted: np.ndarray            # resolved inside the window


def _starts(rows: np.ndarray) -> np.ndarray:
    return np.concatenate([[0], np.cumsum(rows)[:-1]]).astype(np.int64)


class _Items:
    """``items[i:j]``: the ``(tenant name, rows)`` of schedule entries
    ``i`` to ``j``, each a view of its relation's pool, made as the
    loop sends them."""

    def __init__(self, sched: Schedule, tenants: List[str], pools,
                 relation_of):
        self.name = list(tenants)
        self.pool = [pools[relation_of(t)] for t in range(len(tenants))]
        self.tenant = sched.tenant.tolist()
        self.offset = sched.offset.tolist()
        self.rows = sched.rows.tolist()

    def __len__(self) -> int:
        return len(self.rows)

    def __getitem__(self, sl: slice):
        return [(self.name[t], self.pool[t][o:o + n])
                for t, o, n in zip(self.tenant[sl], self.offset[sl],
                                   self.rows[sl])]

    def cycle(self, i: int, n: int):
        """``n`` entries from ``i``, wrapping round at the end."""
        k = len(self)
        i %= k
        out = self[i:i + n]
        while len(out) < n:
            out += self[0:n - len(out)]
        return out


def run_open(server, sched: Schedule, tenants, pools, relation_of, *,
             seconds: float, annotate: bool, drain_s: float) -> Result:
    Ann = _annotation(annotate)
    items = _Items(sched, tenants, pools, relation_of)
    n = len(items)
    start = _starts(sched.rows)
    answers = np.zeros(int(sched.rows.sum()), bool)
    t_done = np.full(n, np.nan)
    failed = np.zeros(n, bool)
    late = np.full(n, np.nan)
    due_rel = sched.due.tolist()
    outstanding = []
    i = 0
    t0 = clock()
    due_abs = t0 + sched.due
    give_up = t0 + seconds + drain_s
    while True:
        now = clock()
        j = bisect.bisect_right(due_rel, now - t0, i)
        if j > i:
            with Ann("bench.submit"):
                futs = server.submit_many(items[i:j])
            late[i:j] = clock() - due_abs[i:j]
            outstanding.extend(zip(futs, range(i, j)))
            i = j
        if outstanding:
            with Ann("bench.step"):
                server.step()
            with Ann("bench.poll"):
                t = clock()
                still = []
                for f, k in outstanding:
                    if f.done():
                        t_done[k] = t
                        req = f.request
                        if req.error is None:
                            answers[start[k]:start[k] + req.ids.shape[0]] \
                                = req.answers
                        else:
                            failed[k] = True
                    else:
                        still.append((f, k))
                outstanding = still
            if t > give_up:
                break
        elif i >= n:
            break
        else:
            with Ann("bench.wait"):
                gap = due_abs[i] - clock()
                if gap > 2e-3:
                    time.sleep(gap - 1e-3)
                while clock() < due_abs[i]:
                    pass
    t_end = max(t0 + seconds, float(np.nanmax(t_done)) if n else t0)
    return Result(t0=t0, t_end=t_end, sent=i, sched_idx=np.arange(i),
                  start=start, answers=answers,
                  t_done=t_done, failed=failed, late=late,
                  latency=t_done - due_abs,
                  counted=~np.isnan(t_done))


def run_closed(server, sched: Schedule, tenants, pools, relation_of, *,
               clients: int, seconds: float, annotate: bool,
               drain_s: float) -> Result:
    """``clients`` callers, each with one request outstanding, drawing
    requests from ``sched`` in order, round and round, until the window
    closes; the requests still out then are drained and checked, but
    not counted. Answers are kept bit-packed until the window has
    closed."""
    Ann = _annotation(annotate)
    items = _Items(sched, tenants, pools, relation_of)
    n = len(items)
    packed, t_done, failed = [], [], []
    t0 = clock()
    t_close = t0 + seconds
    t_end = None
    with Ann("bench.submit"):
        futs = server.submit_many(items.cycle(0, clients))
    outstanding = list(zip(futs, range(clients)))
    packed += [None] * clients
    t_done += [np.nan] * clients
    failed += [False] * clients
    i = clients
    while outstanding:
        with Ann("bench.step"):
            server.step()
        with Ann("bench.poll"):
            t = clock()
            still, freed = [], 0
            for f, k in outstanding:
                if f.done():
                    t_done[k] = t
                    req = f.request
                    if req.error is None:
                        packed[k] = np.packbits(req.answers)
                    else:
                        failed[k] = True
                    freed += 1
                else:
                    still.append((f, k))
            outstanding = still
        if t_end is None and t >= t_close:
            t_end = t
        if t_end is None and freed:
            with Ann("bench.submit"):
                futs = server.submit_many(items.cycle(i, freed))
            outstanding.extend(zip(futs, range(i, i + freed)))
            packed += [None] * freed
            t_done += [np.nan] * freed
            failed += [False] * freed
            i += freed
        if t > t_close + drain_s:
            break
    if t_end is None:
        t_end = clock()
    sched_idx = np.arange(i) % n
    rows = sched.rows[sched_idx]
    start = _starts(rows)
    answers = np.zeros(int(rows.sum()), bool)
    for k in range(i):
        if packed[k] is not None:
            answers[start[k]:start[k] + rows[k]] = np.unpackbits(
                packed[k], count=int(rows[k])).astype(bool)
    t_done = np.asarray(t_done, float)
    return Result(t0=t0, t_end=t_end, sent=i, sched_idx=sched_idx,
                  start=start, answers=answers, t_done=t_done,
                  failed=np.asarray(failed, bool), late=None,
                  latency=None, counted=t_done <= t_end)
