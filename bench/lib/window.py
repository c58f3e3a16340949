"""The measured window: one single-threaded loop, as the server is.

Each pass submits what is due, calls ``server.step()`` once and stamps,
on the benchmark's clock (``time.perf_counter``), every request whose
future resolved in that step. An open-loop request is timed from when
it was due; a closed-loop client sends its next request as soon as the
last one resolved.

The futures still out are kept in one FIFO per tenant
(:class:`Outstanding`), and a pass polls each tenant's queue only up to
its first future that has not resolved. That is exact because the
server resolves a tenant's requests in the order they were submitted
(its queue is FIFO and batches retire in dispatch order), and it keeps
a pass's cost to the futures that resolved plus the tenants with work
out, so a backlog does not slow the loop that has to drain it.

With ``annotate`` the loop's own calls are wrapped in
``jax.profiler.TraceAnnotation`` (``bench.submit``, ``bench.step``,
``bench.poll``, ``bench.wait``), so a device trace can say what the
host was doing in each idle gap.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import time
from typing import List, Optional

import numpy as np

from bench.lib.traffic import Schedule

clock = time.perf_counter
sleep = time.sleep


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


class Outstanding:
    """The futures not yet resolved, in one FIFO per tenant, in the
    order they were submitted."""

    def __init__(self):
        self._queues = {}
        self._n = 0

    def __len__(self) -> int:
        return self._n

    def add(self, futures, ks, tenants) -> None:
        """Request ``ks[j]`` of tenant ``tenants[j]`` went out as
        ``futures[j]``."""
        queues = self._queues
        for f, k, t in zip(futures, ks, tenants):
            q = queues.get(t)
            if q is None:
                q = queues[t] = collections.deque()
            q.append((f, k))
        self._n += len(futures)

    def poll(self, on_done, sweep: bool = False) -> int:
        """``on_done(future, k)`` for each resolved future, in each
        tenant's order, up to the tenant's first unresolved one; with
        ``sweep``, every resolved future. Returns how many resolved."""
        got = 0
        for t in list(self._queues):
            q = self._queues[t]
            if sweep:
                still = collections.deque()
                for f, k in q:
                    if f.done():
                        on_done(f, k)
                        got += 1
                    else:
                        still.append((f, k))
                q = self._queues[t] = still
            else:
                while q and q[0][0].done():
                    f, k = q.popleft()
                    on_done(f, k)
                    got += 1
            if not q:
                del self._queues[t]
        self._n -= got
        return got


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
    outstanding = Outstanding()
    t = 0.0

    def on_done(f, k):
        t_done[k] = t
        req = f.request
        if req.error is None:
            answers[start[k]:start[k] + req.ids.shape[0]] = req.answers
        else:
            failed[k] = True

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
            outstanding.add(futs, range(i, j), items.tenant[i:j])
            i = j
        if outstanding:
            with Ann("bench.step"):
                server.step()
            with Ann("bench.poll"):
                t = clock()
                outstanding.poll(on_done)
            if t > give_up:
                # nothing resolved may go unseen behind a head that
                # never did
                outstanding.poll(on_done, sweep=True)
                break
        elif i >= n:
            break
        else:
            with Ann("bench.wait"):
                gap = due_abs[i] - clock()
                if gap > 2e-3:
                    sleep(gap - 1e-3)
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
    outstanding = Outstanding()
    t = 0.0

    def on_done(f, k):
        t_done[k] = t
        req = f.request
        if req.error is None:
            packed[k] = np.packbits(req.answers)
        else:
            failed[k] = True

    def send(i, m):
        with Ann("bench.submit"):
            futs = server.submit_many(items.cycle(i, m))
        outstanding.add(futs, range(i, i + m),
                        [items.tenant[k % n] for k in range(i, i + m)])
        packed.extend([None] * m)
        t_done.extend([np.nan] * m)
        failed.extend([False] * m)

    t0 = clock()
    t_close = t0 + seconds
    t_end = None
    send(0, clients)
    i = clients
    while outstanding:
        with Ann("bench.step"):
            server.step()
        with Ann("bench.poll"):
            t = clock()
            freed = outstanding.poll(on_done)
        if t_end is None and t >= t_close:
            t_end = t
        if t_end is None and freed:
            send(i, freed)
            i += freed
        if t > t_close + drain_s:
            outstanding.poll(on_done, sweep=True)
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
