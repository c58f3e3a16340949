"""The open loop's polling costs what resolved, not what is out.

A stand-in server on a virtual clock stalls once, then serves at 1.25
times the arrival rate, and each ``done()`` it answers costs the loop
0.4 us of that clock (about what one costs the loop on a CPU). The open
loop keeps its futures in one FIFO per tenant and touches, per pass,
no more than the tenants with work out plus the futures that resolved,
so the backlog of more than 20 000 requests drains; the old loop, which
polled every outstanding future on every pass, touches them all and
falls behind for good. The FIFO order the loop relies on is asserted on
the real server.
"""
import argparse
import collections

import numpy as np
import pytest

from bench.lib import traffic, window
from bench.tests import tiny

RATE = 40_000           # requests (of one row) per second
SECONDS = 2.0
STALL_AT, STALL_S = 0.5, 0.55
STEP_S = 2e-3           # one server step serves 1.25 x RATE x STEP_S
DONE_S = 0.4e-6         # what one done() costs the polling loop
TENANTS = 256


class VirtualClock:
    """Time moves when the loop reads it (1 us a read), sleeps, or the
    server works."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 1e-6
        return self.now

    def sleep(self, s):
        self.now += s


class _Request:
    error = None

    def __init__(self, ids):
        self.ids = ids
        self.answers = np.zeros(len(ids), bool)


class _Future:
    def __init__(self, server, ids):
        self.server, self.request, self.resolved = server, _Request(ids), \
            False

    def done(self):
        self.server.touched += 1
        self.server.clock.now += DONE_S
        return self.resolved


class StandIn:
    """One global FIFO, so each tenant's requests resolve in order;
    records, for every pass, the futures the loop touched, the tenants
    that had work out and the futures the previous step resolved."""

    def __init__(self, clock):
        self.clock = clock
        self.queue = collections.deque()
        self.touched = 0
        self.passes = []            # (touched, active tenants, resolved)
        self.last = (0, 0)          # active tenants, resolved, last step
        self.stalled = False
        self.most_out = 0

    def submit_many(self, items):
        futs = [_Future(self, rows) for _, rows in items]
        self.queue.extend(zip(futs, [name for name, _ in items]))
        return futs

    def step(self):
        self.passes.append((self.touched,) + self.last)
        self.touched = 0
        self.most_out = max(self.most_out, len(self.queue))
        active = len({t for _, t in self.queue})
        if not self.stalled and self.clock.now >= STALL_AT:
            self.stalled = True
            self.clock.now += STALL_S
        self.clock.now += STEP_S
        n = min(len(self.queue), int(1.25 * RATE * STEP_S))
        for _ in range(n):
            f, _ = self.queue.popleft()
            f.resolved = True
        self.last = (active, n)


def _schedule():
    n = int(RATE * SECONDS)
    rng = np.random.default_rng(7)
    return traffic.Schedule(tenant=rng.integers(0, TENANTS, n),
                            rows=np.ones(n, np.int64),
                            offset=np.zeros(n, np.int64),
                            due=np.arange(n) / RATE)


class ScanAll(window.Outstanding):
    """The old loop's polling: every outstanding future, every pass."""

    def poll(self, on_done, sweep=False):
        return super().poll(on_done, sweep=True)


def _run(monkeypatch, outstanding):
    clock = VirtualClock()
    monkeypatch.setattr(window, "clock", clock)
    monkeypatch.setattr(window, "sleep", clock.sleep)
    monkeypatch.setattr(window, "Outstanding", outstanding)
    server = StandIn(clock)
    sched = _schedule()
    res = window.run_open(
        server, sched, [f"t{i}" for i in range(TENANTS)],
        [np.zeros((1, 7), np.int32)], lambda t: 0, seconds=SECONDS,
        annotate=False, drain_s=2.0)
    return server, res, sched


def test_poll_touches_only_active_tenants_and_resolved(monkeypatch):
    server, res, sched = _run(monkeypatch, window.Outstanding)
    assert server.most_out > 20_000
    for touched, active, resolved in server.passes:
        assert touched <= active + resolved
    # the backlog drains: every request answered, the last well before
    # the loop would have given up
    assert res.sent == len(sched)
    assert not np.isnan(res.t_done).any()
    assert res.t_end - res.t0 < SECONDS + 1.0


def test_old_loop_touches_every_outstanding_future(monkeypatch):
    server, res, sched = _run(monkeypatch, ScanAll)
    assert server.most_out > 20_000
    assert max(t for t, _, _ in server.passes) > 20_000
    # so the backlog never drains: requests are still out when the
    # loop gives up
    assert np.isnan(res.t_done).any()


def test_a_tenants_requests_resolve_in_submission_order():
    """The server resolves each tenant's requests in the order they were
    submitted, also under a backlog of requests split over batches:
    after every step, the resolved futures of each tenant are a prefix
    of its submissions (what the open loop's polling relies on)."""
    import jax
    from bench import run
    cfg, mix = tiny.config(), tiny.mix()
    args = argparse.Namespace(seed=tiny.SEED, trace=0)
    su = run.set_up(cfg, mix, args, {}, jax)
    sched = traffic.schedule(mix, len(su.names), tiny.SEED, 1.0)
    items = window._Items(sched, su.names, su.pools, su.rel_of)
    futs = su.server.submit_many(items[0:len(items)])
    by_tenant = collections.defaultdict(list)
    for f, t in zip(futs, sched.tenant):
        by_tenant[t].append(f)
    assert max(sched.rows) > 64 and len(futs) > 100
    steps = 0
    while not all(f.done() for f in futs):
        su.server.step()
        steps += 1
        for fs in by_tenant.values():
            done = [f.done() for f in fs]
            k = done.index(False) if False in done else len(done)
            assert not any(done[k:])
    su.server.close()
    assert steps > 3
