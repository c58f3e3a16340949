"""Fleet router: placement, replication, failover, rebalance — all
in-process (real ``FilterServer`` hosts behind ``InProcessTransport``).

The contracts pinned here:

* ring placement is deterministic and moves minimally on host loss;
* routed answers are BIT-IDENTICAL to direct ``ExistenceIndex.query``
  through replica fan-out, host kill mid-traffic, degraded replicas,
  total-loss recovery, and a live rebalance;
* the three failure paths from the issue: host unreachable at admit
  (backoff retry -> next replica), host kill mid-query (failover,
  answers bit-identical), rebalance interrupted between
  target-SERVING and source-DRAINING (the tenant is never unowned);
* the ``router_*`` snapshot schema is pinned and its counters account
  for every placement/failover/rebalance event.
"""
import os
import sys
import time

import numpy as np
import pytest

from repro.core import existence
from repro.data import tuples
from repro.serve_filter import (FilterServer, ReliabilityConfig,
                                ServeConfig, TenantSpec, TenantState)
from repro.serve_filter.faults import FilterServeError
from repro.serve_filter.fleet import (ROUTER_SNAPSHOT_KEYS, FilterRouter,
                                      HashRing, HostAgent, HostTransport,
                                      HostUnreachable, InProcessTransport,
                                      launch_host)

N_HOSTS = 3


@pytest.fixture(scope="module")
def fleet():
    st = existence.TrainSettings(steps=15, n_pos=800, n_neg=800)
    out = {}
    for name, (cards, theta, seed) in {
            "alpha": ([300, 200, 80], 100, 3),
            "beta": ([500, 150], 120, 4)}.items():
        ds = tuples.synthesize(cards, n_records=900, seed=seed)
        out[name] = (ds, existence.fit(ds, theta=theta, settings=st))
    return out


@pytest.fixture(scope="module")
def checkpoints(fleet, tmp_path_factory):
    root = tmp_path_factory.mktemp("fleet-ckpt")
    for name, (_, idx) in fleet.items():
        existence.save_index(os.path.join(str(root), name), idx, step=0)
    return str(root)


def _probes(ds, n, seed):
    rng = np.random.default_rng(seed)
    pos = ds.records[rng.integers(0, len(ds.records), n // 2)]
    neg = np.stack([rng.integers(1, v, n - n // 2) for v in ds.cards],
                   axis=-1).astype(np.int32)
    return np.concatenate([pos, neg])


class FlakyTransport(HostTransport):
    """Wraps a real transport with scripted failures: per-op failure
    budgets and a hard ``dead`` switch (simulates a killed host)."""

    def __init__(self, inner: HostTransport):
        self.inner = inner
        self.fail_ops = {}          # op -> remaining forced failures
        self.dead = False
        self.requests = []

    def request(self, msg):
        op = msg.get("op")
        self.requests.append(op)
        if self.dead:
            raise HostUnreachable("flaky", "host is dead")
        if self.fail_ops.get(op, 0) > 0:
            self.fail_ops[op] -= 1
            raise HostUnreachable("flaky", f"scripted {op} failure")
        return self.inner.request(msg)


def _make_router(checkpoints, *, replicas=2, retries=1, seed=0,
                 load_slack=None, n_hosts=N_HOSTS):
    """Fresh hosts + flaky-wrapped transports + a router; no tenants
    admitted yet."""
    agents = {f"h{i}": HostAgent(FilterServer(ServeConfig()),
                                 name=f"h{i}")
              for i in range(n_hosts)}
    transports = {h: FlakyTransport(InProcessTransport(a))
                  for h, a in agents.items()}
    rel = ReliabilityConfig(retries=retries, backoff_base_s=1e-4,
                            backoff_cap_s=1e-3)
    router = FilterRouter(dict(transports), replicas=replicas,
                          reliability=rel, seed=seed,
                          load_slack=load_slack, sleep=lambda s: None)
    return router, agents, transports


# ---------------------------------------------------------------- ring
def test_ring_deterministic_and_distinct():
    a = HashRing([f"h{i}" for i in range(5)], seed=11)
    b = HashRing([f"h{i}" for i in range(5)], seed=11)
    for t in range(40):
        owners = a.owners(f"tenant-{t}", 3)
        assert owners == b.owners(f"tenant-{t}", 3)
        assert len(set(owners)) == 3
    assert a.owners("t", 99) == a.owners("t", 5)   # capped at ring size


def test_ring_minimal_movement_on_host_loss():
    hosts = [f"h{i}" for i in range(5)]
    before = HashRing(hosts, seed=2)
    placed = {f"tenant-{t}": before.owners(f"tenant-{t}", 1)[0]
              for t in range(60)}
    after = HashRing(hosts, seed=2)
    after.remove("h3")
    moved = sum(1 for t, h in placed.items()
                if h != "h3" and after.owners(t, 1)[0] != h)
    assert moved == 0, "losing h3 must only re-place h3's tenants"


def test_ring_seed_changes_layout():
    hosts = [f"h{i}" for i in range(4)]
    a, b = HashRing(hosts, seed=0), HashRing(hosts, seed=1)
    assert any(a.owners(f"t{t}", 1) != b.owners(f"t{t}", 1)
               for t in range(30))


# --------------------------------------------------- placement + query
def test_admit_places_on_ring_owners_and_answers_bit_equal(
        fleet, checkpoints):
    router, agents, _ = _make_router(checkpoints)
    for name in fleet:
        owners = router.admit(TenantSpec(name, checkpoint=checkpoints))
        assert len(owners) == 2 and len(set(owners)) == 2
        for h in owners:
            assert agents[h].server.registry.state_of(name) \
                   is TenantState.SERVING
    for r in range(3):
        for name, (ds, idx) in fleet.items():
            p = _probes(ds, 96, seed=10 + r)
            assert np.array_equal(router.query(name, p),
                                  np.asarray(idx.query(p)))
    snap = router.stats_snapshot()
    assert snap["router_placements"] == 2 * len(fleet)
    assert snap["router_replica_placements"] == len(fleet)
    assert snap["router_queries"] == 3 * len(fleet)
    assert snap["router_failovers"] == 0


def test_replica_fanout_is_deterministic(fleet, checkpoints):
    router, _, transports = _make_router(checkpoints)
    owners = router.admit(TenantSpec("alpha", checkpoint=checkpoints))
    ds, _ = fleet["alpha"]
    p = _probes(ds, 32, seed=0)
    seen = []
    for _ in range(6):
        before = {h: len(t.requests) for h, t in transports.items()}
        router.query("alpha", p)
        hit = [h for h, t in transports.items()
               if len(t.requests) > before[h]]
        assert len(hit) == 1
        seen.append(hit[0])
    # strict per-tenant round-robin over the owner list
    assert seen == [owners[i % len(owners)] for i in range(6)]
    assert router.stats_snapshot()["router_fanout_queries"] == 3


def test_unplaced_tenant_raises(checkpoints):
    router, _, _ = _make_router(checkpoints)
    with pytest.raises(KeyError):
        router.query("ghost", np.zeros((1, 2), dtype=np.int32))


# ------------------------------------------------------- failure paths
def test_admit_retries_then_next_replica(fleet, checkpoints):
    """Host unreachable at admit: the router burns its backoff retries
    on the preferred owner, then fails over to the next ring host."""
    router, agents, transports = _make_router(checkpoints, replicas=1,
                                              retries=1)
    ring_order = router._ring.owners("alpha", N_HOSTS)
    # the preferred host refuses every admit attempt (1 + 1 retry)
    transports[ring_order[0]].fail_ops["admit"] = 99
    owners = router.admit(TenantSpec("alpha", checkpoint=checkpoints))
    assert owners == (ring_order[1],)
    assert "alpha" not in agents[ring_order[0]].server.registry
    snap = router.stats_snapshot()
    assert snap["router_admit_retries"] == 1     # the backoff schedule
    assert snap["router_failovers"] == 1         # the diverted placement
    ds, idx = fleet["alpha"]
    p = _probes(ds, 64, seed=5)
    assert np.array_equal(router.query("alpha", p),
                          np.asarray(idx.query(p)))


def test_transient_admit_failure_recovers_in_place(fleet, checkpoints):
    """One scripted admit failure within the retry budget stays on the
    preferred host — failover is a last resort, not a first response."""
    router, _, transports = _make_router(checkpoints, replicas=1,
                                         retries=2)
    ring_order = router._ring.owners("alpha", N_HOSTS)
    transports[ring_order[0]].fail_ops["admit"] = 1
    owners = router.admit(TenantSpec("alpha", checkpoint=checkpoints))
    assert owners == (ring_order[0],)
    assert router.stats_snapshot()["router_failovers"] == 0


def test_host_kill_mid_query_fails_over_bit_identical(fleet,
                                                      checkpoints):
    """The replica answering a tenant dies mid-run: subsequent queries
    divert to the surviving replica with bit-identical answers and the
    failover counter accounts for every diverted block."""
    router, _, transports = _make_router(checkpoints)
    for name in fleet:
        router.admit(TenantSpec(name, checkpoint=checkpoints))
    ds, idx = fleet["alpha"]
    for r in range(2):                       # healthy warm-up traffic
        p = _probes(ds, 64, seed=20 + r)
        assert np.array_equal(router.query("alpha", p),
                              np.asarray(idx.query(p)))
    victim = router.owners("alpha")[0]
    transports[victim].dead = True           # kill: every op now EOFs
    baseline = router.stats_snapshot()["router_failovers"]
    diverted = 0
    for r in range(4):
        p = _probes(ds, 64, seed=40 + r)
        assert np.array_equal(router.query("alpha", p),
                              np.asarray(idx.query(p)))
        if router._qcount["alpha"] % 2 == 1:  # planned pick was victim
            diverted += 1
    snap = router.stats_snapshot()
    assert snap["router_failovers"] - baseline == diverted > 0
    assert snap["router_hosts_down"] == 1.0


def test_all_replicas_lost_recovers_from_checkpoint(fleet, checkpoints):
    """Total loss: every owner dead. The router re-places the tenant
    from its retained wire spec on the surviving ring and answers."""
    router, agents, transports = _make_router(checkpoints)
    owners = router.admit(TenantSpec("alpha", checkpoint=checkpoints))
    for h in owners:
        transports[h].dead = True
    survivor = next(h for h in transports if h not in owners)
    ds, idx = fleet["alpha"]
    p = _probes(ds, 64, seed=7)
    assert np.array_equal(router.query("alpha", p),
                          np.asarray(idx.query(p)))
    assert router.owners("alpha") == (survivor,)
    assert agents[survivor].server.registry.state_of("alpha") \
           is TenantState.SERVING
    snap = router.stats_snapshot()
    assert snap["router_recoveries"] == 1
    assert snap["router_unowned_tenants"] == 0


def test_degraded_replica_is_passed_over(fleet, checkpoints):
    """A DEGRADED replica diverts queries to a healthy one; its
    conservative answers are used only when nothing better exists."""
    router, _, transports = _make_router(checkpoints)
    owners = router.admit(TenantSpec("alpha", checkpoint=checkpoints))

    class DegradedReply(HostTransport):
        def __init__(self, inner):
            self.inner = inner

        def request(self, msg):
            reply = self.inner.request(msg)
            if msg.get("op") == "query":
                reply = dict(reply, degraded=True,
                             state=TenantState.DEGRADED.value)
            return reply

    router._hosts[owners[0]] = DegradedReply(transports[owners[0]])
    ds, idx = fleet["alpha"]
    for r in range(4):
        p = _probes(ds, 64, seed=60 + r)
        assert np.array_equal(router.query("alpha", p),
                              np.asarray(idx.query(p)))
    snap = router.stats_snapshot()
    assert snap["router_degraded_replies"] == 0    # healthy replica won
    assert snap["router_failovers"] == 2           # the diverted picks
    # now degrade BOTH replicas: the conservative answer is the last
    # resort and is counted as such
    router._hosts[owners[1]] = DegradedReply(transports[owners[1]])
    p = _probes(ds, 64, seed=99)
    got = router.query("alpha", p)
    direct = np.asarray(idx.query(p))
    assert got[direct].all()     # degraded stays zero-false-negative
    assert router.stats_snapshot()["router_degraded_replies"] == 1


# ------------------------------------------------------------ rebalance
def test_rebalance_migrates_via_lifecycle(fleet, checkpoints):
    router, agents, _ = _make_router(checkpoints, replicas=1)
    src = router.admit(TenantSpec("beta", checkpoint=checkpoints))[0]
    dst = next(h for h in agents if h != src)
    owners = router.rebalance("beta", dst)
    assert owners == (dst,)
    assert agents[dst].server.registry.state_of("beta") \
           is TenantState.SERVING
    assert "beta" not in agents[src].server.registry     # drained away
    ds, idx = fleet["beta"]
    p = _probes(ds, 64, seed=8)
    assert np.array_equal(router.query("beta", p),
                          np.asarray(idx.query(p)))
    assert router.stats_snapshot()["router_rebalances"] == 1


def test_rebalance_interrupted_never_leaves_tenant_unowned(
        fleet, checkpoints):
    """Interrupt the migration between target-SERVING and
    source-DRAINING (the drain op dies): the tenant stays owned — by
    BOTH hosts — keeps answering, and re-running the same rebalance
    completes it."""
    router, agents, transports = _make_router(checkpoints, replicas=1)
    src = router.admit(TenantSpec("beta", checkpoint=checkpoints))[0]
    dst = next(h for h in agents if h != src)
    transports[src].fail_ops["drain"] = 1
    with pytest.raises(FilterServeError, match="drain"):
        router.rebalance("beta", dst)
    owners = router.owners("beta")
    assert set(owners) == {src, dst}, "interruption must double-own"
    assert router.stats_snapshot()["router_unowned_tenants"] == 0
    assert agents[dst].server.registry.state_of("beta") \
           is TenantState.SERVING
    ds, idx = fleet["beta"]
    for r in range(2):                 # serving continues while split
        p = _probes(ds, 64, seed=70 + r)
        assert np.array_equal(router.query("beta", p),
                              np.asarray(idx.query(p)))
    router.mark_up(src)                # the drain failure marked it down
    assert router.rebalance("beta", dst) == (dst,)
    assert "beta" not in agents[src].server.registry
    assert router.stats_snapshot()["router_rebalances"] == 1


def test_drain_host_decommissions_every_replica(fleet, checkpoints):
    router, agents, _ = _make_router(checkpoints, replicas=2)
    for name in fleet:
        router.admit(TenantSpec(name, checkpoint=checkpoints))
    victim = router.owners("alpha")[0]
    router.drain_host(victim)
    assert len(agents[victim].server.registry) == 0
    for name, (ds, idx) in fleet.items():
        assert victim not in router.owners(name)
        p = _probes(ds, 64, seed=31)
        assert np.array_equal(router.query(name, p),
                              np.asarray(idx.query(p)))


# ------------------------------------------------------- load awareness
def test_load_override_diverts_placement(fleet, checkpoints):
    router, agents, _ = _make_router(checkpoints, replicas=1,
                                     load_slack=2)
    ring_order = router._ring.owners("alpha", N_HOSTS)
    # preload the preferred host well past the slack
    busy = agents[ring_order[0]].server
    for i in range(3):
        busy.admit(TenantSpec(f"filler-{i}", index=fleet["beta"][1]))
    owners = router.admit(TenantSpec("alpha", checkpoint=checkpoints))
    assert owners[0] != ring_order[0]
    assert router.stats_snapshot()["router_load_overrides"] >= 1


# ------------------------------------------------------- snapshot schema
def test_router_snapshot_schema_pinned(checkpoints):
    router, _, _ = _make_router(checkpoints)
    snap = router.stats_snapshot()
    assert set(snap) == ROUTER_SNAPSHOT_KEYS
    assert all(isinstance(v, float) for v in snap.values())


# ------------------------------------------------- launch_host (no JAX)
# A stand-in interpreter: launch_host runs ``sys.executable -m ...``, so
# a shell script in its place shows what the child was given.

def _fake_interpreter(tmp_path, body: str) -> str:
    path = tmp_path / "fake-python"
    path.write_text("#!/bin/sh\n" + body)
    path.chmod(0o755)
    return str(path)


def test_launch_host_honours_timeout(tmp_path, monkeypatch):
    monkeypatch.setattr(sys, "executable",
                        _fake_interpreter(tmp_path, "exec sleep 60\n"))
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        launch_host(name="mute", timeout_s=0.5)
    assert time.monotonic() - t0 < 10


def test_launch_host_pins_child_to_one_chip(tmp_path, monkeypatch):
    out = tmp_path / "env.txt"
    monkeypatch.setattr(sys, "executable", _fake_interpreter(
        tmp_path, f'env > "{out}"\necho "FLEET_HOST_LISTENING 4242"\n'))
    proc, address = launch_host(name="pinned", chip=2, timeout_s=30)
    assert proc.wait(timeout=30) == 0
    assert address == ("127.0.0.1", 4242)
    env = dict(line.split("=", 1)
               for line in out.read_text().splitlines() if "=" in line)
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    proc, _ = launch_host(name="unpinned", timeout_s=30)
    assert proc.wait(timeout=30) == 0
    assert "TPU_VISIBLE_CHIPS" not in out.read_text()
