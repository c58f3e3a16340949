"""Fleet router benchmark: N serving host PROCESSES + one router.

The federation tier's end-to-end check, one box, real process
boundaries (``python -m repro.serve_filter.fleet.host`` subprocesses
reached over ``multiprocessing.connection`` sockets):

* every routed answer is checked BIT-IDENTICAL to a single oracle
  host serving the whole fleet — through
  steady replicated traffic, a LIVE REBALANCE (admit-on-target ->
  SERVING -> drain-on-source, under traffic), and a MID-RUN HOST KILL
  (SIGKILL; replica failover keeps answering);
* zero dropped rows: every submitted block returns a full answer
  vector;
* the ``router_*`` counters are accounted exactly: the driver predicts
  placements (tenants x replicas + rebalance admits), per-block
  planned replica picks, and every diverted block, then requires the
  router's own counters to match.

The bench's own process never starts a JAX backend, because on a TPU machine
a chip belongs to one process: the fleet is fitted in a child process
pinned to chip 0, the oracle is a host pinned to chip 0 once that child
has exited, and host ``h<i>`` is pinned to chip ``i + 1`` (off a TPU
the pinning is inert). On a TPU machine the bench therefore needs
``--hosts`` + 1 chips.

Usage::

    PYTHONPATH=src python benchmarks/fleet_router_bench.py
        [--smoke]              # CI: 2 hosts, small fleet, 1 kill round
        [--hosts N] [--tenants N] [--replicas N]
        [--rows-per-request K] [--rounds N] [--json-out PATH]

Appends one entry per run to ``BENCH_fleet_router.json`` (same
trajectory format as ``serve_filter_bench``).
"""
import argparse
import multiprocessing
import os
import sys
import tempfile
import time
from typing import Dict, List

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from serve_filter_bench import (_env_fields, _query_pool, fit_fleet,
                                record)

from repro.core import existence
from repro.serve_filter import ReliabilityConfig, TenantSpec
from repro.serve_filter.fleet import (FilterRouter, SocketTransport,
                                      chip_env, launch_host)

_DEFAULT_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_fleet_router.json")


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI fast signal: 2 host procs, 6 tenants, "
                         "one kill/failover round")
    ap.add_argument("--hosts", type=int, default=3)
    ap.add_argument("--tenants", type=int, default=12)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--rows-per-request", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=6,
                    help="traffic rounds per leg (each round sends one "
                         "block per tenant)")
    ap.add_argument("--steps", type=int, default=20,
                    help="training steps for the base fits")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=_DEFAULT_JSON)
    return ap


class _Accounting:
    """The driver's independent model of what the router SHOULD count:
    per-tenant planned picks (deterministic round-robin) and every
    block whose planned replica was dead at send time."""

    def __init__(self):
        self.qcount: Dict[str, int] = {}
        self.expected_failovers = 0
        self.blocks = 0

    def planned(self, router, tenant: str, dead: set) -> str:
        owners = router.owners(tenant)
        pick = owners[self.qcount.get(tenant, 0) % len(owners)]
        self.qcount[tenant] = self.qcount.get(tenant, 0) + 1
        self.blocks += 1
        if pick in dead:
            self.expected_failovers += 1
        return pick


def _fit_and_save(tenants: int, steps: int, ckpt: str) -> tuple:
    """Child process on chip 0: fit the fleet and save every tenant's
    checkpoint under ``ckpt``. Returns each tenant's relation (for the
    query pools) and the device context the rows are stamped with."""
    os.environ.update(chip_env(0))
    fleet, _ = fit_fleet(tenants, steps=steps)
    for name, (_, idx) in fleet.items():
        existence.save_index(os.path.join(ckpt, name), idx, step=0)
    return {name: ds for name, (ds, _) in fleet.items()}, _env_fields(None)


def _traffic_leg(router, oracle, fleet, acct, *, rows_per_request: int,
                 rounds: int, seed: int, dead: set) -> dict:
    """One measured leg: every tenant gets ``rounds`` blocks; every
    routed answer must equal the oracle's bit-for-bit."""
    k = rows_per_request
    blocks = rows = 0
    t0 = time.perf_counter()
    for r in range(rounds):
        for name, ds in fleet.items():
            pool = _query_pool(ds, k, seed=seed + r)
            acct.planned(router, name, dead)
            got = router.query(name, pool)
            want = oracle.request({"op": "query", "tenant": name,
                                   "ids": pool})
            assert want["ok"], want
            assert got.shape == (k,), "dropped rows in routed answer"
            assert np.array_equal(got, want["answers"]), \
                f"routed answers for {name!r} diverge from the oracle"
            blocks += 1
            rows += k
    dt = time.perf_counter() - t0
    return {"blocks": blocks, "rows": rows,
            "qps": rows / dt if dt else 0.0}


def run(*, hosts: int, tenants: int, replicas: int,
        rows_per_request: int, rounds: int, steps: int,
        seed: int) -> tuple:
    """Returns ``(rows, env)``: the per-leg rows and the device context
    of the fitting process."""
    assert hosts >= 2, "the fleet bench needs at least two hosts"
    replicas = min(replicas, hosts)
    ckpt = tempfile.mkdtemp(prefix="fleet-bench-ckpt-")
    with multiprocessing.get_context("spawn").Pool(1) as pool:
        fleet, env = pool.apply(_fit_and_save, (tenants, steps, ckpt))

    procs: Dict[str, object] = {}
    router = None
    oracle = None
    rows_out: List[dict] = []
    try:
        # the single-host oracle: same fleet, one host of its own
        proc, address = launch_host(name="oracle", chip=0)
        procs["oracle"] = proc
        oracle = SocketTransport(address, host="oracle")
        for name in fleet:
            reply = oracle.request({"op": "admit", "spec": TenantSpec(
                name, checkpoint=ckpt).to_wire()})
            assert reply["ok"], reply
        transports = {}
        for i in range(hosts):
            name = f"h{i}"
            proc, address = launch_host(name=name, chip=i + 1)
            procs[name] = proc
            transports[name] = SocketTransport(address, host=name)
        router = FilterRouter(
            transports, replicas=replicas,
            reliability=ReliabilityConfig(retries=2,
                                          backoff_base_s=0.05),
            seed=seed, load_slack=None)

        t0 = time.perf_counter()
        for name in fleet:
            owners = router.admit(TenantSpec(name, checkpoint=ckpt))
            assert len(owners) == replicas
        admit_s = time.perf_counter() - t0
        snap = router.stats_snapshot()
        assert snap["router_placements"] == tenants * replicas
        assert snap["router_replica_placements"] == \
            tenants * (replicas - 1)
        assert snap["router_failovers"] == 0

        acct = _Accounting()
        expected_placements = tenants * replicas
        expected_replicas = tenants * (replicas - 1)
        base = dict(scenario="fleet_router", hosts=hosts,
                    tenants=tenants, replicas=replicas,
                    rows_per_request=rows_per_request)

        # leg 1: steady replicated traffic
        leg = _traffic_leg(router, oracle, fleet, acct,
                           rows_per_request=rows_per_request,
                           rounds=rounds, seed=100, dead=set())
        rows_out.append({**base, "leg": "steady",
                         "admit_s": round(admit_s, 3), **leg})

        # leg 2: LIVE REBALANCE under traffic — migrate one replica of
        # the first tenant through the host lifecycle machines
        # (admit-on-target -> verify SERVING -> drain-on-source)
        mover = sorted(fleet)[0]
        owners = router.owners(mover)
        free = [h for h in router.hosts if h not in owners]
        t0 = time.perf_counter()
        if free:
            target = free[0]
            router.rebalance(mover, target)
            expected_placements += 1          # the target admit
            assert target in router.owners(mover)
        else:
            # fully-replicated fleet (hosts == replicas, the --smoke
            # shape): migrate the primary INTO its replica (drain the
            # old primary), then restore full replication via re-admit
            target = owners[1]
            router.rebalance(mover, target, from_host=owners[0])
            assert router.owners(mover) == (target,)
            restored = router.admit(TenantSpec(mover, checkpoint=ckpt))
            assert len(restored) == replicas
            expected_placements += replicas   # the re-admit placements
            expected_replicas += replicas - 1
        rebalance_s = time.perf_counter() - t0
        leg = _traffic_leg(router, oracle, fleet, acct,
                           rows_per_request=rows_per_request,
                           rounds=max(2, rounds // 2), seed=200,
                           dead=set())
        rows_out.append({**base, "leg": "rebalance",
                         "rebalance_s": round(rebalance_s, 3),
                         "moved": mover, "target": target, **leg})
        assert router.stats_snapshot()["router_rebalances"] == 1

        # leg 3: MID-RUN HOST KILL -> replica failover. SIGKILL the
        # most-loaded victim; every tenant keeps a live replica
        # (replicas >= 2 across distinct hosts), so no block drops.
        victim = router.owners(sorted(fleet)[-1])[0]
        procs[victim].kill()
        procs[victim].wait(timeout=30)
        leg = _traffic_leg(router, oracle, fleet, acct,
                           rows_per_request=rows_per_request,
                           rounds=max(2, rounds // 2), seed=300,
                           dead={victim})
        rows_out.append({**base, "leg": "failover", "killed": victim,
                         **leg})

        # ---- counter accounting: the router's own numbers must match
        # the driver's independent model of every event
        snap = router.stats_snapshot()
        assert snap["router_queries"] == acct.blocks
        assert snap["router_placements"] == expected_placements
        assert snap["router_replica_placements"] == expected_replicas
        assert snap["router_rebalances"] == 1
        assert snap["router_failovers"] == acct.expected_failovers, \
            (snap["router_failovers"], acct.expected_failovers)
        assert acct.expected_failovers > 0, \
            "the kill leg never exercised failover"
        assert snap["router_recoveries"] == 0     # replicas sufficed
        assert snap["router_unowned_tenants"] == 0
        assert snap["router_hosts_down"] == 1.0
        for r in rows_out:
            r["bit_equal_vs_oracle"] = True
        rows_out[-1]["router_failovers"] = int(snap["router_failovers"])
        rows_out[-1]["router_placements"] = \
            int(snap["router_placements"])
        rows_out[-1]["router_fanout_queries"] = \
            int(snap["router_fanout_queries"])
    finally:
        if router is not None:
            router.close(shutdown_hosts=True)
        if oracle is not None:
            oracle.close()
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
            proc.wait(timeout=30)
    return rows_out, env


def main() -> List[dict]:
    args = make_parser().parse_args()
    if args.smoke:
        args.hosts, args.tenants = 2, 6
        args.rounds = min(args.rounds, 3)
        args.steps = min(args.steps, 8)
    rows, env = run(hosts=args.hosts, tenants=args.tenants,
                    replicas=args.replicas,
                    rows_per_request=args.rows_per_request,
                    rounds=args.rounds, steps=args.steps, seed=args.seed)
    for r in rows:
        for k, v in env.items():
            r.setdefault(k, v)
    hdr = f"{'leg':>10} {'hosts':>5} {'tenants':>7} {'blocks':>7} " \
          f"{'qps':>10}"
    print(hdr)
    for r in rows:
        extra = ""
        if r["leg"] == "rebalance":
            extra = f"   moved {r['moved']} -> {r['target']} " \
                    f"({r['rebalance_s']}s)"
        if r["leg"] == "failover":
            extra = f"   killed {r['killed']}, " \
                    f"failovers={r['router_failovers']}"
        print(f"{r['leg']:>10} {r['hosts']:>5} {r['tenants']:>7} "
              f"{r['blocks']:>7} {r['qps']:>10.0f}{extra}")
    print("fleet bench: routed answers bit-identical to the "
          "single-host oracle across all legs (steady, live "
          "rebalance, host kill -> failover); zero dropped rows; "
          "router_* counters account for every event")
    record(rows, args.json_out)
    return rows


if __name__ == "__main__":
    main()
