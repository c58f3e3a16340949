"""Filter-serving throughput: queries/sec vs batch size, executor, dispatch.

Tracks the batched-query serving trajectory of ``repro.serve_filter``:

* two tenants with DIFFERENT plan shapes registered concurrently (the
  scheduler interleaves their dispatches round-robin),
* queries/sec for each padding bucket (compile excluded by a warmup
  dispatch per (tenant, bucket)),
* ``--executor sharded`` runs the same workload through the
  ``ShardedExecutor`` on a forced-multi-device CPU mesh (``--shards``),
* ``--async-dispatch`` double-buffers dispatches so host padding
  overlaps device compute,
* ``--tenants N --rows-per-request K`` adds the many-tenant low-load
  scenario this repo's grouped path targets: N lightly-loaded tenants
  each submitting K-row requests, where per-tenant dispatches can never
  fill a big bucket. ``--grouped`` additionally serves the same stream
  through plan-group megabatching (a grouped ``ServeConfig``) and
  reports the grouped-vs-ungrouped speedup. Combined with ``--executor
  sharded`` the scenario runs the COMPOSED path: megabatch arenas that
  are themselves mesh-sharded (combined embedding matrix row-sharded,
  concatenated bitsets word-sharded) — the dispatch-count collapse must
  survive sharding,
* ``--reload-every N`` turns the many-tenant scenario into a CHURN
  scenario: every N fleet ticks one tenant hot-reloads to a re-fitted
  index via ``TenantHandle.reload`` — under live traffic, mid-queue —
  exercising the zero-drain swap path (and, grouped, the arena slot
  swap). The reload schedule is deterministic and shared across modes,
  so a post-churn verification tick still cross-checks grouped
  bit-equal to ungrouped, and reload latency lands in the JSON rows,
* ``--quant`` reruns every many-tenant mode with int8 COMPRESSED
  ARENAS (quantized tenant state, fused dequant in the query body) on
  the same fleet, recording ``arena_mb`` / ``tenants_per_gb`` /
  ``qps_vs_fp32`` side by side with fp32 and asserting the grouped
  arena shrinks >= 3x (>= 2x in smoke) at matched answers: quantized
  answers are cross-checked grouped == ungrouped and zero-false-
  negative on indexed rows,
* ``--chaos`` runs the FAULT-TOLERANCE scenario instead of the
  throughput sweep: a grouped many-tenant fleet hydrated from real
  checkpoints under a seeded ``FaultConfig`` storm (checkpoint-read /
  hydrate / dispatch faults) with hydration retry + degraded-mode
  fallback, deadline pressure (tight ``deadline_ms`` on part of the
  traffic) and ``max_queued_rows`` backpressure. The storm quiesces
  (``max_faults``), the injector is suspended, every tenant is
  re-hydrated to SERVING, and a post-chaos verification tick asserts
  grouped == ungrouped bit-identical with zero false negatives; the
  JSON rows carry the shed/retry/deadline/degraded counters,
* ``--smoke`` is the CI fast path: a few hundred queries through the
  many-tenant scenario, grouped AND ungrouped, with a bit-equality
  cross-check instead of throughput assertions (with ``--chaos``, a
  small-fleet chaos run),
* the anti-baseline: a per-query Python loop over
  ``ExistenceIndex.query`` — the fused jitted path must beat it by
  >= 10x (asserted when run as a script).

Every scripted run appends one entry per bucket/scenario (q/s,
occupancy, p99) to ``BENCH_serve_filter.json`` next to the repo root,
so the perf trajectory across PRs is recorded, not anecdotal. Every
row carries the hardware/placement context (``devices`` =
``jax.device_count()``, ``mesh``, ``placement``) so sharded/grouped
trajectories stay comparable across boxes.

Usage: PYTHONPATH=src python benchmarks/serve_filter_bench.py
           [--executor {local,sharded}] [--shards N] [--async-dispatch]
           [--tenants N] [--rows-per-request K] [--grouped] [--quant]
           [--reload-every N] [--chaos] [--smoke] [--json-out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional

_DEFAULT_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_serve_filter.json")


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--executor", choices=("local", "sharded"),
                    default="local")
    ap.add_argument("--shards", type=int, default=2,
                    help="CPU mesh size for --executor sharded")
    ap.add_argument("--async-dispatch", action="store_true",
                    help="double-buffered dispatch (overlap pad/compute)")
    ap.add_argument("--steps", type=int, default=60,
                    help="training steps per tenant fit")
    ap.add_argument("--tenants", type=int, default=0,
                    help="run the many-tenant low-load scenario with "
                         "this many tenants (0 disables)")
    ap.add_argument("--rows-per-request", type=int, default=16,
                    help="rows per request in the many-tenant scenario")
    ap.add_argument("--grouped", action="store_true",
                    help="also serve the many-tenant scenario through "
                         "plan-group megabatching and report the speedup")
    ap.add_argument("--quant", action="store_true",
                    help="also serve the many-tenant scenario through "
                         "compressed arenas (quantized tenant state) "
                         "and record arena_mb / tenants_per_gb / q/s "
                         "side by side with fp32 on the same fleet")
    ap.add_argument("--bits", type=int, choices=(8, 4), default=8,
                    help="quantized storage width for --quant: 8 (int8) "
                         "or 4 (packed nibbles)")
    ap.add_argument("--grid", choices=("linear", "nf4"), default="linear",
                    help="quantization grid for --quant (nf4 requires "
                         "--bits 4)")
    ap.add_argument("--reload-every", type=int, default=0,
                    help="many-tenant churn: hot-reload one tenant via "
                         "TenantHandle.reload every N fleet ticks "
                         "(0 disables)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the fault-tolerance scenario: grouped "
                         "fleet hydrated from checkpoints under a "
                         "seeded fault storm with retries, degraded "
                         "mode, deadlines and backpressure; post-chaos "
                         "recovery is verified grouped == ungrouped "
                         "bit-identical")
    ap.add_argument("--smoke", action="store_true",
                    help="CI fast path: tiny many-tenant run (grouped + "
                         "ungrouped, bit-equality checked), no classic "
                         "sweep")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="many-tenant scenario: attach a span tracer to "
                         "the last mode's server, export Chrome trace-"
                         "event JSON here, and self-check that prepare/"
                         "device-compute overlap matches the dispatch "
                         "mode (open the file in Perfetto)")
    ap.add_argument("--json-out", default=_DEFAULT_JSON,
                    help="append results here ('' disables)")
    return ap


_ARGS = (make_parser().parse_args() if __name__ == "__main__"
         else make_parser().parse_args([]))
if _ARGS.executor == "sharded":
    # must flip the placeholder-device flag BEFORE jax import
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={_ARGS.shards}")

import numpy as np                                    # noqa: E402

from repro.core import existence, lmbf                # noqa: E402
from repro.data import tuples                         # noqa: E402
from repro.runtime.compile_cache import (             # noqa: E402
    enable_compile_cache)
from repro.serve_filter import (FaultConfig,          # noqa: E402
                                FilterServeError, FilterServer,
                                Overloaded, ReliabilityConfig,
                                ServeConfig, TenantSpec, TenantState)
from repro.serve_filter.config import (               # noqa: E402
    GroupingConfig, LIFECYCLE_TRANSITIONS, PlacementConfig, QuantConfig)
from repro.serve_filter.plan import quant_meta        # noqa: E402

BUCKETS = (64, 256, 1024)
N_QUERIES = 4096            # per tenant per bucket measurement


def _serve_mesh(executor: str, shards: int):
    if executor != "sharded":
        return None
    import jax
    if len(jax.devices()) < shards:
        raise SystemExit(
            f"--executor sharded needs {shards} devices but found "
            f"{len(jax.devices())}; XLA_FLAGS was set too late?")
    return jax.make_mesh((shards,), ("data",))


def _env_fields(mesh) -> dict:
    """Hardware/placement context stamped on every recorded row:
    sharded and grouped trajectories are only comparable across boxes
    when the device count, mesh shape, and placement mode ride along
    with the numbers."""
    import jax
    return {
        "devices": int(jax.device_count()),
        "mesh": {k: int(v) for k, v in mesh.shape.items()}
                if mesh is not None else None,
        "placement": "sharded" if mesh is not None else "local",
    }


def fit_tenants(steps: int = 60) -> Dict[str, tuple]:
    """Two small fitted indexes with distinct plan shapes."""
    st = existence.TrainSettings(steps=steps, n_pos=4000, n_neg=4000)
    out = {}
    for tenant, cards, theta, seed in (
            ("airline-ish", [900, 700, 300, 120], 250, 11),
            ("dmv-ish", [50, 1200, 40, 400], 300, 12)):
        ds = tuples.synthesize(cards, n_records=6000, seed=seed)
        out[tenant] = (ds, existence.fit(ds, theta=theta, settings=st))
    return out


def _query_pool(ds: tuples.TupleDataset, n: int, seed: int) -> np.ndarray:
    """Half indexed positives, half random probes."""
    rng = np.random.default_rng(seed)
    pos = ds.records[rng.integers(0, len(ds.records), n // 2)]
    neg = np.stack([rng.integers(1, v, n - n // 2) for v in ds.cards],
                   axis=-1).astype(np.int32)
    return np.concatenate([pos, neg], axis=0)


def bench_served(tenants: Dict[str, tuple], bucket: int,
                 n_queries: int = N_QUERIES, *, mesh=None,
                 async_dispatch: bool = False) -> dict:
    """QPS through the full server at one request batch size."""
    srv = FilterServer(ServeConfig.from_kwargs(
        buckets=BUCKETS, mesh=mesh, async_dispatch=async_dispatch))
    for name, (_, idx) in tenants.items():
        srv.admit(TenantSpec(name, index=idx))
    pools = {name: _query_pool(ds, n_queries, seed=1)
             for name, (ds, _) in tenants.items()}

    # warmup: compile each tenant's (plan-shape, bucket) program
    for name, pool in pools.items():
        srv.submit(name, pool[:bucket])
    srv.run_until_drained()

    t0 = time.perf_counter()
    for start in range(0, n_queries, bucket):
        for name, pool in pools.items():
            srv.submit(name, pool[start:start + bucket])
    srv.run_until_drained()
    dt = time.perf_counter() - t0

    total = len(tenants) * n_queries
    snap = srv.stats_snapshot()
    return {
        "bucket": bucket,
        "filters": len(tenants),
        "queries": total,
        "qps": total / dt,
        "us_per_query": dt / total * 1e6,
        "batch_occupancy": round(snap["batch_occupancy"], 3),
        "batch_p50_ms": round(snap["batch_p50_ms"], 3),
        "batch_p99_ms": round(snap["batch_p99_ms"], 3),
        "overlapped_batches": int(snap["overlapped_batches"]),
    }


def fit_fleet(n_tenants: int, steps: int = 30, n_bases: int = 4
              ) -> tuple:
    """A fleet sharing ONE plan shape: ``n_bases`` distinct fits
    (distinct weights, tau, fixup m_bits) assigned round-robin, so the
    fleet is heterogeneous where tenants really differ but groupable —
    the regime the paper's "vast amounts of data" serving story lives
    in. Fitting every tenant separately would measure training, not
    serving. Returns ``(fleet, bases)`` — the bases double as reload
    targets for the churn scenario."""
    st = existence.TrainSettings(steps=steps, n_pos=2000, n_neg=2000)
    bases = []
    for i in range(min(n_bases, n_tenants)):
        # wide-ish columns (one split, two unsplit) so the embedding
        # tables dominate the per-tenant footprint — the regime where
        # int8 compressed arenas actually pay (tiny tables are all
        # scale-vector and padding overhead)
        ds = tuples.synthesize([4000, 2500, 900], n_records=4000,
                               seed=40 + i)
        bases.append((ds, existence.fit(ds, theta=3000, settings=st)))
    return ({f"tenant{i:03d}": bases[i % len(bases)]
             for i in range(n_tenants)}, bases)


class _ReloadChurn:
    """Deterministic reload schedule for the churn scenario: every
    ``every`` fleet ticks, the next tenant (rotating) hot-reloads to
    the next base fit — mid-queue, so the swap happens under live
    traffic. The schedule depends only on tick/reload counts, so the
    grouped and ungrouped modes end every window with IDENTICAL
    tenant->index mappings and the post-churn verification tick can
    require bit-equality across modes.

    With ``ckpts`` (one checkpoint dir per base, saved in the server's
    own storage format — ``existence_index_v3`` for quantized modes,
    v2 for fp32) each reload hydrates a FRESH index from disk first, so
    the measured swap exercises the real reload path: a v3 index
    arrives with its packed payload and calibrated tau pinned and the
    swap skips quantization + calibration entirely, which is what
    keeps quant reload p99 in fp32's neighborhood."""

    def __init__(self, srv: FilterServer, names, bases, every: int,
                 ckpts=None):
        self.srv = srv
        self.names = list(names)
        self.bases = bases
        self.ckpts = ckpts
        self.every = every
        self.ticks = 0
        self.reloads = 0

    def due(self) -> bool:
        self.ticks += 1
        return self.every > 0 and self.ticks % self.every == 0

    def fire(self) -> None:
        name = self.names[self.reloads % len(self.names)]
        j = self.reloads % len(self.bases)
        if self.ckpts is not None:
            idx = existence.load_index(self.ckpts[j])
        else:
            _, idx = self.bases[j]
        self.srv.handle(name).reload(idx)
        self.reloads += 1


def _measure_window(srv: FilterServer, pools: Dict[str, np.ndarray],
                    k: int, rounds: int,
                    churn: Optional[_ReloadChurn] = None) -> float:
    """One measurement window: ``rounds`` fleet ticks (every tenant
    submits ONE k-row request per tick, submissions pipelined with the
    in-flight dispatch), drained at the end; on churn ticks one tenant
    hot-reloads after the first dispatch, with the rest of the tick's
    rows still queued. Returns q/s — the INTERVAL qps from the server's
    own stats (queries/time since the previous snapshot), so the
    measurement window is exactly this window, not life-to-date."""
    sched = srv.scheduler
    items = [(name, pool[:k]) for name, pool in pools.items()]
    srv.stats.snapshot()        # pin the interval-qps origin to now
    for _ in range(rounds):
        sched.submit_many(items)
        if churn is not None and churn.due():
            sched.step()        # a batch dispatches against the old epoch
            churn.fire()        # ...then the swap lands under live load
        while sched.pending_rows:
            sched.step()
    sched.run_until_drained()
    return srv.stats.snapshot()["qps_interval"]


def run_many_tenant_scenario(*, tenants: int, rows_per_request: int,
                             grouped: bool, steps: int,
                             quant: bool = False, quant_bits: int = 8,
                             quant_grid: str = "linear",
                             async_dispatch: bool = False,
                             reload_every: int = 0,
                             target_queries: int = 16384,
                             repeats: int = 3, mesh=None,
                             trace_path: Optional[str] = None
                             ) -> List[dict]:
    """The many-tenant low-load regime: every tenant lightly loaded
    (one small request outstanding), where per-tenant dispatches can
    never fill a big bucket. Ungrouped always runs (the 'before');
    grouped additionally when asked (the 'after'), cross-checked
    bit-equal on a verification tick and tagged with the speedup.
    ``reload_every`` > 0 adds hot-reload churn to every mode on a
    shared deterministic schedule — a post-churn verification tick
    re-checks grouped bit-equal to ungrouped AFTER the swaps. With a
    ``mesh``, every mode runs sharded — grouped mode then exercises the
    composed path (mesh-sharded megabatch arenas).

    The modes are measured in INTERLEAVED windows and summarized by
    the median, so an episodic slowdown of the host lands on every mode
    instead of silently skewing the ratios.

    ``quant`` adds the compressed-arena variants: every mode reruns
    with quantized tenant state (a ``quantized`` ServeConfig at
    ``quant_bits``/``quant_grid`` — int8, packed int4, or packed NF4)
    on the SAME fleet. Quantized answers get their own cross-checks —
    quant-grouped bit-equal to quant-ungrouped, and the verification
    tick's indexed rows must all answer yes (the calibrated threshold +
    bit-exact fixup stage keep the no-false-negative invariant) — and
    the grouped quant row records the per-shard arena footprint next to
    fp32's (``arena_shrink_vs_fp32``, ``tenants_per_gb``,
    ``qps_vs_fp32``).

    Grouped modes ALWAYS run with async double-buffered dispatch: the
    megabatch path is the headline serving configuration and its
    arena prepare work is exactly what the double buffer overlaps
    with device compute (``--trace`` self-verifies the overlap).
    ``async_dispatch`` still governs the ungrouped baseline modes, so
    the before/after ratio can be read at either pipelining setting;
    each row records the flag it actually ran with."""
    import shutil
    import tempfile

    fleet, bases = fit_fleet(tenants, steps=steps)
    k = rows_per_request
    # one mode per (grouped, quantized) combination requested; fp32
    # always runs (it is the 'before' for both ratios)
    modes = [(False, False)] + ([(True, False)] if grouped else [])
    if quant:
        modes += [(False, True)] + ([(True, True)] if grouped else [])
    # churn reloads hydrate from per-base checkpoints saved in each
    # mode's own storage format: existence_index_v3 (packed payload +
    # calibrated tau, reload skips calibration) for the quantized
    # modes, plain v2 for fp32 — so reload_p99_ms compares the REAL
    # quant reload fast path against the fp32 baseline
    ckroot = None
    ckpts: Dict[bool, Optional[list]] = {False: None, True: None}
    if reload_every:
        ckroot = tempfile.mkdtemp(prefix="bench_reload_ckpt_")
        qc = QuantConfig(enabled=True, bits=quant_bits, grid=quant_grid)
        for j, (_, idx) in enumerate(bases):
            path = os.path.join(ckroot, f"base{j}_fp32")
            existence.save_index(path, idx, step=0)
            ckpts[False] = (ckpts[False] or []) + [path]
            if quant:
                path = os.path.join(ckroot, f"base{j}_q")
                existence.save_index(path, idx, step=0,
                                     quant=quant_meta(qc))
                ckpts[True] = (ckpts[True] or []) + [path]
    ctx: Dict[tuple, tuple] = {}
    answers: Dict[tuple, dict] = {}
    for mode in modes:
        g, q = mode
        # span tracing rides the LAST mode's server (the grouped one
        # when grouping is on): one trace file, the headline path
        traced = bool(trace_path) and mode == modes[-1]
        srv = FilterServer(ServeConfig.from_kwargs(
            buckets=BUCKETS, grouped=g, quantized=q,
            quant_bits=quant_bits, quant_grid=quant_grid,
            async_dispatch=async_dispatch or g, mesh=mesh, trace=traced,
            trace_path=trace_path if traced else None))
        for name, (_, idx) in fleet.items():
            srv.admit(TenantSpec(name, index=idx))
        pools = {name: _query_pool(ds, max(k * 4, 64), seed=3)
                 for name, (ds, _) in fleet.items()}
        # verification tick: compiles everything AND captures answers
        reqs = dict(zip(pools, srv.submit_many(
            [(name, pool[:k]) for name, pool in pools.items()])))
        srv.run_until_drained()
        answers[mode] = {name: r.answers.copy()
                         for name, r in reqs.items()}
        churn = (_ReloadChurn(srv, sorted(fleet), bases, reload_every,
                              ckpts=ckpts[q])
                 if reload_every else None)
        ctx[mode] = (srv, pools, churn)
    _check_answers(modes, answers, grouped)

    rounds = max(2, target_queries // (len(fleet) * k))
    qps: Dict[tuple, List[float]] = {m: [] for m in modes}
    calib_s: Dict[tuple, float] = {m: 0.0 for m in modes}
    for _ in range(repeats):
        for mode in modes:
            srv, pools, churn = ctx[mode]
            c0 = lmbf.calibration_stats()["seconds"]
            qps[mode].append(_measure_window(srv, pools, k, rounds,
                                             churn))
            calib_s[mode] += lmbf.calibration_stats()["seconds"] - c0
    med = {m: sorted(qps[m])[len(qps[m]) // 2] for m in modes}

    if grouped and reload_every:
        # post-churn verification tick: the shared reload schedule left
        # every mode with the same tenant->index mapping, so the
        # cross-mode equalities must STILL hold after the swaps
        post: Dict[tuple, dict] = {}
        for mode in modes:
            srv, pools, _ = ctx[mode]
            reqs = dict(zip(pools, srv.submit_many(
                [(name, pool[:k]) for name, pool in pools.items()])))
            srv.run_until_drained()
            post[mode] = {name: r.answers.copy()
                          for name, r in reqs.items()}
        _check_answers(modes, post, grouped)

    # snapshot every mode BEFORE building rows: the quant rows compare
    # their arena footprint against the fp32 sibling's
    snaps = {m: ctx[m][0].stats_snapshot() for m in modes}
    rows = []
    for mode in modes:
        g, q = mode
        snap = snaps[mode]
        row = {
            "scenario": "many_tenant",
            "tenants": len(fleet),
            "rows_per_request": k,
            "grouped": g,
            "quantized": q,
            "bits": quant_bits if q else 32,
            "grid": quant_grid if q else "fp32",
            "async_dispatch": async_dispatch or g,
            "queries": repeats * rounds * len(fleet) * k,
            "qps": med[mode],
            "qps_windows": [round(v) for v in qps[mode]],
            "us_per_query": 1e6 / med[mode],
            "batches": int(snap["batches"]),
            "grouped_batches": int(snap["grouped_batches"]),
            "batch_occupancy": round(snap["batch_occupancy"], 3),
            "batch_p99_ms": round(snap["batch_p99_ms"], 3),
            "queue_p99_ms": round(snap["queue_p99_ms"], 3),
            "plan_groups": int(snap["plan_groups"]),
            "arena_mb": round(snap["arena_mb"], 4),
            "arena_quant_mb": round(snap["arena_quant_mb"], 4),
            "tenants_per_gb": round(snap["tenants_per_gb"], 1),
        }
        srv = ctx[mode][0]
        if snap["trace_events"]:
            row["trace"] = srv.dump_trace(trace_path)
            row["trace_events"] = int(snap["trace_events"])
        if reload_every:
            row["reload_every"] = reload_every
            row["reloads"] = int(snap["reloads"])
            row["reload_p99_ms"] = round(snap["reload_p99_ms"], 3)
            # calibration wall time spent INSIDE this mode's measured
            # windows: ~0 when churn hydrates v3 checkpoints (the tau
            # rides the payload), nonzero when reloads re-calibrate
            row["reload_calibration_ms"] = round(calib_s[mode] * 1e3, 3)
            if q and snaps[(g, False)]["reload_p99_ms"]:
                row["reload_p99_vs_fp32"] = round(
                    snap["reload_p99_ms"]
                    / snaps[(g, False)]["reload_p99_ms"], 2)
        if g:
            row["speedup_vs_ungrouped"] = round(
                med[mode] / med[(False, q)], 1)
        if q:
            row["qps_vs_fp32"] = round(med[mode] / med[(g, False)], 2)
            fp32_mb = snaps[(g, False)]["arena_mb"]
            if snap["arena_mb"] and fp32_mb:
                row["arena_shrink_vs_fp32"] = round(
                    fp32_mb / snap["arena_mb"], 2)
        rows.append(row)
    if ckroot is not None:
        shutil.rmtree(ckroot, ignore_errors=True)
    return rows


def run_chaos_scenario(*, tenants: int, rows_per_request: int,
                       steps: int, mesh=None, seed: int = 29,
                       rounds: int = 8, smoke: bool = False
                       ) -> List[dict]:
    """The fault-tolerance scenario: a many-tenant fleet hydrated from
    REAL checkpoints under a seeded fault storm, with retries, degraded
    mode, deadline pressure and backpressure — then recovery.

    Per mode (ungrouped, grouped): every tenant is admitted from its
    on-disk checkpoint while ``checkpoint_read``/``hydrate``/
    ``dispatch`` faults fire (hydration retries with seeded backoff;
    exhaustion falls back to DEGRADED backup-only serving). Traffic
    rounds mix tight ``deadline_ms`` requests (some expire while the
    storm slows the pump) against a ``max_queued_rows`` bound (whole
    submissions shed with ``Overloaded``), with mid-traffic reloads
    under injection. ``max_faults`` quiesces the storm; the injector is
    then suspended, every tenant re-hydrates to SERVING, and a
    verification tick must answer bit-identically across modes with
    zero false negatives — chaos may cost latency and epochs, never
    correctness. The JSON rows carry the reliability counters."""
    import shutil
    import tempfile

    k = rows_per_request
    fleet, _ = fit_fleet(tenants, steps=steps)
    ckroot = tempfile.mkdtemp(prefix="chaos_ckpt_")
    for name, (_, idx) in fleet.items():
        existence.save_index(os.path.join(ckroot, name), idx, step=0)
    pools = {name: _query_pool(ds, max(k * 4, 64), seed=3)
             for name, (ds, _) in fleet.items()}
    names = sorted(fleet)
    rows, answers = [], {}
    try:
        for grouped in (False, True):
            srv = FilterServer(ServeConfig(
                placement=PlacementConfig(mesh=mesh),
                grouping=GroupingConfig(enabled=grouped),
                faults=FaultConfig(
                    enabled=True, seed=seed,
                    rates={"checkpoint_read": 0.25, "hydrate": 0.1,
                           "dispatch": 0.2},
                    max_faults=20 if smoke else 120),
                reliability=ReliabilityConfig(
                    retries=2, backoff_base_s=0.001, backoff_mult=2.0,
                    backoff_cap_s=0.01, jitter=0.1, degraded=True,
                    max_queued_rows=max(k + 1, tenants * k // 2))))
            shed_calls = 0
            for name in names:
                try:
                    srv.admit(TenantSpec(name, checkpoint=ckroot))
                except FilterServeError:
                    pass        # exhausted w/o backup: re-admitted below
            for rnd in range(rounds):
                for i, name in enumerate(names):
                    if srv.registry.state_of(name) is TenantState.RETIRED:
                        continue
                    # deadline pressure on a third of the traffic: with
                    # dispatch faults requeueing batches, queue waits
                    # stretch and some of these expire (typed, counted)
                    ddl = 2.0 if (rnd + i) % 3 == 0 else None
                    try:
                        srv.submit(name, pools[name][:k],
                                   deadline_ms=ddl)
                    except Overloaded:
                        shed_calls += 1
                if rnd % 2 == 1:    # reload under injection, mid-queue
                    try:
                        srv.admit(TenantSpec(names[rnd % len(names)],
                                             checkpoint=ckroot))
                    except FilterServeError:
                        pass
                srv.run_until_drained()
            # the storm never wedges a tenant outside the legal states,
            # and every recorded trail walks the lifecycle graph
            degraded_peak = 0
            for name in names:
                st = srv.registry.state_of(name)
                assert st in (TenantState.SERVING, TenantState.DEGRADED,
                              TenantState.RETIRED), (name, st)
                degraded_peak += st is TenantState.DEGRADED
                for frm, to in srv.stats.transitions_of(name):
                    assert to in LIFECYCLE_TRANSITIONS[frm], \
                        f"{name}: illegal {frm} -> {to}"
            # recovery: storm off, every tenant back to SERVING
            srv.faults.suspend()
            for name in names:
                srv.admit(TenantSpec(name, checkpoint=ckroot))
                assert (srv.registry.state_of(name)
                        is TenantState.SERVING), name
            # verification tick, paced under the still-active
            # max_queued_rows bound (one tenant in the queue at a time)
            got = {}
            for name in names:
                fut = srv.submit(name, pools[name][:k])
                got[name] = np.asarray(fut.result()).copy()
            answers[grouped] = got
            snap = srv.stats_snapshot()
            rows.append({
                "scenario": "chaos",
                "tenants": len(fleet),
                "rows_per_request": k,
                "grouped": grouped,
                "rounds": rounds,
                "fault_seed": seed,
                "faults_injected": srv.faults.injected,
                "faults_by_site": {s: n for s, n
                                   in srv.faults.by_site.items() if n},
                "dispatch_faults": srv.scheduler.dispatch_faults,
                "hydration_retries": int(snap["hydration_retries"]),
                "checksum_failures": int(snap["checksum_failures"]),
                "deadline_expired": int(snap["deadline_expired"]),
                "shed_rows": int(snap["shed_rows"]),
                "shed_calls": shed_calls,
                "degraded_peak": degraded_peak,
                "lifecycle_degraded": int(snap["lifecycle_degraded"]),
                "queries": int(snap["queries"]),
                "reloads": int(snap["reloads"]),
            })
            srv.close()
        for name in names:      # post-chaos: grouped == ungrouped, no FN
            np.testing.assert_array_equal(
                answers[True][name], answers[False][name],
                err_msg=f"post-chaos grouped != ungrouped for {name}")
            assert np.asarray(answers[True][name]).all(), \
                f"post-chaos false negative on indexed rows: {name}"
        for row in rows:
            row["post_chaos_bitequal"] = True
        assert any(r["faults_injected"] > 0 for r in rows), \
            "chaos scenario injected nothing — storm misconfigured"
        assert any(r["hydration_retries"] > 0 for r in rows), \
            "chaos scenario never exercised hydration retry"
    finally:
        shutil.rmtree(ckroot, ignore_errors=True)
    return rows


def _print_chaos(rows: List[dict]) -> None:
    hdr = f"{'mode':>10} {'tenants':>7} {'faults':>7} {'retries':>8} " \
          f"{'deadline':>9} {'shed':>6} {'degraded':>9} {'queries':>8} " \
          f"{'bitequal':>9}"
    print(hdr)
    for r in rows:
        mode = "grouped" if r["grouped"] else "ungrouped"
        print(f"{mode:>10} {r['tenants']:>7} {r['faults_injected']:>7} "
              f"{r['hydration_retries']:>8} {r['deadline_expired']:>9} "
              f"{r['shed_rows']:>6} {r['lifecycle_degraded']:>9} "
              f"{r['queries']:>8} {str(r['post_chaos_bitequal']):>9}")


def _check_answers(modes, answers: Dict[tuple, dict],
                   grouped: bool) -> None:
    """Cross-mode answer invariants on a verification tick: grouped
    bit-equal to ungrouped (per storage dtype), and — because the
    tick's rows are all INDEXED records — every mode must answer yes
    on every row (zero false negatives; for the quantized modes this
    is the calibrated-threshold no-FN guarantee at work)."""
    dtypes = {q for _, q in modes}
    if grouped:
        for q in dtypes:
            for name, ans in answers[(True, q)].items():
                np.testing.assert_array_equal(
                    ans, answers[(False, q)][name],
                    err_msg=f"grouped != ungrouped (quant={q}) "
                            f"for {name}")
    for mode, per_tenant in answers.items():
        for name, ans in per_tenant.items():
            assert np.asarray(ans).all(), \
                f"false negative on indexed rows: mode={mode} " \
                f"tenant={name}"

def _verify_trace(path: str, async_dispatch: bool) -> None:
    """Self-check an exported trace: well-formed Chrome events, and the
    async double buffer's overlap present iff async dispatch was on —
    some prepare-of-batch-*t+1* span must sit inside device-compute of
    an earlier batch *t* (and none may under synchronous dispatch)."""
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    assert xs, f"trace {path} has no complete events"
    assert all(isinstance(e.get("ts"), (int, float))
               and isinstance(e.get("dur"), (int, float))
               and e["dur"] >= 0 for e in xs), "malformed ts/dur"
    prepares = [e for e in xs if e["name"] == "prepare"
                and "seq" in e.get("args", {})]
    computes = [e for e in xs if e["name"] == "device_compute"]
    assert prepares and computes, "trace missing pipeline spans"
    overlapped = 0
    for c in computes:
        c0, c1 = c["ts"], c["ts"] + c["dur"]
        if any(p["args"]["seq"] > c["args"]["seq"]
               and p["ts"] < c1 and p["ts"] + p["dur"] > c0
               for p in prepares):
            overlapped += 1
    if async_dispatch:
        assert overlapped > 0, \
            "async dispatch on, but no prepare overlapped device compute"
    else:
        assert overlapped == 0, \
            f"sync dispatch, yet {overlapped} device windows overlapped " \
            "a later prepare"
    print(f"trace ok: {len(xs)} events, {len(computes)} device windows, "
          f"{overlapped} overlapped by a later prepare "
          f"(async={async_dispatch}) -> {path}")


def bench_python_loop(tenants: Dict[str, tuple], n: int = 64) -> dict:
    """The anti-baseline: one eager ExistenceIndex.query per row."""
    per_query = []
    for name, (ds, idx) in tenants.items():
        pool = _query_pool(ds, n, seed=2)
        idx.query(pool[:1])                       # warmup dispatch
        t0 = time.perf_counter()
        for row in pool:
            np.asarray(idx.query(row[None, :]))
        per_query.append((time.perf_counter() - t0) / len(pool))
    mean_s = float(np.mean(per_query))
    return {"qps": 1.0 / mean_s, "us_per_query": mean_s * 1e6}


def run(*, executor: str = "local", shards: int = 2,
        async_dispatch: bool = False, steps: int = 60,
        mesh=None) -> List[dict]:
    if mesh is None:
        mesh = _serve_mesh(executor, shards)
    tenants = fit_tenants(steps)
    rows = [bench_served(tenants, b, mesh=mesh,
                         async_dispatch=async_dispatch) for b in BUCKETS]
    base = bench_python_loop(tenants)
    for r in rows:
        r["executor"] = executor
        r["async_dispatch"] = async_dispatch
        if executor == "sharded":
            r["shards"] = shards
        r["speedup_vs_python_loop"] = round(base["us_per_query"] /
                                            r["us_per_query"], 1)
    rows.append({"bucket": 1, "filters": len(tenants),
                 "qps": base["qps"], "us_per_query": base["us_per_query"],
                 "executor": "python_loop", "mesh": None,
                 "placement": "local",      # eager per-row, never sharded
                 "note": "per-query Python loop (baseline)"})
    return rows


def record(rows: List[dict], path: Optional[str]) -> None:
    """Append this run's rows to the JSONL-ish trajectory file."""
    if not path:
        return
    history = []
    if os.path.exists(path):
        with open(path) as f:
            history = json.load(f)
    history.append({
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "argv": sys.argv[1:],
        "rows": rows,
    })
    with open(path, "w") as f:
        json.dump(history, f, indent=1)
    print(f"recorded {len(rows)} rows -> {path}")


def _print_many_tenant(rows: List[dict]) -> None:
    hdr = f"{'mode':>12} {'tenants':>7} {'rows/req':>8} {'qps':>12} " \
          f"{'batches':>8} {'occupancy':>9} {'arena MB':>9} " \
          f"{'speedup':>8}"
    print(hdr)
    for r in rows:
        mode = ("grouped" if r["grouped"] else "ungrouped")
        if r.get("quantized"):
            mode += QuantConfig(enabled=True, bits=r.get("bits", 8),
                                grid=r.get("grid", "linear")).label()
        churn = (f"  reloads={r['reloads']} "
                 f"(p99 {r['reload_p99_ms']}ms, "
                 f"calib {r.get('reload_calibration_ms', 0.0)}ms)"
                 if "reloads" in r else "")
        qinfo = ""
        if r.get("quantized"):
            if "arena_shrink_vs_fp32" in r:
                qinfo += f"  shrink={r['arena_shrink_vs_fp32']}x"
            qinfo += f"  qps_vs_fp32={r['qps_vs_fp32']}" \
                     f"  tenants/GB={r['tenants_per_gb']}"
        print(f"{mode:>12} {r['tenants']:>7} {r['rows_per_request']:>8} "
              f"{r['qps']:>12.0f} {r['batches']:>8} "
              f"{r['batch_occupancy']:>9} "
              f"{r.get('arena_mb', 0.0):>9} "
              f"{r.get('speedup_vs_ungrouped', ''):>8}{churn}{qinfo}")


def _check_quant_rows(rows: List[dict], *, smoke: bool) -> None:
    """Assert the compressed-arena headline numbers when --quant ran
    grouped: the quantized arena's per-shard device footprint must be
    >= 3x (int8) / >= 6x (packed int4) smaller than fp32's for the
    same fleet (>= 2x / >= 4x in smoke, whose tiny fleet amortizes
    scale vectors and tile padding worse); grouped quantized
    throughput must stay within 10% (int8) / 15% (int4, which pays an
    in-tile nibble unpack) of fp32 (full runs only — smoke windows are
    too short to compare); and on the churn leg a v3-checkpoint quant
    reload p99 must land within 2x of the fp32 reload p99 (the pinned
    payload + tau skip quantize/calibrate on the swap)."""
    qrows = [r for r in rows
             if r.get("quantized") and r.get("grouped")]
    for r in qrows:
        packed = r.get("bits", 8) == 4
        floor = (4.0 if packed else 2.0) if smoke else \
            (6.0 if packed else 3.0)
        shrink = r.get("arena_shrink_vs_fp32", 0.0)
        assert shrink >= floor, \
            f"quantized arena only {shrink}x smaller than fp32 " \
            f"(need >= {floor}x)"
        if not smoke:
            qps_floor = 0.85 if packed else 0.9
            assert r["qps_vs_fp32"] >= qps_floor, \
                f"grouped quantized q/s {r['qps_vs_fp32']}x of fp32 " \
                f"(need >= {qps_floor})"
            if "reload_p99_vs_fp32" in r:
                assert r["reload_p99_vs_fp32"] <= 2.0, \
                    f"quant reload p99 {r['reload_p99_vs_fp32']}x of " \
                    "fp32 (v3 fast path should keep it within 2x)"


def main():
    enable_compile_cache()
    rows: List[dict] = []
    if _ARGS.grid == "nf4" and _ARGS.bits != 4:
        raise SystemExit("--grid nf4 requires --bits 4")
    mesh = _serve_mesh(_ARGS.executor, _ARGS.shards)
    if _ARGS.chaos:
        chaos = run_chaos_scenario(
            tenants=_ARGS.tenants or (8 if _ARGS.smoke else 64),
            rows_per_request=_ARGS.rows_per_request,
            steps=min(_ARGS.steps, 10) if _ARGS.smoke else _ARGS.steps,
            mesh=mesh, rounds=4 if _ARGS.smoke else 8,
            smoke=_ARGS.smoke)
        print("chaos: seeded fault storm + recovery "
              + ("(sharded arenas) " if mesh is not None else "")
              + "(post-chaos grouped verified bit-equal to ungrouped, "
              "zero FN)")
        _print_chaos(chaos)
        env = _env_fields(mesh)
        for r in chaos:
            for k, v in env.items():
                r.setdefault(k, v)
        record(chaos, _ARGS.json_out)
        return chaos
    if _ARGS.smoke:
        # CI fast signal: tiny fleet, few hundred queries through BOTH
        # paths, grouped answers cross-checked bit-equal to ungrouped
        # (post-churn too when --reload-every adds hot-swap churn; the
        # tick budget grows so the schedule actually fires). With
        # --executor sharded this covers the composed path: megabatch
        # arenas that are themselves mesh-sharded.
        many = run_many_tenant_scenario(
            tenants=_ARGS.tenants or 8,
            rows_per_request=_ARGS.rows_per_request,
            grouped=True, quant=_ARGS.quant, quant_bits=_ARGS.bits,
            quant_grid=_ARGS.grid,
            steps=min(_ARGS.steps, 10),
            async_dispatch=_ARGS.async_dispatch,
            reload_every=_ARGS.reload_every,
            target_queries=1024 if _ARGS.reload_every else 384,
            repeats=2, mesh=mesh, trace_path=_ARGS.trace)
        print("smoke: many-tenant scenario "
              + ("(sharded arenas) " if mesh is not None else "")
              + "(grouped answers verified bit-equal to ungrouped"
              + (", incl. quantized modes" if _ARGS.quant else "")
              + (", incl. post-reload-churn)" if _ARGS.reload_every
                 else ")"))
        _print_many_tenant(many)
        assert any(r["grouped"] and r["grouped_batches"] > 0
                   for r in many), "grouped path never megabatched"
        if _ARGS.reload_every:
            assert all(r["reloads"] > 0 for r in many), \
                "churn scenario never hot-reloaded"
        _check_quant_rows(many, smoke=True)
        rows += many
    else:
        classic = run(executor=_ARGS.executor, shards=_ARGS.shards,
                      async_dispatch=_ARGS.async_dispatch,
                      steps=_ARGS.steps, mesh=mesh)
        hdr = f"{'bucket':>7} {'filters':>7} {'qps':>12} " \
              f"{'us/query':>10} {'occupancy':>9} {'speedup':>8}"
        print(f"executor={_ARGS.executor} async={_ARGS.async_dispatch}")
        print(hdr)
        for r in classic:
            print(f"{r['bucket']:>7} {r['filters']:>7} {r['qps']:>12.0f} "
                  f"{r['us_per_query']:>10.1f} "
                  f"{r.get('batch_occupancy', ''):>9} "
                  f"{r.get('speedup_vs_python_loop', ''):>8}"
                  + ("   " + r["note"] if "note" in r else ""))
        best = max(r.get("speedup_vs_python_loop", 0) for r in classic)
        assert best >= 10, f"fused path only {best}x over the Python loop"
        print(f"\nfused path beats the per-query loop by {best}x at best")
        rows += classic
        if _ARGS.tenants:
            many = run_many_tenant_scenario(
                tenants=_ARGS.tenants,
                rows_per_request=_ARGS.rows_per_request,
                grouped=_ARGS.grouped, quant=_ARGS.quant,
                quant_bits=_ARGS.bits, quant_grid=_ARGS.grid,
                steps=_ARGS.steps,
                async_dispatch=_ARGS.async_dispatch,
                reload_every=_ARGS.reload_every, mesh=mesh,
                trace_path=_ARGS.trace)
            print(f"\nmany-tenant low-load scenario "
                  f"({_ARGS.tenants} tenants x "
                  f"{_ARGS.rows_per_request}-row requests"
                  + (", sharded arenas)" if mesh is not None else ")"))
            _print_many_tenant(many)
            _check_quant_rows(many, smoke=False)
            rows += many
    if _ARGS.trace and any("trace" in r for r in rows):
        # the traced server is the LAST mode of the scenario (grouped
        # runs async regardless of --async-dispatch), so verify the
        # overlap expectation against the flag that row RAN with
        traced_row = next(r for r in rows if "trace" in r)
        _verify_trace(_ARGS.trace,
                      traced_row.get("async_dispatch",
                                     _ARGS.async_dispatch))
    env = _env_fields(mesh)
    for r in rows:              # stamp the hardware/placement context
        for k, v in env.items():
            r.setdefault(k, v)
    record(rows, _ARGS.json_out)
    return rows


if __name__ == "__main__":
    main()
