"""Serving observability: snapshot schema, tenant drift, trace overlap.

Three contracts are pinned here:

* the ``stats_snapshot()`` / ``tenant_snapshot()`` KEY SETS are frozen
  (fast-signal schema tests — dashboards and the bench parse these
  dicts, so a key rename must be a conscious break);
* per-tenant stage counters sum EXACTLY with the global stage rates,
  and the drift baseline resets on hot-reload;
* the exported span trace shows host/device OVERLAP iff async
  double-buffered dispatch is on — the one fact flat counters cannot
  express.
"""
import json

import numpy as np
import pytest

from repro.core import existence
from repro.data import tuples
from repro.serve_filter import (FilterServer, ServeConfig, TenantSpec)
from repro.serve_filter import executors as executors_lib
from repro.serve_filter.stats import ServeStats, TenantStats


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


def _probes(ds, n, seed):
    rng = np.random.default_rng(seed)
    pos = ds.records[rng.integers(0, len(ds.records), n // 2)]
    neg = np.stack([rng.integers(1, v, n - n // 2) for v in ds.cards],
                   axis=-1).astype(np.int32)
    return np.concatenate([pos, neg])


def _served(fleet, rounds=2, **kw):
    srv = FilterServer(ServeConfig.from_kwargs(**kw))
    for name, (_, idx) in fleet.items():
        srv.admit(TenantSpec(name, index=idx))
    for r in range(rounds):
        for name, (ds, _) in fleet.items():
            srv.submit(name, _probes(ds, 128, seed=100 + r))
        srv.run_until_drained()
    return srv


# -------------------------------------------------------- schema pinning

# the frozen JSONL schema: dashboards, the bench, and CI artifacts all
# parse these dicts — adding/renaming a key must update this pin
SNAPSHOT_KEYS = {
    # throughput
    "queries", "batches", "qps", "qps_interval", "batch_occupancy",
    "tenants_served", "overlapped_batches", "grouped_batches",
    # stage FPR decomposition (paper §3.3)
    "model_pos_rate", "fixup_hit_rate", "positive_rate",
    # latencies (ms)
    "batch_p50_ms", "batch_p99_ms", "batch_max_ms",
    "request_p50_ms", "request_p99_ms", "request_max_ms",
    "reload_p50_ms", "reload_p99_ms", "reload_max_ms",
    "queue_p50_ms", "queue_p99_ms", "queue_max_ms",
    # lifecycle
    "reloads", "lifecycle_admitted", "lifecycle_hydrating",
    "lifecycle_serving", "lifecycle_draining", "lifecycle_retired",
    "lifecycle_degraded",
    # reliability (PR 8): shedding, deadlines, hydration resilience
    "shed_rows", "deadline_expired", "hydration_retries",
    "checksum_failures", "degraded_tenants",
    # drift
    "max_drift_score",
    # registry / compile / cache / arena / trace telemetry
    "registered_filters", "registry_mb", "compiled_programs",
    "plan_groups", "compile_count", "compile_ms_total",
    "executor_cache_hits", "executor_cache_misses",
    "arena_holes", "arena_dead_words", "arena_slot_occupancy",
    "arena_compactions", "arena_growths", "arena_mb", "arena_host_mb",
    "arena_tile_cache_hits", "arena_tile_cache_misses",
    "trace_events",
    # compressed arenas (quantized tenant state)
    "arena_quant_mb", "tenants_per_gb",
    "arena_tenants_int8", "arena_tenants_fp32", "arena_tenants_int4",
    # what quantization costs: entries' own device arrays, and how far
    # calibration lowered the quantized thresholds
    "tenant_device_mb", "arena_quant_tau_margin_max",
    "arena_quant_tau_margin_mean",
}

TENANT_KEYS = {
    "rows", "batches", "model_pos", "fixup_pos", "final_pos",
    "model_pos_rate", "fixup_hit_rate", "positive_rate",
    "window_model_pos_rate", "window_fixup_hit_rate",
    "window_positive_rate",
    "ewma_model_pos_rate", "ewma_fixup_hit_rate", "ewma_positive_rate",
    "baseline_model_pos_rate", "baseline_fixup_hit_rate",
    "baseline_positive_rate",
    "has_baseline", "drift_score",
}


def test_stats_snapshot_schema_pinned(fleet):
    srv = _served(fleet)
    snap = srv.stats_snapshot()
    assert set(snap) == SNAPSHOT_KEYS
    assert all(isinstance(v, float) for v in snap.values()), \
        {k: type(v) for k, v in snap.items() if not isinstance(v, float)}
    # tracing is off by default: zero cost, zero events
    assert not srv.tracer.enabled
    assert snap["trace_events"] == 0.0


def test_router_snapshot_schema_pinned(fleet, tmp_path):
    """The fleet tier's ``router_*`` snapshot is pinned the same way:
    every key always present, every value a float, schema frozen in
    ``fleet.router.ROUTER_SNAPSHOT_KEYS`` (PR 9). The keys live in one
    place so this test, the router bench's counter accounting, and
    dashboards cannot drift apart."""
    from repro.core import existence
    from repro.serve_filter.fleet import (ROUTER_SNAPSHOT_KEYS,
                                          FilterRouter, HostAgent,
                                          InProcessTransport)
    hosts = {h: InProcessTransport(
                 HostAgent(FilterServer(ServeConfig()), name=h))
             for h in ("h0", "h1")}
    router = FilterRouter(hosts, replicas=2, load_slack=None)
    for prefix in ("router_hosts", "router_tenants",
                   "router_placements", "router_rebalances",
                   "router_failovers", "router_queries"):
        assert any(k.startswith(prefix) for k in ROUTER_SNAPSHOT_KEYS)
    snap = router.stats_snapshot()
    assert set(snap) == ROUTER_SNAPSHOT_KEYS
    assert all(isinstance(v, float) for v in snap.values())
    # the schema holds with live placements and traffic too
    name, (ds, idx) = next(iter(fleet.items()))
    existence.save_index(str(tmp_path / name), idx, step=0)
    router.admit(TenantSpec(name, checkpoint=str(tmp_path)))
    router.query(name, _probes(ds, 64, seed=1))
    snap = router.stats_snapshot()
    assert set(snap) == ROUTER_SNAPSHOT_KEYS
    assert snap["router_tenants"] == 1.0
    assert snap["router_queries"] == 1.0
    assert snap["router_placements"] == 2.0


def test_tenant_snapshot_schema_pinned(fleet):
    srv = _served(fleet)
    for name in fleet:
        ts = srv.tenant_snapshot(name)
        assert set(ts) == TENANT_KEYS
        assert all(isinstance(v, float) for v in ts.values())
    # handle.stats() is the same surface
    assert srv.handle("alpha").stats() == srv.tenant_snapshot("alpha")
    # unknown tenant -> the all-zeros empty snapshot, same schema
    ghost = srv.tenant_snapshot("nope")
    assert set(ghost) == TENANT_KEYS
    assert ghost["rows"] == 0.0 and ghost["drift_score"] == 0.0


# ------------------------------------------------- per-tenant consistency

def test_tenant_stage_counts_sum_to_global(fleet):
    srv = _served(fleet, rounds=3)
    snap = srv.stats_snapshot()
    tot = {k: 0.0 for k in ("rows", "model_pos", "fixup_pos",
                            "final_pos")}
    for name in fleet:
        ts = srv.tenant_snapshot(name)
        for k in tot:
            tot[k] += ts[k]
    assert tot["rows"] == snap["queries"]
    # the per-tenant stage decomposition sums EXACTLY with the global
    # rates (both are integer counts over the same valid rows)
    assert tot["model_pos"] == pytest.approx(
        snap["model_pos_rate"] * snap["queries"])
    assert tot["fixup_pos"] == pytest.approx(
        snap["fixup_hit_rate"] * snap["queries"])
    assert tot["final_pos"] == pytest.approx(
        snap["positive_rate"] * snap["queries"])


def test_grouped_dispatch_attributes_stages_per_tenant(fleet):
    """On the grouped path one dispatch carries several tenants' rows;
    the stage counts must still land on the right tenant."""
    srv = FilterServer(ServeConfig.from_kwargs(grouped=True))
    for name, (_, idx) in fleet.items():
        srv.admit(TenantSpec(name, index=idx))
    items = [(name, _probes(ds, 16, seed=5))
             for name, (ds, _) in fleet.items()]
    srv.submit_many(items)
    srv.run_until_drained()
    snap = srv.stats_snapshot()
    rows = sum(srv.tenant_snapshot(n)["rows"] for n in fleet)
    final = sum(srv.tenant_snapshot(n)["final_pos"] for n in fleet)
    assert rows == snap["queries"] == 32
    assert final == pytest.approx(snap["positive_rate"]
                                  * snap["queries"])
    # every tenant served rows, even though alpha/beta rode different
    # (or shared) megabatches
    assert all(srv.tenant_snapshot(n)["rows"] == 16 for n in fleet)


def test_queue_time_recorded_per_request(fleet):
    srv = _served(fleet, rounds=2)
    # one queue-time sample per submitted request
    assert srv.stats.queue_time.count == 2 * len(fleet)
    snap = srv.stats_snapshot()
    assert (0.0 <= snap["queue_p50_ms"] <= snap["queue_p99_ms"]
            <= snap["queue_max_ms"])


# ------------------------------------------------------------ drift score

def test_tenant_drift_ewma_baseline():
    ts = TenantStats(window_batches=4, baseline_rows=100, alpha=0.5)
    for _ in range(2):
        ts.record(64, 32, 6, 38)            # steady 0.5 model-pos rate
    snap = ts.snapshot()
    assert snap["has_baseline"] == 1.0      # froze at 128 >= 100 rows
    assert snap["baseline_model_pos_rate"] == pytest.approx(0.5)
    assert ts.drift_score == 0.0
    for _ in range(8):                      # the model drifts hot
        ts.record(64, 64, 0, 64)
    snap = ts.snapshot()
    assert snap["ewma_model_pos_rate"] > 0.95
    assert snap["drift_score"] == pytest.approx(
        snap["ewma_model_pos_rate"] - 0.5)
    assert snap["window_model_pos_rate"] == 1.0     # window forgot 0.5
    assert snap["model_pos_rate"] < 1.0             # cumulative didn't
    ts.reset_baseline()
    assert ts.drift_score == 0.0
    assert ts.snapshot()["has_baseline"] == 0.0


def test_reload_resets_drift_baseline(fleet):
    ds, idx = fleet["alpha"]
    srv = FilterServer(ServeConfig())
    handle = srv.admit(TenantSpec("alpha", index=idx))
    for r in range(3):                      # 384 rows >= BASELINE_ROWS
        srv.submit("alpha", _probes(ds, 128, seed=30 + r))
        srv.run_until_drained()
    assert handle.stats()["has_baseline"] == 1.0
    handle.reload(idx)                      # hot-swap (same fit is fine)
    after = handle.stats()
    assert after["has_baseline"] == 0.0     # measured vs the NEW epoch
    assert after["drift_score"] == 0.0
    assert after["rows"] == 384.0           # cumulative counts survive
    assert srv.stats_snapshot()["reloads"] == 1.0


# -------------------------------------------------------------- qps fixes

def test_qps_interval_does_not_decay_while_idle():
    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clk = Clock()
    st = ServeStats(clock=clk)
    yes = np.ones(100, dtype=bool)
    clk.t = 1.0
    st.record_batch("a", 100, 128, 0.001, yes, yes, yes)
    snap = st.snapshot()
    assert snap["qps"] == pytest.approx(100.0)
    assert snap["qps_interval"] == pytest.approx(100.0)
    clk.t = 101.0                           # 100s of idle
    snap = st.snapshot()
    assert snap["qps"] == pytest.approx(100 / 101)   # decays forever...
    assert snap["qps_interval"] == 0.0               # ...interval doesn't
    yes2 = np.ones(200, dtype=bool)
    st.record_batch("a", 200, 256, 0.001, yes2, yes2, yes2)
    clk.t = 102.0
    snap = st.snapshot()
    # the interval rate reflects ONLY the last second's 200 queries
    assert snap["qps_interval"] == pytest.approx(200.0)
    assert snap["qps"] == pytest.approx(300 / 102)


# ----------------------------------------------------- compile telemetry

def test_compile_and_cache_telemetry(fleet):
    st = existence.TrainSettings(steps=10, n_pos=400, n_neg=400)
    ds = tuples.synthesize([277, 133], n_records=700, seed=77)
    idx = existence.fit(ds, theta=90, settings=st)
    executors_lib.reset_telemetry()
    srv = FilterServer(ServeConfig.from_kwargs(buckets=(64,)))
    srv.admit(TenantSpec("fresh", index=idx))
    assert srv.stats_snapshot()["executor_cache_misses"] >= 1.0
    srv.submit("fresh", _probes(ds, 64, 9))
    srv.run_until_drained()
    snap = srv.stats_snapshot()
    assert snap["compile_count"] >= 1.0     # first (plan, bucket) call
    assert snap["compile_ms_total"] > 0.0
    srv.submit("fresh", _probes(ds, 64, 10))
    srv.run_until_drained()
    # same plan + same bucket: the compiled program is reused
    assert srv.stats_snapshot()["compile_count"] == snap["compile_count"]
    # a second server on the SAME plan hits the executor cache
    srv2 = FilterServer(ServeConfig.from_kwargs(buckets=(64,)))
    srv2.admit(TenantSpec("fresh", index=idx))
    assert srv2.stats_snapshot()["executor_cache_hits"] >= 1.0
    # per-label breakdown is queryable and consistent
    stats = executors_lib.compile_stats()
    assert sum(n for n, _ in stats.values()) \
        == int(snap["compile_count"])


# ----------------------------------------------------------- span traces

@pytest.mark.parametrize("async_dispatch", [True, False])
def test_trace_overlap_iff_async(fleet, async_dispatch):
    """The acceptance criterion: prepare-of-batch-t+1 overlaps
    device-compute of batch t exactly when the double buffer is on."""
    ds, idx = fleet["alpha"]
    srv = FilterServer(ServeConfig.from_kwargs(
        buckets=(256,), async_dispatch=async_dispatch, trace=True))
    srv.admit(TenantSpec("alpha", index=idx))
    for i in range(6):
        srv.submit("alpha", _probes(ds, 256, seed=50 + i))
    srv.run_until_drained()
    spans = srv.tracer.events()
    prepares = [s for s in spans
                if s.name == "prepare" and s.args and "seq" in s.args]
    computes = [s for s in spans if s.name == "device_compute"]
    assert len(prepares) >= 6 and len(computes) >= 6
    overlapped = sum(
        1 for c in computes for p in prepares
        if p.args["seq"] > c.args["seq"]
        and p.t_start < c.t_end and p.t_end > c.t_start)
    if async_dispatch:
        assert overlapped > 0
    else:
        assert overlapped == 0


def test_server_close_dumps_trace_and_closes_logger(fleet, tmp_path):
    ds, idx = fleet["beta"]
    mpath = str(tmp_path / "metrics.jsonl")
    tpath = str(tmp_path / "trace.json")
    with FilterServer(ServeConfig.from_kwargs(
            buckets=(64,), metrics_path=mpath,
            trace_path=tpath)) as srv:
        srv.admit(TenantSpec("beta", index=idx))
        srv.submit("beta", _probes(ds, 64, seed=21))
        srv.run_until_drained()
        f = srv.metrics._f
        assert f is not None and not f.closed
    # __exit__ closed the JSONL logger (the handle used to leak)...
    assert f.closed and srv.metrics._f is None
    # ...and dumped the trace to the configured path
    with open(tpath) as fh:
        payload = json.load(fh)
    xs = [e for e in payload["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert {"admit", "prepare", "dispatch", "device_block",
            "scatter_retire", "device_compute"} <= names
    for e in xs:
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
    # the JSONL stream got the drain-time snapshot, schema intact
    with open(mpath) as fh:
        rows = [json.loads(line) for line in fh]
    assert rows and set(ServeStats().snapshot()) <= set(rows[-1])
    srv.close()                             # idempotent


def test_dump_trace_requires_path(fleet):
    srv = FilterServer(ServeConfig.from_kwargs(trace=True))
    with pytest.raises(ValueError, match="trace path"):
        srv.dump_trace()


# ------------------------------------------- spans, counters, profiler

@pytest.mark.parametrize("trace", [False, True])
def test_dispatch_reads_compile_count_only_when_traced(fleet, monkeypatch,
                                                       trace):
    """The ``compiled`` arg of the dispatch span is the only reader of
    the process-wide compile count on the hot path: with tracing off a
    dispatch never sums it."""
    calls = []
    real = executors_lib.compile_count

    def counted():
        calls.append(1)
        return real()

    ds, idx = fleet["alpha"]
    srv = FilterServer(ServeConfig.from_kwargs(grouped=True, trace=trace))
    srv.admit(TenantSpec("alpha", index=idx))
    monkeypatch.setattr(executors_lib, "compile_count", counted)
    srv.submit_many([("alpha", _probes(ds, 32, seed=8))])
    srv.run_until_drained()
    assert srv.stats.totals.batches == 1
    assert (len(calls) > 0) is trace


def test_tile_cache_counters(fleet):
    """One dispatch counts once: a layout seen before hits, a new one
    misses, and a mutation of the arena (``_touch``) clears the cache
    so the next dispatch misses even on a layout seen before."""
    ds, idx = fleet["alpha"]
    srv = FilterServer(ServeConfig.from_kwargs(grouped=True))
    for t in ("a1", "a2"):
        srv.admit(TenantSpec(t, index=idx))
    (arena,) = srv.registry.groups.values()

    def dispatch(*tenants):
        srv.submit_many([(t, _probes(ds, 16, seed=9)) for t in tenants])
        srv.run_until_drained()
        return arena.tile_hits, arena.tile_misses

    assert dispatch("a1") == (0, 1)
    assert dispatch("a1") == (1, 1)           # same layout
    assert dispatch("a1", "a2") == (1, 2)     # a megabatch: new layout
    assert dispatch("a1", "a2") == (2, 2)
    srv.admit(TenantSpec("a3", index=idx))    # mutation: cache cleared
    assert dispatch("a1") == (2, 3)
    assert dispatch("a1") == (3, 3)
    snap = srv.stats_snapshot()
    assert snap["arena_tile_cache_hits"] == 3.0
    assert snap["arena_tile_cache_misses"] == 3.0


SERVE_SPANS = {"serve.submit", "serve.prepare", "serve.dispatch",
               "serve.tiles", "serve.launch", "serve.device_block",
               "serve.scatter_retire", "serve.stats"}


def test_spans_reach_the_profiler_host_plane(fleet, tmp_path):
    """A traced server under ``jax.profiler`` puts every one of its
    spans on the profiler's ``/host:CPU`` plane as ``serve.<name>``;
    the ring keeps the four ``serve``-category stages apart from the
    ``detail`` spans inside and beside them."""
    import glob

    import jax
    from jax.profiler import ProfileData

    ds, idx = fleet["alpha"]
    srv = FilterServer(ServeConfig.from_kwargs(grouped=True, trace=True))
    for t in ("a1", "a2"):
        srv.admit(TenantSpec(t, index=idx))
    srv.submit_many([("a1", _probes(ds, 16, seed=1))])
    srv.run_until_drained()                   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        srv.submit_many([(t, _probes(ds, 16, seed=2))
                         for t in ("a1", "a2")])
        srv.run_until_drained()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events}
    assert SERVE_SPANS <= names
    cats = {s.name: s.cat for s in srv.tracer.events()
            if s.name != "device_compute"}
    assert {n for n, c in cats.items() if c == "serve"} == {
        "prepare", "dispatch", "device_block", "scatter_retire"}
    assert {n for n, c in cats.items() if c == "detail"} == {
        "submit", "tiles", "launch", "stats"}
