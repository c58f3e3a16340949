"""Top-level filter server: declarative config, tenant handles, futures.

``FilterServer`` is the serving-subsystem facade, configured by ONE
frozen :class:`~repro.serve_filter.config.ServeConfig` (placement,
dispatch, grouping, buckets, probe, metrics sub-configs — the old
11-kwarg constructor survives only as a deprecated shim). Tenants are
declared as :class:`~repro.serve_filter.config.TenantSpec`\\ s and
admitted through :meth:`FilterServer.admit`, which returns a
:class:`TenantHandle` — the live control surface for that tenant's
lifecycle (``ADMITTED -> HYDRATING -> SERVING -> DRAINING ->
RETIRED``):

* ``handle.reload(new_index | checkpoint=...)`` — the headline
  operation: atomically swap in a re-fitted index under live traffic
  (arena-slot hot-swap on the grouped path, fresh ``PlacedFilter`` on
  local/sharded) with **no drain** — batches dispatched before the
  swap retire against the old arrays, batches prepared after bind the
  new ones, and not a row is dropped or misanswered;
* ``handle.retire()`` — graceful shutdown: submissions stop, queued
  and in-flight rows finish, then the tenant leaves the registry;
* ``handle.submit`` / ``handle.query`` — per-tenant shorthand for the
  futures surface below.

Reliability (PR 8) is declared, not coded: ``ServeConfig.reliability``
turns on hydration retry/backoff, degraded-mode fallback (a tenant
whose hydration keeps failing serves conservatively from its backup
Bloom filter alone — DEGRADED state, zero false negatives preserved),
queue-wait deadlines (``submit(..., deadline_ms=...)``) and
backpressure shedding (``Overloaded``); ``ServeConfig.faults`` arms a
deterministic seeded fault injector for chaos testing. Both are
inert no-ops by default.

Queries are observed through futures: :meth:`FilterServer.submit`
returns a :class:`~repro.serve_filter.scheduler.QueryFuture` whose
``result(timeout)`` drives the scheduler only until THAT request
retires — unlike the deprecated ``query()``, it does not drain (and
silently retire) other tenants' pending work. Fleet drivers keep using
``submit_many`` + ``step()``/``run_until_drained()`` loops (mirroring
``launch/serve.py``) or ``scheduler.wait_all``.
"""
from __future__ import annotations

import time
import warnings
from typing import Dict, List, Optional

import numpy as np

from repro.core import existence
from repro.runtime.metrics import MetricsLogger
from repro.runtime.trace import Tracer
from repro.serve_filter import executors as executors_lib
from repro.serve_filter.config import ServeConfig, TenantSpec, TenantState
from repro.serve_filter.faults import NULL_INJECTOR, FaultInjector
from repro.serve_filter.registry import FilterEntry, FilterRegistry
from repro.serve_filter.scheduler import QueryFuture, QueryScheduler
from repro.serve_filter.stats import ServeStats


class TenantHandle:
    """Live control surface for one admitted tenant.

    Returned by :meth:`FilterServer.admit`; stays valid across
    reloads (the tenant's ``epoch`` counts them) and reports
    ``TenantState.RETIRED`` once the tenant has left the registry.
    """

    def __init__(self, server: "FilterServer", spec: TenantSpec):
        self._server = server
        self._spec = spec
        self._last_epoch = 0

    def __repr__(self) -> str:
        return (f"TenantHandle({self.tenant!r}, state="
                f"{self.state.value}, epoch={self.epoch})")

    # ------------------------------------------------------------- state
    @property
    def tenant(self) -> str:
        return self._spec.tenant

    @property
    def spec(self) -> TenantSpec:
        """The most recent spec admitted for this tenant (reloads
        update it)."""
        return self._spec

    @property
    def state(self) -> TenantState:
        return self._server.registry.state_of(self.tenant)

    @property
    def entry(self) -> Optional[FilterEntry]:
        """The current registry entry (None once retired)."""
        return self._server.registry.peek(self.tenant)

    @property
    def epoch(self) -> int:
        """How many reloads this tenant has seen (0 = as admitted);
        the last live epoch once retired."""
        entry = self.entry
        if entry is not None:
            self._last_epoch = entry.epoch
        return self._last_epoch

    # ----------------------------------------------------------- queries
    def submit(self, ids: np.ndarray, *,
               deadline_ms: Optional[float] = None) -> QueryFuture:
        return self._server.submit(self.tenant, ids,
                                   deadline_ms=deadline_ms)

    def stats(self) -> Dict[str, float]:
        """This tenant's observability snapshot: cumulative / rolling /
        EWMA stage rates and the drift score vs its admit-time baseline
        (see :meth:`FilterServer.tenant_snapshot`)."""
        return self._server.tenant_snapshot(self.tenant)

    def query(self, ids: np.ndarray) -> np.ndarray:
        """Synchronous convenience, scoped to this request: submit one
        block and drive the scheduler until IT retires (other tenants'
        pending work stays queued)."""
        return self.submit(ids).result()

    # --------------------------------------------------------- lifecycle
    def reload(self, index: Optional[existence.ExistenceIndex] = None, *,
               checkpoint: Optional[str] = None,
               step: Optional[int] = None) -> "TenantHandle":
        """Atomically swap in a re-fitted index — from memory or from
        ``<checkpoint>/<tenant>`` — under live traffic, with no drain:
        rows dispatched before the swap answer from the old index,
        rows prepared after answer from the new one, none are dropped.
        The tenant passes SERVING -> HYDRATING -> SERVING and its
        ``epoch`` increments; swap latency lands in
        ``ServeStats.record_reload``.
        """
        if self._server.registry.peek(self.tenant) is None:
            # RETIRED is terminal: resurrecting through a stale handle
            # would silently reset the epoch and bypass the lifecycle —
            # a retired tenant comes back only via an explicit admit()
            raise RuntimeError(
                f"tenant {self.tenant!r} is retired; admit a new "
                "TenantSpec instead of reloading a stale handle")
        spec = TenantSpec(tenant=self.tenant, index=index,
                          checkpoint=checkpoint, step=step,
                          pinned=self._spec.pinned,
                          groupable=self._spec.groupable)
        # server.admit owns the reload bookkeeping (metrics + spec
        # update) and returns the tenant's live handle — this object
        return self._server.admit(spec)

    def retire(self, *, drain: bool = True,
               max_steps: int = 100_000) -> None:
        """Remove the tenant. ``drain=True`` (default) first moves it
        to DRAINING — new submissions are rejected while its queued
        and in-flight rows finish answering — then retires it.
        ``drain=False`` force-retires: queued requests fail now (their
        futures resolve with an error); spans already dispatched still
        retire with answers. Idempotent once retired."""
        server = self._server
        entry = server.registry.peek(self.tenant)
        if entry is None:
            return
        self._last_epoch = entry.epoch  # snapshot before the entry goes
        sched = server.scheduler
        if drain:
            server.registry.begin_drain(self.tenant)
            steps = 0
            while (sched.pending_rows_for(self.tenant)
                   or sched.has_inflight(self.tenant)):
                if steps >= max_steps or not sched.step():
                    break
                steps += 1
        else:
            sched.cancel_tenant(
                self.tenant, f"tenant {self.tenant!r} force-retired")
        server.registry.evict(self.tenant)   # RETIRED hook reaps the handle

    # ------------------------------------------------------- persistence
    def save(self, directory: str, *, step: int = 0) -> str:
        """Persist the CURRENT epoch's index under
        ``directory/<tenant>``."""
        return self._server.registry.save(self.tenant, directory,
                                          step=step)


class FilterServer:
    """Registry + scheduler + stats behind one declarative config."""

    def __init__(self, config: Optional[ServeConfig] = None, **legacy):
        if legacy:
            if config is not None:
                raise TypeError("pass either a ServeConfig or legacy "
                                "kwargs, not both")
            warnings.warn(
                "FilterServer(**kwargs) is deprecated; build a frozen "
                "ServeConfig (repro.serve_filter.config) and pass it as "
                "the single argument", DeprecationWarning, stacklevel=2)
            config = ServeConfig.from_kwargs(**legacy)
        elif config is None:
            config = ServeConfig()
        self.config = config
        self.stats = ServeStats()
        # one tracer for the whole server; disabled it is a shared
        # no-op, so the scheduler's instrumentation costs one method
        # call per stage
        self.tracer = Tracer(maxlen=config.metrics.trace_events,
                             enabled=config.metrics.trace_enabled)
        # disabled faults share the process-wide no-op injector, same
        # pattern as the tracer: one dead-cheap method call per site
        self.faults = (FaultInjector(config.faults)
                       if config.faults.enabled else NULL_INJECTOR)
        if config.faults.enabled:
            # compile happens inside the process-global executor caches,
            # so the compile site installs process-globally too
            executors_lib.set_fault_injector(self.faults)
        self.registry = FilterRegistry(
            config.budget_mb, probe=config.probe,
            placement=config.placement, grouping=config.grouping,
            quant=config.quant, reliability=config.reliability,
            on_transition=self._on_transition, tracer=self.tracer,
            injector=self.faults, stats=self.stats)
        self.scheduler = QueryScheduler(
            self.registry, buckets=config.buckets.sizes, stats=self.stats,
            async_dispatch=config.dispatch.async_dispatch,
            max_inflight=config.dispatch.max_inflight,
            tracer=self.tracer, injector=self.faults,
            reliability=config.reliability)
        self.metrics = (MetricsLogger(config.metrics.path,
                                      echo=config.metrics.echo)
                        if config.metrics.enabled else None)
        self._handles: Dict[str, TenantHandle] = {}
        self._log_step = 0
        self._closed = False

    def _on_transition(self, tenant: str, frm, to: TenantState) -> None:
        """Registry lifecycle hook: count the transition and, at
        RETIRED, reap the tenant's handle — budget-LRU evictions retire
        tenants without going through ``handle.retire``/``evict``, and
        a leaked handle would pin the spec's whole in-memory index."""
        self.stats.record_transition(tenant, frm, to)
        if to is TenantState.RETIRED:
            handle = self._handles.pop(tenant, None)
            if handle is not None:
                entry = self.registry.peek(tenant)   # still present here
                if entry is not None:
                    handle._last_epoch = entry.epoch

    # ----------------------------------------------------------- tenants
    def admit(self, spec: TenantSpec) -> TenantHandle:
        """Admit a declared tenant (hydrating from its spec'd source)
        and return its lifecycle handle. Admitting an already-serving
        tenant IS a hot-reload: the swap latency lands in the reload
        metrics and the tenant's EXISTING handle is updated and
        returned, so every reference stays coherent."""
        live = self.registry.peek(spec.tenant) is not None
        t0 = time.perf_counter()
        self.registry.admit(spec)
        if live:
            self.stats.record_reload(time.perf_counter() - t0)
            # drift is measured against the freshly-installed model's
            # own early behavior, not the replaced one's
            self.stats.reset_tenant_baseline(spec.tenant)
        handle = self._handles.get(spec.tenant)
        if handle is None:
            handle = TenantHandle(self, spec)
            self._handles[spec.tenant] = handle
        else:
            handle._spec = spec
        return handle

    def admit_wire(self, payload: Dict) -> TenantHandle:
        """Admit a tenant from its versioned wire form (what a
        :class:`~repro.serve_filter.fleet.router.FilterRouter` ships
        across the process boundary): decode ``payload`` through the
        closed ``fleet.wire`` schema, then :meth:`admit` as usual —
        same lifecycle, same reload-on-readmit semantics."""
        from repro.serve_filter.fleet import wire
        return self.admit(wire.spec_from_wire(payload))

    def drain(self, tenant: str, *, max_steps: int = 100_000) -> None:
        """Name-addressed graceful retirement — the host-side entry
        point a router's rebalance drives (``DRAINING`` -> queued and
        in-flight rows finish -> ``RETIRED``). Idempotent: draining a
        tenant this server never had (or already retired) is a no-op,
        so a re-run migration cannot fail on its own success."""
        if self.registry.peek(tenant) is None:
            return
        handle = self._handles.get(tenant)
        if handle is not None:
            handle.retire(drain=True, max_steps=max_steps)
            return
        # registry-level tenants (admitted around the handle surface)
        self.registry.begin_drain(tenant)
        steps = 0
        sched = self.scheduler
        while (sched.pending_rows_for(tenant)
               or sched.has_inflight(tenant)):
            if steps >= max_steps or not sched.step():
                break
            steps += 1
        self.registry.evict(tenant)

    def handle(self, tenant: str) -> TenantHandle:
        """The lifecycle handle for an admitted tenant."""
        return self._handles[tenant]

    @property
    def handles(self) -> Dict[str, TenantHandle]:
        """Live handles by tenant id (read-only view)."""
        return dict(self._handles)

    def save(self, tenant: str, directory: str, *, step: int = 0) -> str:
        return self.registry.save(tenant, directory, step=step)

    def evict(self, tenant: str) -> None:
        """Drop a tenant immediately (queued requests fail on the
        scheduler's next pass). Prefer ``handle(tenant).retire()`` for
        the graceful, drain-then-retire path."""
        self.registry.evict(tenant)          # RETIRED hook reaps the handle

    # ------------------------------------------------------------ queries
    def submit(self, tenant: str, ids: np.ndarray, *,
               deadline_ms: Optional[float] = None) -> QueryFuture:
        """Admit one query block; returns its future (resolved by the
        scheduler at retire time). ``deadline_ms`` bounds QUEUE WAIT:
        if the request has not been dispatched within that many
        milliseconds its future resolves with ``DeadlineExceeded``
        (rows already on device always finish)."""
        return QueryFuture(
            self.scheduler.submit(tenant, ids, deadline_ms=deadline_ms),
            self.scheduler)

    def submit_many(self, items, *,
                    deadline_ms: Optional[float] = None
                    ) -> List[QueryFuture]:
        """Bulk admission for fleet clients: ``[(tenant, ids), ...]``
        -> futures, in order. A shared ``deadline_ms`` applies to every
        request in the batch. Traced as the ``submit`` span."""
        sched = self.scheduler
        with self.tracer.span("submit", cat="detail"):
            return [QueryFuture(req, sched)
                    for req in sched.submit_many(items,
                                                 deadline_ms=deadline_ms)]

    def step(self) -> bool:
        return self.scheduler.step()

    def run_until_drained(self, max_steps: int = 100_000) -> int:
        n = self.scheduler.run_until_drained(max_steps)
        if self.metrics is not None:
            self._log_step += 1
            self.stats.log_to(self.metrics, self._log_step)
        return n

    # ------------------------------------------------------------ readout
    def tenant_snapshot(self, tenant: str) -> Dict[str, float]:
        """One tenant's per-stage observability: cumulative
        ``model_pos_rate`` / ``fixup_hit_rate`` / ``positive_rate``
        (these sum consistently with the global rates), rolling-window
        and EWMA variants, and ``drift_score`` — the largest EWMA gap
        vs the baseline frozen shortly after admit/reload. The signal a
        drift-driven refit loop polls."""
        return self.stats.tenant_snapshot(tenant)

    def stats_snapshot(self) -> Dict[str, float]:
        # refresh the per-dtype arena membership gauges BEFORE the
        # snapshot so they ride along in the same flat dict
        n_int8 = n_fp32 = n_int4 = 0
        for a in self.registry.groups.values():
            if not a.key.quant.enabled:
                n_fp32 += len(a)
            elif a.key.quant.bits == 4:
                n_int4 += len(a)
            else:
                n_int8 += len(a)
        self.stats.set_arena_membership(n_int8, n_fp32, n_int4)
        self.stats.set_degraded_tenants(sum(
            1 for t in self.registry.tenants
            if self.registry.state_of(t) is TenantState.DEGRADED))
        snap = self.stats.snapshot()
        snap["registered_filters"] = float(len(self.registry))
        snap["registry_mb"] = self.registry.total_mb
        snap["compiled_programs"] = float(
            executors_lib.compiled_program_count())
        snap["plan_groups"] = float(len(self.registry.groups))
        # compile/cache telemetry (process-global, like the executor
        # caches themselves: servers sharing plans share programs)
        hits, misses = executors_lib.cache_stats()
        snap["compile_count"] = float(executors_lib.compile_count())
        snap["compile_ms_total"] = \
            executors_lib.compile_time_total() * 1e3
        snap["executor_cache_hits"] = float(hits)
        snap["executor_cache_misses"] = float(misses)
        # arena health, aggregated over this server's plan groups
        arenas = list(self.registry.groups.values())
        live = sum(len(a) for a in arenas)
        cap = sum(a.capacity for a in arenas)
        snap["arena_holes"] = float(sum(a.holes for a in arenas))
        snap["arena_dead_words"] = float(sum(a.dead_words
                                             for a in arenas))
        snap["arena_slot_occupancy"] = live / cap if cap else 0.0
        snap["arena_compactions"] = float(sum(a.compactions
                                              for a in arenas))
        snap["arena_growths"] = float(sum(a.growths for a in arenas))
        # grouped dispatches whose per-tile weight gather the arena's
        # tile-signature cache spared, and those that paid for it
        snap["arena_tile_cache_hits"] = float(sum(a.tile_hits
                                                  for a in arenas))
        snap["arena_tile_cache_misses"] = float(sum(a.tile_misses
                                                    for a in arenas))
        snap["trace_events"] = float(len(self.tracer))
        # actual PER-SHARD device footprint of the arenas (padding +
        # growth headroom included) — budget_mb counts nominal
        # per-filter sizes, so operators watch this for the true
        # grouped-residency cost. On a sharded fleet the row/word-
        # sharded arrays contribute one slice per device (charging the
        # whole arena to every device would overstate HBM pressure by
        # ~the shard count — exactly where sharding is the point);
        # arena_host_mb keeps the whole-arena host-mirror total.
        snap["arena_mb"] = sum(a.device_nbytes for a in
                               self.registry.groups.values()) / 2 ** 20
        snap["arena_host_mb"] = sum(a.nbytes for a in
                                    self.registry.groups.values()) / 2 ** 20
        # compressed-arena gauges: device footprint of the QUANTIZED
        # arenas alone (subset of arena_mb), and fleet density — live
        # grouped tenants per GB of arena device memory, the number the
        # compression tentpole moves (ISSUE 7 / the paper's point:
        # smaller learned filters => more tenants per device)
        snap["arena_quant_mb"] = sum(
            a.device_nbytes for a in self.registry.groups.values()
            if a.key.quant.enabled) / 2 ** 20
        arena_gb = snap["arena_mb"] / 1024.0
        snap["tenants_per_gb"] = (live / arena_gb) if arena_gb else 0.0
        # the device arrays the entries keep outside the arenas (the
        # float32 params an index brings, ungrouped placements), as
        # the device stores them, tiled padding included
        snap["tenant_device_mb"] = \
            self.registry.tenant_device_bytes() / 2 ** 20
        # how far calibration lowered the quantized tenants' thresholds,
        # logit(tau) - logit(tau_q) (0.0 with no quantized tenant)
        margins = [m for a in arenas for m in a.tau_margins().values()]
        snap["arena_quant_tau_margin_max"] = \
            float(max(margins)) if margins else 0.0
        snap["arena_quant_tau_margin_mean"] = \
            float(np.mean(margins)) if margins else 0.0
        return snap

    def dump_trace(self, path: Optional[str] = None) -> str:
        """Export the span buffer as Chrome trace-event JSON (open it
        at https://ui.perfetto.dev). ``path`` defaults to the config's
        ``metrics.trace_path``; returns the written path."""
        path = path or self.config.metrics.trace_path
        if not path:
            raise ValueError(
                "no trace path: pass one or set "
                "MetricsConfig(trace_path=...)")
        return self.tracer.to_chrome_trace(path)

    # ----------------------------------------------------------- shutdown
    def close(self) -> None:
        """Release observability resources: close the JSONL metrics
        logger (the file handle used to leak) and, when the config
        names a ``trace_path``, dump the trace there. Idempotent; the
        server remains usable for queries afterwards (a new logger is
        NOT reopened — close last)."""
        if self._closed:
            return
        self._closed = True
        if self.config.faults.enabled:
            # uninstall the process-global compile hook so later servers
            # (and bare executor users) don't inherit this chaos config
            executors_lib.set_fault_injector(None)
        if self.config.metrics.trace_path and len(self.tracer):
            self.tracer.to_chrome_trace(self.config.metrics.trace_path)
        if self.metrics is not None:
            self.metrics.close()

    def __enter__(self) -> "FilterServer":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # ------------------------------------------------- deprecated surface
    def register(self, tenant: str, index: existence.ExistenceIndex
                 ) -> FilterEntry:
        """.. deprecated:: PR 4
            Use ``admit(TenantSpec(tenant, index=...))`` — the handle
            it returns is the lifecycle surface (reload/retire)."""
        warnings.warn(
            "FilterServer.register is deprecated; use "
            "admit(TenantSpec(tenant, index=...)) and keep the returned "
            "TenantHandle", DeprecationWarning, stacklevel=2)
        return self.admit(TenantSpec(tenant=tenant, index=index)).entry

    def load(self, tenant: str, directory: str,
             step: Optional[int] = None) -> FilterEntry:
        """.. deprecated:: PR 4
            Use ``admit(TenantSpec(tenant, checkpoint=...))``."""
        warnings.warn(
            "FilterServer.load is deprecated; use "
            "admit(TenantSpec(tenant, checkpoint=directory, step=...))",
            DeprecationWarning, stacklevel=2)
        return self.admit(TenantSpec(tenant=tenant, checkpoint=directory,
                                     step=step)).entry

    def query(self, tenant: str, ids: np.ndarray) -> np.ndarray:
        """.. deprecated:: PR 4
            Use ``submit(tenant, ids).result()``. The old implementation
            drained the ENTIRE scheduler to answer one block — silently
            retiring other tenants' pending requests; the future-backed
            path is scoped to the submitted request."""
        warnings.warn(
            "FilterServer.query is deprecated; use "
            "submit(tenant, ids).result() — it completes this request "
            "without draining other tenants' pending work",
            DeprecationWarning, stacklevel=2)
        return self.submit(tenant, ids).result()
