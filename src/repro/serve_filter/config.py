"""Declarative serving configuration and the tenant lifecycle vocabulary.

``FilterServer`` used to be configured through an 11-kwarg constructor
whose flags fanned out to the registry, scheduler, planner, and metrics
logger by name. This module replaces that kwarg soup with a frozen
:class:`ServeConfig` composed of small orthogonal sub-configs — each one
names the subsystem it parameterizes:

* :class:`BucketConfig`    — the scheduler's padding-bucket ladder;
* :class:`PlacementConfig` — the planner's target mesh + shard axis
  (``None`` = local placement);
* :class:`DispatchConfig`  — async double-buffering and the in-flight cap;
* :class:`GroupingConfig`  — plan-group megabatching + the tile granule;
* :class:`~repro.serve_filter.plan.ProbeConfig` — fixup-probe flavor
  (pure JAX vs the Pallas kernel; defined next to the planner, re-exported
  here);
* :class:`MetricsConfig`   — the JSONL metrics sink;
* :class:`~repro.serve_filter.faults.FaultConfig` — seeded fault
  injection for chaos testing (shared no-op when disabled);
* :class:`~repro.serve_filter.faults.ReliabilityConfig` — hydration
  retry/backoff, degraded mode, queue bound, dispatch watchdog.

Being frozen, a ``ServeConfig`` is a value: it can be built once at
deploy time, logged, compared, and handed to any number of servers —
nothing about it mutates as tenants come and go.

Tenants are declared the same way: a :class:`TenantSpec` names the
tenant, its **source** (exactly one of an in-memory fitted
``ExistenceIndex`` or a checkpoint directory to hydrate from), and its
placement hints (``pinned`` exempts it from LRU budget eviction;
``groupable=False`` keeps a heavy tenant out of plan-group arenas even
on a grouped server). ``server.admit(spec)`` turns the spec into a live
:class:`~repro.serve_filter.server.TenantHandle`.

:class:`TenantState` is the per-tenant lifecycle the registry drives::

    ADMITTED -> HYDRATING -> SERVING -> DRAINING -> RETIRED
                    ^  |         |
                    |  v         |
                    +- DEGRADED -+ (reload recovers; drain retires)

``handle.reload()`` re-enters HYDRATING from SERVING (an atomic swap —
no drain, no dropped rows) and returns to SERVING; every transition is
counted by ``ServeStats``. When hydration retries exhaust under a
:class:`~repro.serve_filter.faults.ReliabilityConfig` with
``degraded=True``, the tenant lands in ``DEGRADED`` instead of wedging:
it keeps answering from its last-good epoch — or, never hydrated, from
its fixup/backup Bloom structure alone (conservative: still zero false
negatives, FPR up to ~1 until a reload restores the model).
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Sequence, Tuple

from jax.sharding import Mesh

from repro.core import existence
from repro.serve_filter.faults import FaultConfig, ReliabilityConfig
from repro.serve_filter.plan import (DEFAULT_TILE_ROWS, ProbeConfig,
                                     QuantConfig)

# the scheduler's historical default ladder (re-exported by scheduler.py)
DEFAULT_BUCKETS = (64, 256, 1024, 4096)


class TenantState(enum.Enum):
    """Lifecycle of one tenant inside a registry/server."""
    ADMITTED = "admitted"      # spec accepted, nothing on device yet
    HYDRATING = "hydrating"    # loading + placing arrays (also: reloading)
    SERVING = "serving"        # live, accepting submissions
    DRAINING = "draining"      # submissions rejected, queued work finishing
    RETIRED = "retired"        # gone from the registry
    DEGRADED = "degraded"      # hydration exhausted: last-good epoch or
                               # backup-Bloom-only answers until a reload


# legal transitions; None is the pre-admission pseudo-state
LIFECYCLE_TRANSITIONS = {
    None: (TenantState.ADMITTED,),
    TenantState.ADMITTED: (TenantState.HYDRATING,),
    TenantState.HYDRATING: (TenantState.SERVING,
                            TenantState.RETIRED,    # failed fresh hydration
                            TenantState.DEGRADED),  # retries exhausted
    TenantState.SERVING: (TenantState.HYDRATING,   # hot-reload re-entry
                          TenantState.DRAINING),
    TenantState.DRAINING: (TenantState.RETIRED,),
    TenantState.RETIRED: (),
    TenantState.DEGRADED: (TenantState.HYDRATING,  # reload recovery
                           TenantState.DRAINING),
}


@dataclasses.dataclass(frozen=True)
class BucketConfig:
    """The scheduler's padding-bucket ladder: every dispatch is padded
    up to the smallest bucket that fits, so the number of compiled
    (plan-shape, batch-shape) programs stays bounded."""
    sizes: Tuple[int, ...] = DEFAULT_BUCKETS

    def __post_init__(self):
        sizes = tuple(sorted(int(b) for b in self.sizes))
        if not sizes or sizes[0] < 1:
            raise ValueError("buckets must be a non-empty ladder of "
                             "positive sizes")
        object.__setattr__(self, "sizes", sizes)


@dataclasses.dataclass(frozen=True)
class PlacementConfig:
    """Where tenants' arrays live: ``mesh=None`` plans local placement;
    a mesh whose ``shard_axis`` has >= 2 devices plans sharded placement
    (tables row-sharded, fixup bitset word-sharded over that axis)."""
    mesh: Optional[Mesh] = None
    shard_axis: str = "data"


@dataclasses.dataclass(frozen=True)
class DispatchConfig:
    """Host-side dispatch pipelining: ``async_dispatch=True`` keeps up
    to ``max_inflight`` dispatched batches un-retired so host padding
    overlaps device compute (2 = classic double buffer)."""
    async_dispatch: bool = False
    max_inflight: int = 2

    def __post_init__(self):
        if self.max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")


# GroupingConfig.placement values: how grouping composes with the
# server's PlacementConfig (the two are orthogonal axes of the executor
# core — see repro.serve_filter.executors)
GROUP_PLACEMENT_AUTO = "auto"    # arenas follow the plan placement:
                                 # on a sharded server the arenas are
                                 # themselves mesh-sharded
GROUP_PLACEMENT_LOCAL = "local"  # arenas only for local plans: a mesh
                                 # wins over grouping (the pre-composition
                                 # behavior, for fleets that want sharded
                                 # tenants served per-tenant)


@dataclasses.dataclass(frozen=True)
class GroupingConfig:
    """Plan-group megabatching: stack same-group-key tenants into one
    device arena so a single dispatch answers many lightly-loaded
    tenants. ``tile_rows`` is the single-tenant tile granule.

    ``placement`` is the composition knob: ``"auto"`` (default) lets
    arenas follow the plan placement — on a mesh-sharded server the
    combined embedding matrix is row-sharded and the concatenated
    fixup bitsets word-sharded, so one megabatch dispatch serves many
    tenants AND splits their storage; ``"local"`` restores the old
    gating (sharded plans never group)."""
    enabled: bool = False
    tile_rows: int = DEFAULT_TILE_ROWS
    placement: str = GROUP_PLACEMENT_AUTO

    def __post_init__(self):
        if self.tile_rows < 1:
            raise ValueError("tile_rows must be >= 1")
        if self.placement not in (GROUP_PLACEMENT_AUTO,
                                  GROUP_PLACEMENT_LOCAL):
            raise ValueError(
                f"unknown grouping placement {self.placement!r}: "
                f"expected {GROUP_PLACEMENT_AUTO!r} or "
                f"{GROUP_PLACEMENT_LOCAL!r}")

    def groups_plan(self, plan) -> bool:
        """Whether a tenant on ``plan`` may join a plan-group arena
        under this config (the tenant's own ``groupable`` hint still
        applies on top)."""
        if not self.enabled:
            return False
        return (not plan.placement.sharded
                or self.placement == GROUP_PLACEMENT_AUTO)


@dataclasses.dataclass(frozen=True)
class MetricsConfig:
    """JSONL metrics sink (``runtime.MetricsLogger``) and span tracing
    (``runtime.trace.Tracer``). ``path``/``echo`` both off means no
    logger is constructed; ``trace`` (or a ``trace_path``) attaches a
    tracer to the scheduler's hot path, bounded to ``trace_events``
    retained spans (about nine a batch). ``server.dump_trace()``
    exports Chrome trace-event JSON to ``trace_path`` (or an explicit
    path) — ``server.close()`` dumps automatically when ``trace_path``
    is set. An enabled tracer also writes its spans into a running
    ``jax.profiler`` trace (``runtime/trace.py``)."""
    path: Optional[str] = None
    echo: bool = False
    trace: bool = False
    trace_path: Optional[str] = None
    trace_events: int = 65536

    @property
    def enabled(self) -> bool:
        return bool(self.path or self.echo)

    @property
    def trace_enabled(self) -> bool:
        return bool(self.trace or self.trace_path)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Frozen, declarative configuration for a whole ``FilterServer``."""
    budget_mb: Optional[float] = None
    buckets: BucketConfig = BucketConfig()
    placement: PlacementConfig = PlacementConfig()
    dispatch: DispatchConfig = DispatchConfig()
    grouping: GroupingConfig = GroupingConfig()
    probe: ProbeConfig = ProbeConfig()
    quant: QuantConfig = QuantConfig()
    metrics: MetricsConfig = MetricsConfig()
    faults: FaultConfig = FaultConfig()
    reliability: ReliabilityConfig = ReliabilityConfig()

    @classmethod
    def from_kwargs(cls, *, budget_mb: Optional[float] = None,
                    buckets: Sequence[int] = DEFAULT_BUCKETS,
                    use_kernel: bool = False,
                    interpret: Optional[bool] = None,
                    block_n: int = 2048,
                    mesh: Optional[Mesh] = None,
                    shard_axis: str = "data",
                    async_dispatch: bool = False,
                    max_inflight: int = 2,
                    grouped: bool = False,
                    tile_rows: int = DEFAULT_TILE_ROWS,
                    quantized: bool = False,
                    quant_bits: int = 8,
                    quant_grid: str = "linear",
                    quant_row_group: int = 32,
                    metrics_path: Optional[str] = None,
                    metrics_echo: bool = False,
                    trace: bool = False,
                    trace_path: Optional[str] = None) -> "ServeConfig":
        """Bridge from the legacy ``FilterServer`` kwarg surface (the
        deprecated constructor routes through here)."""
        return cls(
            budget_mb=budget_mb,
            buckets=BucketConfig(tuple(buckets)),
            placement=PlacementConfig(mesh=mesh, shard_axis=shard_axis),
            dispatch=DispatchConfig(async_dispatch=bool(async_dispatch),
                                    max_inflight=int(max_inflight)),
            grouping=GroupingConfig(enabled=bool(grouped),
                                    tile_rows=int(tile_rows)),
            probe=ProbeConfig(use_kernel=bool(use_kernel),
                              interpret=interpret, block_n=int(block_n)),
            quant=QuantConfig(enabled=bool(quantized),
                              bits=int(quant_bits),
                              grid=str(quant_grid),
                              row_group=int(quant_row_group)),
            metrics=MetricsConfig(path=metrics_path,
                                  echo=bool(metrics_echo),
                                  trace=bool(trace),
                                  trace_path=trace_path))

    # ------------------------------------------------------------- wire
    def to_wire(self) -> dict:
        """Versioned JSON-ready form (``fleet.wire``): what a router
        ships to a remote host. Raises when the config holds a live
        mesh — device layout never crosses the wire."""
        from repro.serve_filter.fleet import wire
        return wire.config_to_wire(self)

    @classmethod
    def from_wire(cls, payload: dict) -> "ServeConfig":
        """Exact inverse of :meth:`to_wire` (closed schema: unknown
        keys and version mismatches are loud ``WireError``\\ s)."""
        from repro.serve_filter.fleet import wire
        return wire.config_from_wire(payload)


@dataclasses.dataclass(frozen=True, eq=False)
class TenantSpec:
    """Declarative description of one tenant: id, source, placement
    hints. Exactly one source must be given — an in-memory fitted
    ``index``, or a ``checkpoint`` directory (the tenant hydrates from
    ``<checkpoint>/<tenant>``, optionally at a specific ``step``).

    ``pinned`` tenants are never LRU-evicted by the memory budget;
    ``groupable=False`` opts a tenant out of plan-group arenas (a heavy
    tenant that fills buckets alone gains nothing from megabatching and
    would drag arena recompiles behind it)."""
    tenant: str
    index: Optional[existence.ExistenceIndex] = None
    checkpoint: Optional[str] = None
    step: Optional[int] = None
    pinned: bool = False
    groupable: bool = True

    def __post_init__(self):
        if not self.tenant or not isinstance(self.tenant, str):
            raise ValueError("tenant must be a non-empty string")
        if (self.index is None) == (self.checkpoint is None):
            raise ValueError(
                f"tenant {self.tenant!r} needs exactly one source: an "
                "in-memory index or a checkpoint directory")
        if self.step is not None and self.checkpoint is None:
            raise ValueError("step only applies to a checkpoint source")

    # ------------------------------------------------------------- wire
    def to_wire(self) -> dict:
        """Versioned JSON-ready form (``fleet.wire``). Only
        checkpoint-sourced specs serialize — an in-memory index is
        process-local by definition."""
        from repro.serve_filter.fleet import wire
        return wire.spec_to_wire(self)

    @classmethod
    def from_wire(cls, payload: dict) -> "TenantSpec":
        """Exact inverse of :meth:`to_wire`."""
        from repro.serve_filter.fleet import wire
        return wire.spec_from_wire(payload)
