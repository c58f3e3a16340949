"""Multi-tenant filter registry: placement + an explicit tenant lifecycle.

Each tenant/dataset id maps to a :class:`FilterEntry` bundling the
fitted ``ExistenceIndex``, its :class:`~repro.serve_filter.plan.QueryPlan`,
the (cached) executor compiled for that plan, the tenant's
device-placed arrays (:class:`~repro.serve_filter.executors.PlacedFilter`
— on a sharded registry each hydrated tenant's tables/bitset land
directly on their shard), and per-filter memory accounting. A registry
optionally enforces a total memory budget with LRU eviction (``pinned``
tenants are exempt), and round-trips filters through
``checkpoint/manager.py`` so a serving process can hydrate tenants from
disk. Evicting the last tenant on a plan also releases the plan's
cached executor, so compiled-program count tracks live tenants rather
than all-time churn.

Every tenant moves through the explicit lifecycle of
:class:`~repro.serve_filter.config.TenantState`::

    ADMITTED -> HYDRATING -> SERVING -> DRAINING -> RETIRED

:meth:`FilterRegistry.admit` drives the left half (a
:class:`~repro.serve_filter.config.TenantSpec` in, a SERVING entry
out); re-admitting a SERVING tenant is the **hot-reload** path — the
entry re-enters HYDRATING, the re-fitted index's arrays are installed
(an in-place arena-slot swap on the grouped path, a fresh
``PlacedFilter`` on local/sharded), and the tenant returns to SERVING
with its ``epoch`` bumped, all without draining: batches already
dispatched hold the old device arrays and retire against them, batches
prepared afterwards bind the new ones. :meth:`begin_drain` +
:meth:`evict` drive the right half. Every transition is validated
against ``config.LIFECYCLE_TRANSITIONS`` and reported through the
``on_transition`` hook (the server wires it to ``ServeStats``).

Reliability: under a :class:`~repro.serve_filter.faults.ReliabilityConfig`
with ``retries > 0``, transient hydration failures (injected faults,
checkpoint corruption) are retried with a capped, seeded
exponential-backoff schedule (:func:`~repro.serve_filter.faults.backoff_delays`).
When retries exhaust and ``degraded=True``, the tenant enters
``DEGRADED`` instead of wedging or vanishing: a reloading tenant keeps
serving its last-good epoch; a never-hydrated tenant gets a
**backup-only** entry that answers conservatively from its fixup/backup
Bloom structure alone (:func:`existence.load_fixup_only` — a selective
CRC-verified read). Backup-only answers treat the unavailable model as
all-positive — the degenerate sandwich bound of Mitzenmacher
(arXiv 1901.00902): zero false negatives are preserved but the FPR
rises toward 1 until a successful ``reload`` restores the model and the
tenant returns to SERVING.

With grouping enabled the registry additionally maintains plan-group
membership: groupable tenants whose plans share a
:class:`~repro.serve_filter.plan.GroupKey` live stacked in ONE
:class:`~repro.serve_filter.arena.PlanGroupArena` (registration and
checkpoint hydration write straight into an arena slot), so the
scheduler can answer many tenants per device dispatch. Grouping
COMPOSES with placement: on a mesh-sharded registry the group keys
carry the sharded placement and the arenas are themselves mesh-sharded
(combined embedding matrix row-sharded, concatenated bitsets
word-sharded), unless ``GroupingConfig.placement="local"`` restores
the old mesh-wins gating. Eviction frees the tenant's slot for reuse
and compacts the arena once churn leaves more holes than live tenants
— LRU churn cannot leak arena rows — and the last tenant out releases
the group's cached megabatch executor.
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from repro.core import existence, fixup as fixup_lib, memory
from repro.runtime.trace import NULL_TRACER, Tracer
from repro.serve_filter import executors as executors_lib
from repro.serve_filter.arena import PlanGroupArena
from repro.serve_filter.config import (GroupingConfig, LIFECYCLE_TRANSITIONS,
                                       PlacementConfig, TenantSpec,
                                       TenantState)
from repro.serve_filter.faults import (NULL_INJECTOR, CheckpointCorruption,
                                       FaultInjector, InjectedFault,
                                       ReliabilityConfig, backoff_delays)
from repro.serve_filter.plan import (GroupKey, ProbeConfig, QuantConfig,
                                     QueryPlan, group_key, plan_query,
                                     quant_meta)

# hydration failure kinds the retry loop treats as TRANSIENT: injected
# faults (chaos), and corrupt/unreadable checkpoint reads (a writer may
# be mid-replace, or the next keep-N step may land). Anything else —
# planner bugs, OOM, bad specs — fails fast like before.
TRANSIENT_HYDRATION_ERRORS = (InjectedFault, CheckpointCorruption)

# hook signature: (tenant, from_state_or_None, to_state)
TransitionHook = Callable[[str, Optional[TenantState], TenantState], None]


@dataclasses.dataclass
class FilterEntry:
    tenant: str
    index: Optional[existence.ExistenceIndex]  # None: backup-only entry
    plan: Optional[QueryPlan]       # None when backup-only
    executor: object                # Executor/GroupedExecutor; None when
                                    # backup-only (degraded, no model)
    placed: Optional[executors_lib.PlacedFilter]  # None when grouped
    model_mb: float
    fixup_mb: float
    last_used: int = 0              # registry LRU clock tick
    n_queries: int = 0
    group: Optional[PlanGroupArena] = None   # set iff grouped placement
    state: TenantState = TenantState.SERVING
    pinned: bool = False            # exempt from LRU budget eviction
    groupable: bool = True          # may join a plan-group arena
    epoch: int = 0                  # bumped on every hot-reload
    backup_only: Optional[fixup_lib.FixupFilter] = None  # degraded path
    n_cols_hint: int = 0            # query width when index is None

    def run(self, raw_ids):
        """One fused dispatch: (n, n_cols) ids -> (ans, model, backup).
        With JAX's async dispatch this returns un-materialized device
        arrays immediately — the scheduler exploits that to overlap
        host-side padding with device compute. A grouped entry runs
        through its arena's megabatch program (constant tenant_idx);
        the scheduler upgrades that to true multi-tenant batches.

        A backup-only (DEGRADED, never-hydrated) entry has no model: it
        answers conservatively, treating the unavailable model as
        all-positive — the degenerate sandwich bound. Zero false
        negatives survive; the FPR is ~1 until a reload restores the
        model. The real backup-Bloom probe is still reported so the
        stage decomposition stays observable."""
        if self.executor is None:
            n = np.asarray(raw_ids).shape[0]
            ones = np.ones(n, dtype=bool)
            backup = np.asarray(self.backup_only.query(raw_ids))
            return ones, ones, backup
        if self.group is not None:
            return self.group.run_single(raw_ids, self.slot)
        return self.executor(self.placed, self.index.tau, raw_ids)

    @property
    def slot(self) -> int:
        """Arena slot id (grouped entries only). Never cached: arena
        compaction renumbers slots."""
        return self.group.slot_of(self.tenant)

    @property
    def fused(self):
        """The executor's raw jitted callable (back-compat surface)."""
        return self.executor.fn

    @property
    def bits(self) -> jax.Array:
        if self.group is not None:
            return self.group.device_arrays()[1]
        return self.placed.bits

    @property
    def total_mb(self) -> float:
        return self.model_mb + self.fixup_mb

    @property
    def n_cols(self) -> int:
        if self.index is None:
            return self.n_cols_hint
        return self.index.cfg.plan.n_columns


class FilterRegistry:
    """Loads/owns multiple fitted indexes keyed by tenant id.

    ``budget_mb`` bounds the summed per-filter memory (weights + packed
    fixup bitset); admitting past the budget evicts least-recently-used
    unpinned tenants first. ``probe`` selects the fixup-probe flavor for
    all tenants' plans; ``placement`` with a mesh whose shard axis has
    >= 2 devices makes the planner choose sharded placement (every
    admitted/hydrated tenant's embedding tables and fixup bitset are
    scattered straight onto their shard slices); ``grouping.enabled``
    stacks same-group-key groupable tenants into per-group device
    arenas so one dispatch can serve many of them. The two compose:
    with both configured, the arenas themselves are mesh-sharded
    (``grouping.placement="local"`` keeps sharded tenants out of
    arenas instead).

    ``quant.enabled`` turns on compressed storage for every admitted
    tenant: the plan (and so the group key) carries the
    :class:`~repro.serve_filter.plan.QuantConfig`, quantization +
    threshold calibration happen once at admit/reload time, and the
    placed arrays / arena slots hold int8 payloads with fused dequant
    in the compiled programs. Quantized and fp32 tenants never share a
    program or an arena (the config is part of both cache keys).

    ``budget_mb`` counts NOMINAL per-filter sizes (weights + packed
    bitset). A grouped arena's real footprint carries bounded overhead
    on top (e_max-padded embedding columns, <= 2x slot headroom after
    growth, <= 1.5x bitset over-allocation; compaction reclaims churn)
    — observable as ``arena_mb`` in the server stats snapshot and
    ``PlanGroupArena.nbytes``.
    """

    def __init__(self, budget_mb: Optional[float] = None, *,
                 probe: ProbeConfig = ProbeConfig(),
                 placement: PlacementConfig = PlacementConfig(),
                 grouping: GroupingConfig = GroupingConfig(),
                 quant: QuantConfig = QuantConfig(),
                 reliability: ReliabilityConfig = ReliabilityConfig(),
                 on_transition: Optional[TransitionHook] = None,
                 tracer: Optional[Tracer] = None,
                 injector: FaultInjector = NULL_INJECTOR,
                 stats=None):
        self.budget_mb = budget_mb
        self.probe = probe
        self.placement = placement
        self.grouping = grouping
        self.quant = quant
        self.reliability = reliability
        self.on_transition = on_transition
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.injector = injector
        self.stats = stats              # ServeStats or None (counters)
        self._entries: Dict[str, FilterEntry] = {}
        self._groups: Dict[GroupKey, PlanGroupArena] = {}
        self._clock = itertools.count(1)
        self.evictions: List[str] = []

    # back-compat accessors (pre-config callers and sibling modules)
    @property
    def mesh(self):
        return self.placement.mesh

    @property
    def shard_axis(self) -> str:
        return self.placement.shard_axis

    @property
    def grouped(self) -> bool:
        return self.grouping.enabled

    @property
    def tile_rows(self) -> int:
        return self.grouping.tile_rows

    # ------------------------------------------------------------ access
    def __contains__(self, tenant: str) -> bool:
        return tenant in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def tenants(self) -> List[str]:
        return list(self._entries)

    @property
    def total_mb(self) -> float:
        return sum(e.total_mb for e in self._entries.values())

    def tenant_device_bytes(self) -> int:
        """Device bytes the live entries hold outside their arenas:
        each index's params, fixup bits and quant cache, an ungrouped
        entry's placed arrays, a degraded entry's backup bits — where
        they are JAX arrays. Each array counts as the device stores
        it (``on_device_size_in_bytes``, so a TPU's tiled padding of
        the many small per-tenant arrays is included), once if several
        entries share it, and a sharded one by the slice one device
        holds. Walks every entry: a snapshot-time reading, never a
        per-batch one."""
        seen, total = set(), 0
        for e in self._entries.values():
            trees = []
            if e.placed is not None:
                trees += [e.placed.params, e.placed.bits]
            if e.index is not None:
                trees += [e.index.params, e.index.fixup_filter.bits,
                          (e.index.quant_cache or {}).get("qparams")]
            if e.backup_only is not None:
                trees.append(e.backup_only.bits)
            for leaf in jax.tree.leaves(trees):
                if isinstance(leaf, jax.Array) and id(leaf) not in seen:
                    seen.add(id(leaf))
                    total += leaf.on_device_size_in_bytes() // \
                        len(leaf.sharding.device_set)
        return total

    def get(self, tenant: str) -> FilterEntry:
        """Fetch + touch (bumps LRU recency)."""
        entry = self._entries[tenant]
        entry.last_used = next(self._clock)
        return entry

    def peek(self, tenant: str) -> Optional[FilterEntry]:
        """Fetch WITHOUT touching LRU recency (scheduler group scans)."""
        return self._entries.get(tenant)

    def tick(self) -> int:
        """Next LRU clock value — for callers that already hold an
        entry (from :meth:`peek`) and want to bump its recency without
        a second lookup: ``entry.last_used = registry.tick()``."""
        return next(self._clock)

    @property
    def groups(self) -> Dict[GroupKey, PlanGroupArena]:
        """Live plan-group arenas (read-only view for stats/tests)."""
        return dict(self._groups)

    def state_of(self, tenant: str) -> TenantState:
        """The tenant's lifecycle state (RETIRED once gone)."""
        entry = self._entries.get(tenant)
        return entry.state if entry is not None else TenantState.RETIRED

    def states(self) -> Dict[str, TenantState]:
        """Every live tenant's lifecycle state — the whole-host view a
        fleet router reads through the ``states`` host op to verify
        placement (SERVING on target before DRAINING on source)."""
        return {t: e.state for t, e in self._entries.items()}

    # --------------------------------------------------------- lifecycle
    def _transition(self, tenant: str, frm: Optional[TenantState],
                    to: TenantState) -> None:
        if to not in LIFECYCLE_TRANSITIONS[frm]:
            raise RuntimeError(
                f"illegal lifecycle transition for tenant {tenant!r}: "
                f"{frm.value if frm else None} -> {to.value}")
        if self.on_transition is not None:
            self.on_transition(tenant, frm, to)

    def plan_for(self, index: existence.ExistenceIndex) -> QueryPlan:
        """The plan this registry's planner assigns an index."""
        return plan_query(index.cfg, index.fixup_filter.params,
                          mesh=self.placement.mesh,
                          shard_axis=self.placement.shard_axis,
                          probe=self.probe, quant=self.quant)

    def admit(self, spec: TenantSpec) -> FilterEntry:
        """Drive a tenant spec through ADMITTED -> HYDRATING -> SERVING.

        A fresh tenant is admitted; re-admitting a SERVING tenant is
        the **hot-reload** path: the tenant re-enters HYDRATING, the
        new source's arrays are installed atomically (arena-slot swap
        when the plan group is unchanged, otherwise a fresh placement),
        and the entry returns to SERVING with ``epoch + 1`` — no drain,
        and batches already dispatched still retire against the old
        arrays. Evicts LRU unpinned tenants if over budget.
        """
        tenant = spec.tenant
        prev = self._entries.get(tenant)
        prev_state = prev.state if prev is not None else None
        if prev is None:
            self._transition(tenant, None, TenantState.ADMITTED)
            self._transition(tenant, TenantState.ADMITTED,
                             TenantState.HYDRATING)
        else:
            if prev.state not in (TenantState.SERVING,
                                  TenantState.DEGRADED):
                raise RuntimeError(
                    f"tenant {tenant!r} is {prev.state.value}; only a "
                    "serving or degraded tenant can be reloaded")
            self._transition(tenant, prev.state, TenantState.HYDRATING)
            prev.state = TenantState.HYDRATING
        try:
            with self.tracer.span(
                    "reload" if prev is not None else "admit",
                    cat="lifecycle", tenant=tenant):
                entry = self._hydrate_with_retries(spec, prev)
        except BaseException as err:
            # hydration failed: a transient error (bad checkpoint
            # path, device OOM) must not brick a live tenant. Three
            # distinct failure points, all resolved so the tenant
            # never dangles in HYDRATING:
            cur = self._entries.get(tenant)
            degrade = (self.reliability.degraded
                       and isinstance(err, TRANSIENT_HYDRATION_ERRORS))
            if prev is not None and cur is prev:
                if degrade:
                    # retries exhausted on a LIVE tenant: DEGRADED, not
                    # an outage — it keeps answering on its last-good
                    # epoch (or its backup bitset, if it never had a
                    # model) until a later reload succeeds
                    self._transition(tenant, TenantState.HYDRATING,
                                     TenantState.DEGRADED)
                    prev.state = TenantState.DEGRADED
                else:
                    # failed BEFORE the swap landed: roll the old entry
                    # back to where it was — it keeps answering on its
                    # current epoch and a later reload can retry
                    self._transition(tenant, TenantState.HYDRATING,
                                     prev_state)
                    prev.state = prev_state
            elif prev is None and cur is None:
                if degrade:
                    # fresh admission exhausted its retries: try to
                    # stand the tenant up on its backup Bloom structure
                    # alone (conservative answers, zero-FN preserved)
                    fallback = self._install_degraded(spec)
                    if fallback is not None:
                        self._transition(tenant, TenantState.HYDRATING,
                                         TenantState.DEGRADED)
                        fallback.state = TenantState.DEGRADED
                        self._enforce_budget(keep=tenant)
                        return fallback
                # no backup path either: terminate the lifecycle
                # (HYDRATING -> RETIRED) so the event log matches
                # state_of() reporting RETIRED
                self._transition(tenant, TenantState.HYDRATING,
                                 TenantState.RETIRED)
            elif cur is not None and cur is not prev:
                # the NEW entry already landed and the failure came
                # from releasing the old one (e.g. compaction OOM in
                # _release_entry): the swap is complete — mark the new
                # entry SERVING rather than wedging it in HYDRATING
                self._transition(tenant, TenantState.HYDRATING,
                                 TenantState.SERVING)
                cur.state = TenantState.SERVING
            raise
        self._transition(tenant, TenantState.HYDRATING, TenantState.SERVING)
        entry.state = TenantState.SERVING
        self._enforce_budget(keep=tenant)
        return entry

    def _hydrate_with_retries(self, spec: TenantSpec,
                              prev: Optional[FilterEntry]) -> FilterEntry:
        """One admit/reload hydration under the retry policy: transient
        failures (``TRANSIENT_HYDRATION_ERRORS``) are retried up to
        ``reliability.retries`` times with the seeded capped-backoff
        schedule. Retrying stops early when a failed attempt already
        blew ``attempt_timeout_s`` (slow-not-transient) or when a
        partial swap landed (retry would double-install)."""
        tenant = spec.tenant
        rel = self.reliability
        delays = backoff_delays(rel, self.injector.config.seed, tenant)
        attempt = 0
        while True:
            t0 = time.monotonic()
            try:
                return self._hydrate_once(spec, prev)
            except TRANSIENT_HYDRATION_ERRORS as err:
                if (isinstance(err, CheckpointCorruption)
                        and self.stats is not None):
                    self.stats.record_checksum_failure()
                if attempt >= len(delays):
                    raise
                if (rel.attempt_timeout_s is not None
                        and time.monotonic() - t0 > rel.attempt_timeout_s):
                    raise       # slow failure: classified non-transient
                if self._entries.get(tenant) is not prev:
                    raise       # partial swap landed; do not re-install
                if self.stats is not None:
                    self.stats.record_hydration_retry()
                time.sleep(delays[attempt])
                attempt += 1

    def _hydrate_once(self, spec: TenantSpec,
                      prev: Optional[FilterEntry]) -> FilterEntry:
        tenant = spec.tenant
        index = spec.index
        if index is None:
            self.injector.check("checkpoint_read", tenant)
            index = existence.load_index(
                os.path.join(spec.checkpoint, tenant), step=spec.step,
                tracer=self.tracer)
        self.injector.check("hydrate", tenant)
        return self._install(tenant, index, prev, pinned=spec.pinned,
                             groupable=spec.groupable)

    def _install_degraded(self, spec: TenantSpec
                          ) -> Optional[FilterEntry]:
        """Best-effort backup-only entry for a fresh admission whose
        hydration exhausted its retries: load just the fixup/backup
        bitset (selective CRC-verified read) and serve conservatively.
        Returns None when even the backup structure is unreachable."""
        tenant = spec.tenant
        try:
            if spec.index is not None:
                cfg = spec.index.cfg
                fx = spec.index.fixup_filter
            else:
                cfg, fx = existence.load_fixup_only(
                    os.path.join(spec.checkpoint, tenant), step=spec.step)
        except BaseException:
            return None
        entry = FilterEntry(
            tenant=tenant, index=None, plan=None, executor=None,
            placed=None, model_mb=0.0, fixup_mb=fx.size_mb,
            last_used=next(self._clock), state=TenantState.HYDRATING,
            pinned=spec.pinned, groupable=spec.groupable,
            backup_only=fx, n_cols_hint=cfg.plan.n_columns)
        self._entries[tenant] = entry
        return entry

    # ------------------------------------------------- mutation plumbing
    def _install(self, tenant: str, index: existence.ExistenceIndex,
                 prev: Optional[FilterEntry], *, pinned: bool,
                 groupable: bool) -> FilterEntry:
        """Place an index's arrays and swap the new entry in. The swap
        itself is a dict assignment — atomic from the scheduler's view:
        every prepare after this call binds the new arrays, every batch
        dispatched before it holds (and retires against) the old ones."""
        mem = memory.accounting(index.cfg)
        plan = self.plan_for(index)
        gk = (group_key(plan, self.grouping.tile_rows)
              if (groupable and self.grouping.groups_plan(plan))
              else None)
        common = dict(tenant=tenant, index=index, plan=plan,
                      model_mb=mem.weights_mb,
                      fixup_mb=index.fixup_filter.size_mb,
                      last_used=next(self._clock),
                      state=TenantState.HYDRATING,
                      pinned=pinned, groupable=groupable,
                      epoch=prev.epoch + 1 if prev is not None else 0)
        if gk is not None:
            arena = self._groups.get(gk)
            if arena is None:
                # a sharded group key hands the arena its mesh through
                # the executor, so the device views land on-shard
                arena = PlanGroupArena(
                    gk, executors_lib.acquire_grouped_executor(
                        gk, self.placement.mesh),
                    injector=self.injector, tracer=self.tracer)
                self._groups[gk] = arena
            try:
                if (prev is not None and prev.group is arena
                        and tenant in arena):
                    # hot-reload within the same plan group: in-place
                    # slot swap — the tenant's slot id (and any
                    # tile-signature assumptions built on it) survive
                    # the reload
                    arena.swap(tenant, index)
                else:
                    arena.add(tenant, index)
            except BaseException:
                # an arena freshly created for this admission must not
                # outlive the failure holding its executor ref (retry
                # exhaustion would otherwise leak empty arenas)
                if len(arena) == 0 and self._groups.get(gk) is arena:
                    del self._groups[gk]
                    executors_lib.release_grouped_executor(
                        gk, self.placement.mesh)
                raise
            entry = FilterEntry(executor=arena.executor, placed=None,
                                group=arena, **common)
        else:
            executor = executors_lib.acquire_executor(plan,
                                                      self.placement.mesh)
            try:
                self.injector.check("device_put", tenant)
                placed = executor.place(index)
            except BaseException:
                executors_lib.release_executor(plan, self.placement.mesh)
                raise
            entry = FilterEntry(executor=executor, placed=placed,
                                **common)
        self._entries[tenant] = entry
        if prev is not None:    # replaced: give back the old entry's ref
            self._release_entry(prev, replaced_by=entry)
        return entry

    def register(self, tenant: str, index: existence.ExistenceIndex,
                 *, pinned: bool = False, groupable: bool = True
                 ) -> FilterEntry:
        """Admit a fitted in-memory index (or hot-reload the tenant's
        current one) — shorthand for :meth:`admit` with an in-memory
        source."""
        return self.admit(TenantSpec(tenant=tenant, index=index,
                                     pinned=pinned, groupable=groupable))

    def begin_drain(self, tenant: str) -> None:
        """SERVING -> DRAINING: the scheduler keeps answering the
        tenant's already-queued rows but rejects new submissions; call
        :meth:`evict` once drained to finish the retirement."""
        entry = self._entries.get(tenant)
        if entry is None or entry.state is TenantState.DRAINING:
            return
        self._transition(tenant, entry.state, TenantState.DRAINING)
        entry.state = TenantState.DRAINING

    def evict(self, tenant: str) -> None:
        """Drop a tenant (-> RETIRED). Queued requests the scheduler
        still holds fail on its next pass; spans already dispatched
        retire normally against the arrays they were bound to."""
        entry = self._entries.get(tenant)
        if entry is None:
            return
        if entry.state in (TenantState.SERVING, TenantState.DEGRADED):
            self._transition(tenant, entry.state,
                             TenantState.DRAINING)
            entry.state = TenantState.DRAINING
        # validate against the entry's REAL state — anything but
        # DRAINING here (admit() rolls failed hydrations back) is an
        # illegal jump and must fail loudly, not fabricate events
        self._transition(tenant, entry.state, TenantState.RETIRED)
        entry.state = TenantState.RETIRED
        del self._entries[tenant]
        self.evictions.append(tenant)
        self._release_entry(entry)

    def _release_entry(self, entry: FilterEntry, *,
                       replaced_by: Optional[FilterEntry] = None) -> None:
        """Give back whatever the entry holds: its arena slot (grouped)
        or its per-plan executor reference. The last tenant out of an
        arena/plan drops the cached executor and its compiled programs;
        surviving arenas compact when churn leaves too many holes."""
        if entry.executor is None:
            return          # backup-only entry: nothing device-side held
        if entry.group is not None:
            arena = entry.group
            if replaced_by is not None and replaced_by.group is arena:
                # hot-swap in place: the slot was reused, but a re-fit
                # whose bitset GREW left the old word range dead —
                # compact when that waste piles up, or repeated
                # hot-swaps would leak arena words
                arena.maybe_compact()
                return
            arena.remove(entry.tenant)
            if len(arena) == 0:
                del self._groups[arena.key]
                executors_lib.release_grouped_executor(
                    arena.key, self.placement.mesh)
            else:
                arena.maybe_compact()
        else:
            # drop this tenant's reference; the cache entry (and compiled
            # programs) go away with the LAST reference process-wide, so
            # other registries serving the same plan are unaffected
            executors_lib.release_executor(entry.plan, self.placement.mesh)

    def _enforce_budget(self, keep: str) -> None:
        if self.budget_mb is None:
            return
        while self.total_mb > self.budget_mb and len(self._entries) > 1:
            victim = min(
                (e for t, e in self._entries.items()
                 if t != keep and not e.pinned),
                key=lambda e: e.last_used, default=None)
            if victim is None:      # everything else is pinned
                return
            self.evict(victim.tenant)

    # ------------------------------------------------------- persistence
    def save(self, tenant: str, directory: str, *, step: int = 0) -> str:
        """Write a tenant's filter under ``directory/<tenant>``.

        A quantized registry writes ``existence_index_v3``: the packed
        payload, scales, and calibrated tau ride along (reusing the
        tenant's cached quant state, so no extra quantize/calibrate
        runs), and a later hydration into the same QuantConfig skips
        calibration entirely — the quant reload fast path."""
        path = os.path.join(directory, tenant)
        quant = quant_meta(self.quant) if self.quant.enabled else None
        existence.save_index(path, self._entries[tenant].index, step=step,
                             quant=quant)
        return path

    def load(self, tenant: str, directory: str,
             step: Optional[int] = None) -> FilterEntry:
        """Hydrate a tenant from ``directory/<tenant>`` and admit it
        (on a sharded registry the arrays land directly on-shard)."""
        return self.admit(TenantSpec(tenant=tenant, checkpoint=directory,
                                     step=step))
