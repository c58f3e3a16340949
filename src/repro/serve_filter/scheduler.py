"""Micro-batching scheduler: admission queue, padding buckets, async dispatch.

The continuous-batching pattern from ``launch/serve.py`` adapted from
token-steps to one-shot membership queries: requests (a tenant id + a
block of raw-id rows) enter per-tenant FIFO queues; each ``step()``
coalesces waiting rows into one fused dispatch, padded up to a fixed
bucket size so every dispatch hits a pre-compiled (plan-shape, bucket)
XLA program instead of triggering a fresh trace per request shape.
Padding rows are all-wildcard and sliced off before answers are
scattered back to their requests. Tenants take dispatches round-robin
(the ``_order`` deque rotates after every pick, with a set mirror for
O(1) membership), so sustained load from one tenant cannot starve late
arrivals.

Coalescing is GROUP-AWARE: when the picked tenant's entry belongs to a
plan-group arena (grouping enabled on the registry) and its own rows
don't fill the bucket, the scheduler keeps pulling rows from the next
same-group tenants in ring order and dispatches ONE megabatch with a
per-row ``tenant_idx`` — so a fleet of lightly-loaded filters rides
bucket-1024-class dispatches instead of each paying a lonely bucket-64
one. Per-request scatter is unchanged (spans stay contiguous); the
round-robin ring still rotates on the picked tenant only, so tenants
in other groups keep their turn. The coalescing is PLACEMENT-AGNOSTIC:
grouping and placement are orthogonal executor axes, so the same
megabatch path drives local arenas and mesh-sharded ones (where the
arena arrays live split over a mesh axis) — the scheduler never looks
at where the arrays live.

``step()`` is split into a host half and a device half:

* **prepare** — pick the next tenant, pop row spans off its queue, and
  pad/coalesce them into a bucket-sized batch (pure host work);
* **dispatch** — hand the batch to the tenant's executor. JAX dispatch
  is asynchronous: the call returns un-materialized device arrays
  immediately while the device crunches.

With ``async_dispatch=True`` the scheduler keeps ONE dispatched batch
in flight between steps (a double buffer): batch *t+1* is prepared and
dispatched while the device still computes batch *t*; only then does
the scheduler block on *t*'s arrays and scatter its answers. Host
pad/scatter time thus overlaps device compute instead of serializing
with it. ``async_dispatch=False`` (default) retires every batch
immediately after its dispatch — the original synchronous behavior.

Bucket policy: the smallest bucket that fits the coalesced rows; rows
beyond the largest bucket stay queued for the next step (bounded
per-dispatch latency). Occupancy (valid/padded) is tracked per batch by
``ServeStats`` — the classic throughput-vs-padding trade.

Observability: the scheduler takes an optional ``runtime.trace.Tracer``
and emits one span per pipeline stage — ``prepare`` / ``dispatch`` /
``device_block`` / ``scatter_retire`` on the host thread (category
``serve``), plus a ``device_compute`` span on a synthetic ``device``
track covering dispatch -> materialization. In an exported Chrome trace
the async double buffer is therefore VISIBLE: prepare-of-batch-*t+1*
sits under device-compute of batch *t*. Spans of category ``detail``
split the stages: ``tiles`` (the arena's tile-cache lookup and, on a
miss, the weight gather) and ``launch`` (the fused program's dispatch)
inside ``dispatch``, ``stats`` (per-tenant stage sums and
``record_batch``) inside ``scatter_retire``, and the server's
``submit`` beside them. Every span also reaches the profiler as the
annotation ``serve.<name>`` (``runtime/trace.py``). Each request's
queue time (submit -> first dispatch) and end-to-end latency land in
``ServeStats``.

Completion surface: callers no longer poll ``QueryRequest.done`` — a
submission is observed through a :class:`QueryFuture` (``result``,
``exception``, bulk :func:`wait_all`). The scheduler resolves each
future at RETIRE time — the instant its request's last span lands (or
fails) — and, because serving is single-threaded, ``result()`` drives
``step()`` itself until that instant, dispatching whatever batches are
ahead of it in ring order but leaving every other queued request
queued (no drain-the-world side effect). Admission is
lifecycle-gated: only SERVING (or DEGRADED — conservative answers, see
``registry``) tenants accept submissions — a DRAINING tenant's queued
rows still complete, but new rows are rejected.

Reliability surface (all off by default, enabled per
:class:`~repro.serve_filter.faults.ReliabilityConfig`):

* **deadlines** — ``submit(..., deadline_ms=)`` attaches a per-request
  budget; each ``step()`` first retires still-queued past-deadline
  requests, whose futures raise
  :class:`~repro.serve_filter.faults.DeadlineExceeded` instead of
  hanging. Rows already dispatched retire with answers — the device
  work is paid for either way;
* **backpressure** — ``max_queued_rows`` bounds the total queued rows:
  a ``submit``/``submit_many`` that would exceed it is rejected whole
  with :class:`~repro.serve_filter.faults.Overloaded` (shed BEFORE
  queuing — the caller keeps no half-admitted handles) and the shed
  rows counted in ``stats_snapshot()['shed_rows']``;
* **dispatch watchdog** — the device-block wait runs under
  ``runtime.fault.StepTimer`` (relative stragglers) plus an absolute
  ``dispatch_timeout_s`` bound; breaches land in ``stuck_batches`` /
  ``stragglers``;
* **injection** — a dispatch-site
  :class:`~repro.serve_filter.faults.InjectedFault` requeues the
  prepared spans (rows never lost) and the step counts as progress, so
  a chaos storm degrades throughput instead of crashing the pump.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Deque, Dict, Iterable, List, Optional, Sequence, Set, \
    Tuple

import numpy as np

from repro.runtime.fault import StepTimer
from repro.runtime.trace import NULL_TRACER, Tracer
from repro.serve_filter import executors
from repro.serve_filter.config import DEFAULT_BUCKETS, TenantState
# FilterServeError moved to faults.py (typed errors need it as a base
# without a circular import); re-exported here for back-compat
from repro.serve_filter.faults import (NULL_INJECTOR, DeadlineExceeded,
                                       FaultInjector, FilterServeError,
                                       InjectedFault, Overloaded,
                                       ReliabilityConfig)
from repro.serve_filter.registry import FilterEntry, FilterRegistry
from repro.serve_filter.stats import ServeStats


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n (n must not exceed the largest bucket)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"batch of {n} exceeds largest bucket {buckets[-1]}")


@dataclasses.dataclass(slots=True)
class QueryRequest:
    """One admitted query block. The result arrays (``answers``,
    ``model_yes``, ``backup_yes``) are owned by the scheduler and must
    be treated as READ-ONLY: single-span requests receive zero-copy
    views of the batch output (non-writeable), multi-span requests a
    private buffer — copy before mutating."""
    rid: int
    tenant: str
    ids: np.ndarray                       # (n, n_cols) int32 raw ids
    t_submit: float
    t_first_dispatch: Optional[float] = None  # queue time endpoint
    answers: Optional[np.ndarray] = None  # (n,) bool when done
    model_yes: Optional[np.ndarray] = None
    backup_yes: Optional[np.ndarray] = None
    t_done: Optional[float] = None
    error: Optional[str] = None           # set when failed (e.g. eviction)
    error_cls: Optional[type] = None      # typed failure (DeadlineExceeded)
    t_deadline: Optional[float] = None    # absolute budget (clock domain)
    future: Optional["QueryFuture"] = None  # resolved at retire time

    @property
    def done(self) -> bool:
        """Fully answered (or failed) — NOT merely partially scattered:
        a multi-dispatch request stays pending until its last rows land.
        """
        return self.t_done is not None

    @property
    def latency_s(self) -> float:
        assert self.t_done is not None
        return self.t_done - self.t_submit

    def _complete(self, t_done: float, error: Optional[str] = None,
                  error_cls: Optional[type] = None) -> None:
        """Mark done (once) and resolve the attached future, if any."""
        if self.t_done is None:
            if error is not None:
                self.error = error
                self.error_cls = error_cls
            self.t_done = t_done
        if self.future is not None:
            self.future._resolve()

    def _raise_type(self) -> type:
        return self.error_cls or FilterServeError


class QueryFuture:
    """Completion handle for one submitted query block.

    Serving is single-threaded, so the future is also the pump:
    ``result()``/``exception()`` drive ``scheduler.step()`` until THIS
    request retires — batches ahead of it in ring order get dispatched
    (the device must answer them anyway), but every other queued
    request stays queued. That scoping is the fix for the old
    ``FilterServer.query`` convenience, which drained the entire
    scheduler (silently retiring OTHER tenants' pending requests) as a
    side effect of answering one block.

    The scheduler resolves the future at retire time; after that,
    ``answers`` / ``model_yes`` / ``backup_yes`` expose the scheduler-
    owned result arrays (treat as read-only — see ``QueryRequest``).

    Migration note: ``done`` here is a METHOD (``concurrent.futures``
    idiom), unlike the old ``QueryRequest.done`` property — a
    transplanted ``while not req.done`` poll over a future is always
    falsy-negated-truthy and exits immediately. It then fails fast
    (``answers`` is still None), but prefer ``result()``/``wait_all``
    over polling entirely.
    """

    def __init__(self, request: QueryRequest, scheduler: "QueryScheduler"):
        self._request = request
        self._scheduler = scheduler
        self._resolved = request.done       # zero-row fast path
        request.future = self

    def _resolve(self) -> None:
        """Called by the scheduler the instant the request retires (or
        fails) — the ONLY thing that completes a future: ``done()`` and
        the waiters observe this flag, not the request's fields."""
        self._resolved = True

    # ------------------------------------------------------------- state
    @property
    def tenant(self) -> str:
        return self._request.tenant

    @property
    def request(self) -> QueryRequest:
        """The underlying request (scheduler-internal surface)."""
        return self._request

    def done(self) -> bool:
        return self._resolved

    @property
    def error(self) -> Optional[str]:
        return self._request.error

    @property
    def answers(self) -> Optional[np.ndarray]:
        return self._request.answers

    @property
    def model_yes(self) -> Optional[np.ndarray]:
        return self._request.model_yes

    @property
    def backup_yes(self) -> Optional[np.ndarray]:
        return self._request.backup_yes

    # -------------------------------------------------------- completion
    def _wait(self, deadline: Optional[float]) -> None:
        while not self._resolved:
            if deadline is not None and time.monotonic() >= deadline:
                raise TimeoutError(
                    f"request {self._request.rid} (tenant "
                    f"{self._request.tenant!r}) not retired in time")
            try:
                progressed = self._scheduler.step()
            except InjectedFault:
                # a chaos-injected dispatch fault escaped the pump
                # (non-transient classification): with no timeout we
                # re-raise — the waiter must not spin forever — but a
                # bounded wait keeps driving; the spans were requeued
                if deadline is None:
                    raise
                continue
            if self._resolved:
                # the step that resolved THIS future (e.g. by expiring
                # its deadline) may also be the drained step — check
                # resolution before judging progress
                break
            if not progressed:
                # nothing queued, nothing in flight, yet unresolved:
                # the rows were lost upstream — fail loudly
                raise FilterServeError(
                    "scheduler drained without resolving this future")

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block (driving the scheduler) until this request retires;
        return its (n,) bool answers or raise its failure (typed:
        ``DeadlineExceeded`` for an expired request, ``FilterServeError``
        otherwise). ``timeout`` bounds the drive loop itself — a wedged
        scheduler surfaces as ``TimeoutError`` instead of a hang."""
        self._wait(None if timeout is None
                   else time.monotonic() + timeout)
        if self._request.error is not None:
            raise self._request._raise_type()(self._request.error)
        return self._request.answers

    def exception(self, timeout: Optional[float] = None
                  ) -> Optional[Exception]:
        """Like :meth:`result`, but return the failure (or None)."""
        self._wait(None if timeout is None
                   else time.monotonic() + timeout)
        if self._request.error is not None:
            return self._request._raise_type()(self._request.error)
        return None


def wait_all(futures: Iterable[QueryFuture],
             timeout: Optional[float] = None) -> List[QueryFuture]:
    """Drive the scheduler until every future is resolved (one shared
    ``timeout`` across the batch); returns the futures for chaining.
    Failures surface when each future's ``result()`` is read — a failed
    request does not abort the rest of the batch here."""
    futures = list(futures)
    deadline = None if timeout is None else time.monotonic() + timeout
    for fut in futures:
        fut._wait(deadline)
    return futures


@dataclasses.dataclass(slots=True)
class _Prepared:
    """Host half of one dispatch: padded batch + scatter plan."""
    tenant: str                                 # picked (primary) tenant
    entry: FilterEntry                          # its registry entry
    take: List[Tuple[QueryRequest, int, int]]   # (request, row offset, rows)
    span_entries: List[FilterEntry]             # per-span owning entry
    span_pos: List[int]                         # per-span batch position
    batch: np.ndarray                           # (bucket, n_cols) padded
    bucket: int
    n_total: int                                # valid rows (gaps excluded)
    slots: Optional[np.ndarray] = None          # (bucket,) arena slot ids
    group: Optional[object] = None              # PlanGroupArena if grouped
    valid_idx: Optional[np.ndarray] = None      # set iff alignment gaps
    seq: int = 0                                # batch sequence (tracing)


@dataclasses.dataclass(slots=True)
class _InFlight:
    """Device half: a dispatched batch awaiting retirement."""
    prep: _Prepared
    outputs: tuple            # (ans, model, backup) device arrays
    t_dispatch: float


class QueryScheduler:
    def __init__(self, registry: FilterRegistry,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 stats: Optional[ServeStats] = None,
                 clock=time.perf_counter, *,
                 async_dispatch: bool = False,
                 max_inflight: int = 2,
                 tracer: Optional[Tracer] = None,
                 injector: FaultInjector = NULL_INJECTOR,
                 reliability: ReliabilityConfig = ReliabilityConfig()):
        self.registry = registry
        self.buckets = tuple(sorted(int(b) for b in buckets))
        self.stats = stats or ServeStats()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._clock = clock
        self._rid = itertools.count()
        self._seq = itertools.count()       # batch sequence, for traces
        self.injector = injector
        self.max_queued_rows = reliability.max_queued_rows
        self.dispatch_timeout_s = reliability.dispatch_timeout_s
        # dispatch watchdog: relative stragglers (trailing-median) plus
        # the absolute dispatch_timeout_s bound counted in stuck_batches
        self.watchdog = StepTimer()
        self.stuck_batches = 0
        self.dispatch_faults = 0            # injected dispatch faults seen
        self._has_deadlines = False
        self.async_dispatch = bool(async_dispatch)
        # batches allowed past dispatch before the oldest must retire;
        # 1 = synchronous, 2 = classic double buffer
        self.max_inflight = max(1, int(max_inflight)) if async_dispatch else 1
        # per-tenant FIFO of (request, first row not yet taken)
        self._queues: Dict[str, Deque[Tuple[QueryRequest, int]]] = \
            collections.defaultdict(collections.deque)
        self._order: Deque[str] = collections.deque()   # round-robin ring
        self._order_set: Set[str] = set()               # O(1) membership
        self._inflight: Deque[_InFlight] = collections.deque()

    # ------------------------------------------------------------ intake
    def submit(self, tenant: str, ids: np.ndarray,
               deadline_ms: Optional[float] = None) -> QueryRequest:
        """Admit one request; rows may exceed the largest bucket (they
        will be answered across several dispatches). ``deadline_ms``
        bounds how long the rows may wait QUEUED: a request still
        undispatched when the budget expires retires with
        :class:`DeadlineExceeded` instead of hanging."""
        return self.submit_many(((tenant, ids),),
                                deadline_ms=deadline_ms)[0]

    def submit_many(self, items,
                    deadline_ms: Optional[float] = None
                    ) -> List[QueryRequest]:
        """Bulk admission: ``[(tenant, ids), ...]`` -> requests, in
        order. One call per fleet tick instead of one per tenant — the
        megabatch regime serves thousands of small requests per second,
        so per-request Python overhead is the serving bottleneck once
        dispatches are grouped; this path keeps the hot loop tight
        (locals bound once, validation per item preserved).

        With ``max_queued_rows`` configured, a call whose rows would
        push the queued total past the bound is rejected WHOLE with
        :class:`Overloaded` before anything is queued — load shedding
        happens at admission, where the caller can still retry/route,
        not deep in the dispatch path."""
        registry = self.registry
        queues = self._queues
        order = self._order
        order_set = self._order_set
        clock = self._clock
        rid = self._rid
        # validate EVERYTHING first: a bad item must reject the whole
        # call before any request is queued, or the caller loses the
        # handles of the items admitted ahead of the failure
        checked = []
        new_rows = 0
        for tenant, ids in items:
            entry = registry.peek(tenant)
            if entry is None:
                raise KeyError(f"unknown tenant {tenant!r}")
            if entry.state not in (TenantState.SERVING,
                                   TenantState.DEGRADED):
                raise FilterServeError(
                    f"tenant {tenant!r} is {entry.state.value}, not "
                    "serving — submissions rejected")
            ids = np.asarray(ids, np.int32)
            if ids.ndim == 1:
                ids = ids[None, :]
            if ids.shape[-1] != entry.n_cols:
                raise ValueError(
                    f"tenant {tenant!r} expects {entry.n_cols} columns, "
                    f"got {ids.shape[-1]}")
            checked.append((tenant, entry, ids))
            new_rows += ids.shape[0]
        if (self.max_queued_rows is not None and new_rows
                and self.pending_rows + new_rows > self.max_queued_rows):
            self.stats.record_shed(new_rows)
            raise Overloaded(
                f"queue full: {self.pending_rows} rows queued, admitting "
                f"{new_rows} would exceed max_queued_rows="
                f"{self.max_queued_rows}")
        t_deadline = (None if deadline_ms is None
                      else clock() + float(deadline_ms) / 1e3)
        if t_deadline is not None:
            self._has_deadlines = True
        out: List[QueryRequest] = []
        for tenant, entry, ids in checked:
            # LRU touch: a tenant with freshly queued work must not be
            # the next budget-eviction victim (evicting fails its
            # requests), so submission counts as recency
            entry.last_used = registry.tick()
            req = QueryRequest(rid=next(rid), tenant=tenant, ids=ids,
                               t_submit=clock(), t_deadline=t_deadline)
            if ids.shape[0] == 0:
                req.answers = np.zeros(0, bool)
                req.model_yes = np.zeros(0, bool)
                req.backup_yes = np.zeros(0, bool)
                req.t_done = req.t_submit
            else:
                queues[tenant].append((req, 0))
                if tenant not in order_set:
                    order.append(tenant)
                    order_set.add(tenant)
            out.append(req)
        return out

    @property
    def pending_rows(self) -> int:
        """Rows admitted but not yet taken into a dispatch."""
        return sum(req.ids.shape[0] - off
                   for q in self._queues.values() for req, off in q)

    @property
    def inflight_batches(self) -> int:
        return len(self._inflight)

    @property
    def stragglers(self) -> List[dict]:
        """Device-block waits flagged by the watchdog's trailing-median
        straggler detector (see ``runtime.fault.StepTimer``)."""
        return self.watchdog.stragglers

    def pending_rows_for(self, tenant: str) -> int:
        """Rows queued (not yet dispatched) for ONE tenant — the drain
        condition the tenant-retirement path watches."""
        return sum(req.ids.shape[0] - off
                   for req, off in self._queues.get(tenant, ()))

    def has_inflight(self, tenant: str) -> bool:
        """True while any dispatched-but-unretired batch carries the
        tenant's rows (they retire against the arrays bound at
        dispatch, so draining must outlast them)."""
        return any(e.tenant == tenant
                   for inf in self._inflight
                   for e in inf.prep.span_entries)

    def cancel_tenant(self, tenant: str, reason: str) -> None:
        """Fail a tenant's QUEUED requests now (their futures resolve
        with ``reason``); spans already in flight still retire with
        answers. The force-retire path — graceful retirement drains
        instead."""
        self._fail_tenant(tenant, reason)

    # ---------------------------------------------------------- dispatch
    def step(self) -> bool:
        """Prepare + dispatch one batch, retiring per the in-flight cap.

        Returns False only when nothing is queued AND nothing is in
        flight (expiring a deadline counts as progress — the step
        resolved a future). With async dispatch the final in-flight
        batches drain one per step once the queues empty.
        """
        expired = 0
        if self._has_deadlines:
            expired = self._expire_deadlines()
        with self.tracer.span("prepare") as sp:
            prep = self._prepare()
            if sp and prep is not None:
                sp.args.update(seq=prep.seq, tenant=prep.tenant,
                               bucket=prep.bucket, rows=prep.n_total)
        if prep is None:
            if self._inflight:
                self._retire(self._inflight.popleft())
                return True
            return expired > 0
        try:
            self._dispatch(prep)
        except InjectedFault:
            # a chaos-injected transient dispatch fault: the spans go
            # back to the queue heads and the step counts as progress —
            # the next attempt re-rolls the injector, so a storm slows
            # the pump down instead of crashing it (rows never lost)
            self._requeue(prep)
            self.dispatch_faults += 1
            return True
        except Exception:
            # dispatch never launched: put the taken spans back at the
            # head of the queue so the rows stay answerable (a retry
            # after the fault sees them exactly where they were)
            self._requeue(prep)
            raise
        while len(self._inflight) >= self.max_inflight:
            self._retire(self._inflight.popleft())
        return True

    def _expire_deadlines(self) -> int:
        """Retire still-QUEUED requests whose deadline passed; their
        futures raise :class:`DeadlineExceeded`. Requests with rows
        already dispatched are exempt — the device work is in flight
        and their answers land normally (a deadline bounds queue wait,
        not compute). Returns how many requests expired."""
        now = self._clock()
        live_deadlines = False
        n_expired = 0
        for tenant in list(self._queues):
            queue = self._queues[tenant]
            kept: Deque[Tuple[QueryRequest, int]] = collections.deque()
            for req, off in queue:
                if (req.t_deadline is not None
                        and req.t_first_dispatch is None
                        and now >= req.t_deadline):
                    req._complete(
                        now, error=(
                            f"deadline exceeded: request {req.rid} "
                            f"(tenant {tenant!r}) waited "
                            f"{(now - req.t_submit) * 1e3:.1f}ms queued"),
                        error_cls=DeadlineExceeded)
                    self.stats.record_deadline_expired()
                    n_expired += 1
                else:
                    if req.t_deadline is not None:
                        live_deadlines = True
                    kept.append((req, off))
            if kept:
                self._queues[tenant] = kept
            else:
                del self._queues[tenant]
        self._has_deadlines = live_deadlines
        return n_expired

    def _prepare(self) -> Optional[_Prepared]:
        """Host half: coalesce the next tenant's rows — and, for a
        grouped tenant with room to spare, rows from the next same-group
        tenants in ring order — into a padded batch. Pops the taken
        spans off the queues, so a later prepare (while this batch is
        still in flight) continues after them.

        Grouped batches are TILE-ALIGNED: each tenant's region starts on
        a ``tile_rows`` boundary (gap rows are wildcard padding on the
        region owner's slot), so every tile is single-tenant and the
        grouped program can gather MLP weights per tile instead of per
        row. Regions are laid out in SLOT ORDER (not boarding order),
        so a recurring tenant mix produces a canonical tile signature —
        the arena memoizes its per-tile weight gather on it, and the
        round-robin rotation would otherwise permute the layout every
        dispatch and defeat that cache. Alignment gaps count as padding
        in occupancy stats.
        """
        tenant = self._next_tenant()
        if tenant is None:
            return None
        registry = self.registry
        queues = self._queues
        entry = registry.get(tenant)
        cap = self.buckets[-1]
        group = entry.group
        tile = group.tile_rows if group is not None else 1
        # whole-tile capacity so per-region tile-alignment can never
        # overflow the bucket (cap < tile: a single region, no siblings)
        cap_tiles = (cap // tile) * tile
        cap_eff = cap_tiles if cap_tiles >= tile else cap

        take: List[Tuple[QueryRequest, int, int]] = []
        span_entries: List[FilterEntry] = []
        # (entry, first span idx, span count, valid rows) per tenant
        regions: List[Tuple[FilterEntry, int, int, int]] = []
        aligned = 0     # committed tile-aligned rows
        n_total = 0     # valid rows

        # span-taking, inlined: this runs once per candidate tenant on
        # the hottest host path (a 64-tenant megabatch walks 64 regions
        # per dispatch), so no helper-call or closure overhead
        order_list = list(self._order) if group is not None else ()
        order_i = 0
        name, e = tenant, entry
        while True:
            queue = queues.get(name)
            if queue:
                budget = cap_eff - aligned
                first = len(take)
                taken = 0
                while queue:
                    req, off = queue[0]
                    n = req.ids.shape[0] - off
                    left = budget - taken
                    if n >= left:         # budget hit (maybe mid-request)
                        if n > left:
                            queue[0] = (req, off + left)
                        else:
                            queue.popleft()
                        take.append((req, off, left))
                        span_entries.append(e)
                        taken += left
                        break
                    take.append((req, off, n))
                    span_entries.append(e)
                    taken += n
                    queue.popleft()
                if not queue:
                    queues.pop(name, None)
                if taken:
                    regions.append((e, first, len(take) - first, taken))
                    n_total += taken
                    t = taken + tile - 1
                    aligned += t - t % tile
            # megabatch: top the bucket up with group siblings' rows
            # (ring order, so the tenants next in line board first)
            if group is None or aligned >= cap_eff:
                break
            name = None
            while order_i < len(order_list):
                cand = order_list[order_i]
                order_i += 1
                if cand == tenant or not queues.get(cand):
                    continue
                ce = registry.peek(cand)
                if ce is None or ce.group is not group:
                    continue
                ce.last_used = registry.tick()      # LRU touch
                name, e = cand, ce
                break
            if name is None:
                break

        # lay regions out in slot order (canonical tile signature)
        if group is not None and len(regions) > 1:
            regions.sort(key=lambda r: group.slot_of(r[0].tenant))
        span_pos: List[int] = [0] * len(take)
        bounds: List[Tuple[FilterEntry, int, int]] = []
        chunks: List[np.ndarray] = []       # span payloads in layout order
        pos = 0
        for e, first, n_spans, rows in regions:
            p = pos
            for si in range(first, first + n_spans):
                span_pos[si] = p
                req, off, n = take[si]
                chunks.append(req.ids[off:off + n])
                p += n
            end = min(cap, -(-(pos + rows) // tile) * tile)
            bounds.append((e, pos, end))
            pos = end

        bucket = bucket_for(pos, self.buckets)
        batch = np.zeros((bucket, entry.n_cols), np.int32)  # pad = wildcard
        slots = None
        valid_idx = None
        if pos == n_total:      # gapless: one vectorized fill
            batch[:n_total] = chunks[0] if len(chunks) == 1 \
                else np.concatenate(chunks)
        else:                   # alignment gaps: per-span fill + map
            for p, (req, off, n) in zip(span_pos, take):
                batch[p:p + n] = req.ids[off:off + n]
            valid_idx = np.concatenate(
                [np.arange(p, p + n)
                 for p, (_, _, n) in zip(span_pos, take)])
        if group is not None:
            # bucket-padding rows extend the LAST (highest-slot) region:
            # any live slot is safe (their answers are sliced off), and
            # keeping the fill canonical preserves the tile signature;
            # gap rows inside a region carry the region owner's slot,
            # keeping tiles uniform
            vals = np.fromiter((group.slot_of(e.tenant)
                                for e, _, _ in bounds),
                               np.int32, len(bounds))
            lens = np.empty(len(bounds), np.int64)
            for j, (_, start, end) in enumerate(bounds):
                lens[j] = end - start
            lens[-1] += bucket - pos        # tail padding
            slots = np.repeat(vals, lens)
        return _Prepared(tenant=tenant, entry=entry, take=take,
                         span_entries=span_entries, span_pos=span_pos,
                         batch=batch, bucket=bucket, n_total=n_total,
                         slots=slots, group=group, valid_idx=valid_idx,
                         seq=next(self._seq))

    def _dispatch(self, prep: _Prepared) -> None:
        """Device half: launch the fused program (async — returns
        un-materialized device arrays) and park it in flight. Records
        each request's queue time (submit -> FIRST dispatch) the first
        time any of its rows goes out."""
        self.injector.check("dispatch", prep.tenant)
        with self.tracer.span("dispatch", seq=prep.seq,
                              bucket=prep.bucket) as sp:
            # only a live span reads the (process-wide) compile count
            compiles_before = executors.compile_count() if sp else 0
            if prep.group is not None:
                outputs = prep.group.run(prep.batch, prep.slots)
            else:
                outputs = prep.entry.run(prep.batch)
            if sp and executors.compile_count() > compiles_before:
                sp.args["compiled"] = True
        t = self._clock()
        record_queue_time = self.stats.record_queue_time
        for req, _, _ in prep.take:
            if req.t_first_dispatch is None:
                req.t_first_dispatch = t
                record_queue_time(t - req.t_submit)
        for e, (_, _, n) in zip(prep.span_entries, prep.take):
            e.n_queries += n
        self._inflight.append(_InFlight(prep=prep, outputs=outputs,
                                        t_dispatch=t))

    def _requeue(self, prep: _Prepared) -> None:
        """Restore a prepared-but-never-dispatched batch's spans to the
        front of their tenants' queues, in their original order."""
        for e, (req, off, n) in zip(reversed(prep.span_entries),
                                    reversed(prep.take)):
            queue = self._queues.setdefault(e.tenant, collections.deque())
            if queue and queue[0][0] is req:    # cap-split head entry
                queue[0] = (req, off)
            else:
                queue.appendleft((req, off))
            if e.tenant not in self._order_set:
                self._order.append(e.tenant)
                self._order_set.add(e.tenant)

    def _retire(self, inf: _InFlight) -> None:
        """Block on a dispatched batch, scatter answers back, complete
        fully-answered requests, record stats."""
        prep = inf.prep
        tracer = self.tracer
        try:
            with tracer.span("device_block", seq=prep.seq), \
                    self.watchdog:
                full_ans = np.asarray(inf.outputs[0])
                full_model = np.asarray(inf.outputs[1])
                full_backup = np.asarray(inf.outputs[2])
        except Exception as e:
            # the async computation itself failed: the rows are gone
            # from the queue, so fail their requests rather than hang
            # their owners on req.done forever
            t = self._clock()
            for req, _, _ in prep.take:
                req._complete(t, error=f"dispatch failed: {e!r}")
            raise
        # absolute watchdog bound on top of StepTimer's relative
        # straggler detection: a wait past dispatch_timeout_s is a
        # stuck batch regardless of the trailing median
        if (self.dispatch_timeout_s is not None and self.watchdog.times
                and self.watchdog.times[-1] > self.dispatch_timeout_s):
            self.stuck_batches += 1
        t_block_end = self._clock()
        latency = t_block_end - inf.t_dispatch
        # the device's compute window as the host observed it: dispatch
        # to materialization. On the exported trace this span lives on
        # the synthetic "device" track, so overlap with the NEXT
        # batch's host-side prepare span is directly visible
        tracer.add("device_compute", inf.t_dispatch, t_block_end,
                   track="device", cat="device",
                   args={"seq": prep.seq, "bucket": prep.bucket})
        with tracer.span("scatter_retire", seq=prep.seq):
            if prep.valid_idx is not None:  # tile-alignment gaps present
                ans = full_ans[prep.valid_idx]
                model = full_model[prep.valid_idx]
                backup = full_backup[prep.valid_idx]
            else:
                ans = full_ans[:prep.n_total]
                model = full_model[:prep.n_total]
                backup = full_backup[:prep.n_total]

            clock = self._clock
            record_request = self.stats.record_request
            t_done = clock()    # one retirement instant for the batch
            for p, (req, off, n) in zip(prep.span_pos, prep.take):
                if off == 0 and n == req.ids.shape[0]:
                    # whole request answered by this span (the common
                    # case in the many-small-request regime): hand out
                    # zero-copy views instead of allocating + copying
                    # three arrays
                    req.answers = full_ans[p:p + n]
                    req.model_yes = full_model[p:p + n]
                    req.backup_yes = full_backup[p:p + n]
                else:
                    if req.answers is None:
                        m = req.ids.shape[0]
                        req.answers = np.zeros(m, bool)
                        req.model_yes = np.zeros(m, bool)
                        req.backup_yes = np.zeros(m, bool)
                    req.answers[off:off + n] = full_ans[p:p + n]
                    req.model_yes[off:off + n] = full_model[p:p + n]
                    req.backup_yes[off:off + n] = full_backup[p:p + n]
                if off + n >= req.ids.shape[0]:  # last span: req done
                    req._complete(t_done)     # resolves the future too
                    record_request(t_done - req.t_submit)
            with tracer.span("stats", cat="detail"):
                per_tenant: Dict[str, int] = {}
                # per-tenant stage-positive sums (spans are contiguous
                # row ranges of the FULL batch, so each slices the full
                # arrays)
                stages: Dict[str, List[int]] = {}
                for e, p, (_, _, n) in zip(prep.span_entries,
                                           prep.span_pos, prep.take):
                    per_tenant[e.tenant] = per_tenant.get(e.tenant, 0) + n
                    acc = stages.get(e.tenant)
                    if acc is None:
                        acc = stages[e.tenant] = [0, 0, 0, 0]
                    acc[0] += n
                    acc[1] += int(full_model[p:p + n].sum())
                    acc[2] += int(full_backup[p:p + n].sum())
                    acc[3] += int(full_ans[p:p + n].sum())
                self.stats.record_batch(
                    prep.tenant, prep.n_total, prep.bucket, latency, ans,
                    model, backup, inflight=len(self._inflight),
                    per_tenant=per_tenant,
                    per_tenant_stages={k: tuple(v)
                                       for k, v in stages.items()})

    def _next_tenant(self) -> Optional[str]:
        while self._order:
            tenant = self._order[0]
            if not self._queues.get(tenant):
                self._order.popleft()
                self._order_set.discard(tenant)
                continue
            if tenant not in self.registry:
                self._fail_tenant(tenant, f"tenant {tenant!r} evicted "
                                  "with requests queued")
                self._order.popleft()
                self._order_set.discard(tenant)
                continue
            # rotate so tenants with sustained load share dispatches
            self._order.rotate(-1)
            return tenant
        return None

    def _fail_tenant(self, tenant: str, reason: str) -> None:
        """Retire a tenant's queued requests with an error (their owner
        sees ``req.done`` with ``req.error`` set instead of answers).
        Spans already in flight still retire with answers — they ran
        against the entry as placed at dispatch time."""
        t = self._clock()
        for req, _ in self._queues.pop(tenant, ()):
            req._complete(t, error=reason)

    def run_until_drained(self, max_steps: int = 100_000) -> int:
        """Steps until queues AND the in-flight buffer are empty (the
        final async batches drain one per step). Returns step count.

        Never returns with batches still in flight: even when
        ``max_steps`` cuts the loop short, the already-dispatched
        batches are retired (pure progress — retiring launches nothing
        new and is bounded by ``max_inflight``), so their requests
        complete and their latency lands in ``ServeStats`` instead of
        dangling un-materialized on the device.
        """
        steps = 0
        while steps < max_steps and self.step():
            steps += 1
        while self._inflight:
            self._retire(self._inflight.popleft())
        return steps
