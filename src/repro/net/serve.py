"""Serving engine: continuous bucketed batching over the fused-pyramid runner.

``run_network`` is a batch call: fast once planned and compiled, but both
costs key on the exact batch size — every distinct request shape pays a
fresh ``auto_partition`` DP and a fresh jit trace.  Sustained traffic is the
opposite shape: many small requests, few distinct sizes.  This module turns
the runner into a service (ROADMAP's continuous-batching item):

* **Admission** — requests (single images or micro-batches) enter the
  queue through :func:`repro.robust.validate.check_request`: shape and
  finiteness are the per-request half of the preflight contract, so a
  poisoned request surfaces as a typed error *at submit* and never stalls
  or contaminates the queue (the plan/params half is validated once per
  cache entry).  The submit path is **thread-safe** (one engine lock), so
  N producer threads can feed one drain loop — the contract the
  :mod:`repro.net.frontend` async layer builds on.
* **Deadlines and priorities** — ``submit(x, deadline_us=, priority=)``
  with ``ServeConfig(deadline_aware=True)`` turns the FIFO queue into an
  earliest-deadline-first scheduler: higher priority first, then nearest
  deadline.  A request whose modeled ETA (queue delay from
  :func:`repro.core.cycle_model.queue_delay_cycles` plus its bucket's SLO,
  scaled by the measured-vs-modeled calibration ratio) already blows its
  deadline is **shed at admission** with a typed
  :class:`~repro.robust.errors.DeadlineExceeded` — load shedding instead
  of wasting a launch on a result nobody can use.  Requests that expire
  while queued complete immediately with the same typed error and never
  occupy a launch.
* **Bucketing** — admitted rows are packed (FIFO, or EDF order when
  deadline-aware) into power-of-two batch **buckets** (:func:`bucket_for`)
  and padded to the bucket size (:func:`pad_to_bucket`).  Batch elements
  are independent through every conv/pool/dense/global-pool op, so the
  real rows of a padded batch are **bit-identical** to running them
  unpadded under the same plan (``tests/test_serve.py`` enforces this at
  f32 and bf16) — padding buys shape reuse for free.
* **Plan + jit cache** — each bucket executes through one cache entry keyed
  ``(graph identity, vmem budget, bucket, dtype)``: the bucket-batch
  ``auto_partition`` plan (the DP costs launches at the *bucket's* batch,
  so cut points shift with bucket — see DESIGN.md §14), its prepared
  params, and the modeled latency estimate.  Plans come through the
  memoized ``auto_partition`` (its lru is the seed cache), and because all
  requests in a bucket share one padded shape, the jit executable is reused
  too — wave 2 of a bucket performs zero replans and zero retraces
  (``repro.net.runner.jit_trace_count`` is the regression hook).  The
  engine's own :class:`collections.OrderedDict` LRU bounds live entries and
  counts hits/misses/evictions next to ``partition_cache_info()``.
* **Two forwards in flight** — the drain loop dispatches bucket *n+1*
  before it blocks on bucket *n* (jax dispatch is asynchronous, so *n+1*
  queues behind *n* on the device), then records *n* and stages *n+2*
  (``jax.device_put`` of its padded host batch) while *n+1* computes: the
  host's record, staging and dispatch all overlap device compute.  Where
  no next batch is queued, or where *n*'s outcome could change *n+1*'s
  route (a breaker, the guarded ladder, an armed fault injector), it
  keeps depth 1: only the input staging overlaps.  The cost model twin
  is :func:`repro.core.cycle_model.serve_stream_cycles` (which still
  models depth 1).
* **Failure containment** (DESIGN.md §15) — a launch that dies with a
  typed :class:`~repro.robust.errors.RobustError` (including injected
  staging failures) fails *its batch* typed and the queue keeps draining.
  A **watchdog** (``watchdog_factor=N``) flags launches exceeding N× their
  expected wall (the max of the modeled SLO and the bucket's measured
  batch p50, so interpret-mode wall clocks calibrate it).  A per-key
  **circuit breaker** (``breaker_threshold=K``,
  :mod:`repro.robust.breaker`) opens after K consecutive failing launches
  — fallback-laden guarded runs, watchdog trips, sentinel trips, or typed
  errors — and pins the key to its last-good degraded rung (interpret or
  reference) for a cooldown window; a half-open probe re-tries the fused
  path.  An **output sentinel** (``output_sentinel=True``) catches
  non-finite logits post-launch and re-serves the batch from the reference
  walk — degraded-but-correct, never silent garbage.  All of it is off by
  default: a default-config engine behaves exactly like the PR 9 engine.
* **SLO + measurement** — each bucket publishes ``slo_us`` (modeled
  cold latency: host staging + the plan's ``modeled_us()``), ``steady_us``
  (the double-buffered steady state, ``max(compute, staging)``), and
  measured p50/p95 request latency + imgs/s.  The process's tracer
  (``repro.obs.get_tracer()``, an always-on bounded recorder by default)
  gets host spans where the work happens: ``engine.admit`` per request
  (linked to its batch at staging), and ``engine.stage`` /
  ``engine.dispatch`` (with its route) / ``engine.block`` /
  ``engine.record`` per batch; the drain loop bumps ``serve.batches`` per
  fused dispatch and ``serve.dispatched_ahead`` per dispatch made while
  the previous batch was in flight; the cache bumps
  ``serve_cache_{hit,miss,eviction}`` counters (a miss also records an
  event), and every shed/expiry/watchdog/breaker/sentinel action records
  its own event.  Installing a tracer never changes the forward path:
  batches always run the jit-compiled ``run_network``.
* **Degradation, not drops** — ``ServeConfig(guarded=True)`` runs each
  bucket under the PR 8 ladder (``repro.robust.guarding``): a VMEM miss
  replans, a numeric fault quarantines the launch to the reference path,
  and the requests still complete.

``python -m repro.net.serve --model lenet --requests 32 --dry-stream``
drives a deterministic two-wave synthetic stream and prints the
bucket/SLO/throughput table (the CI smoke contract); ``--inject
slow_launch --breaker 1 --watchdog 3`` arms a wave-2 fault and shows the
breaker cycle in the summary (the CI chaos contract).
"""

from __future__ import annotations

import argparse
import json
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cycle_model import (
    DEFAULT_PARAMS,
    host_staging_cycles,
    queue_delay_cycles,
    serve_stream_cycles,
)
from repro.core.dtypes import DTYPE_BYTES, canonical_dtype
from repro.core.program import VMEM_BUDGET_BYTES
from repro.obs.stats import percentile
from repro.obs.trace import get_tracer, span_code
from repro.robust.breaker import CircuitBreaker
from repro.robust.errors import (
    DeadlineExceeded,
    PreflightError,
    RobustError,
)
from repro.robust.faults import get_injector
from repro.robust.guard import GuardConfig, guarding
from repro.robust.validate import check_request

from .graph import Graph
from .partition import PartitionPlan, auto_partition
from .runner import (
    Params,
    prepare_network_params,
    reference_network,
    run_network,
)


_ADMIT = span_code("engine.admit")
_STAGE = span_code("engine.stage")
_DISPATCH = span_code("engine.dispatch")
_BLOCK = span_code("engine.block")
_RECORD = span_code("engine.record")
_ROUTES = {r: span_code(r) for r in ("fused", "interpret", "reference")}


def bucket_for(rows: int, buckets: tuple[int, ...]) -> int:
    """Smallest configured bucket that fits ``rows`` real rows."""
    for b in sorted(buckets):
        if rows <= b:
            return b
    raise PreflightError(
        f"request spans {rows} rows but the largest bucket is"
        f" {max(buckets)}; split micro-batches before submit",
        rows=rows, buckets=sorted(buckets),
    )


def pad_to_bucket(x, bucket: int) -> np.ndarray:
    """Zero-pad a ``(rows, H, W, C)`` batch up to ``bucket`` rows.

    Zero rows ride along through the padded launch and are sliced off
    before results are returned; the real rows' logits are bit-identical to
    the unpadded run under the same plan (batch elements never interact)."""
    x = np.asarray(x)
    rows = x.shape[0]
    if rows == bucket:
        return x
    if rows > bucket:
        raise PreflightError(
            f"cannot pad {rows} rows down to bucket {bucket}",
            rows=rows, bucket=bucket,
        )
    pad = np.zeros((bucket - rows,) + x.shape[1:], dtype=x.dtype)
    return np.concatenate([x, pad], axis=0)


@dataclass(frozen=True)
class ServeConfig:
    """Static knobs of one serving engine.

    ``buckets`` are the admissible padded batch sizes (ascending powers of
    two by convention; any ascending ints work).  ``plan_cache_size`` bounds
    the engine's plan+params LRU — evictions are counted, and because plans
    come through the memoized ``auto_partition``, a re-admitted key usually
    rebuilds from the lru without re-running the DP.  ``compute_dtype``
    ``None`` means the graph's own default.  ``guarded`` runs every bucket
    under the degradation ladder; ``require_finite`` controls the admission
    NaN/Inf scan (shape checks always run).  ``max_queue`` bounds queued
    requests — an overfull queue rejects at submit (backpressure) instead
    of growing without bound.

    The resilience knobs all default **off** (a default engine is the PR 9
    engine):

    * ``deadline_aware`` — EDF batch formation, queue-expiry sweeps, and
      admission-time load shedding against modeled ETA.  ``shed_margin``
      scales the modeled ETA before it is compared to the deadline (>1 is
      more aggressive shedding).
    * ``breaker_threshold`` / ``breaker_cooldown_s`` — per-(graph, bucket,
      dtype) circuit breaker: K consecutive failing launches pin the key
      to its last-good degraded rung for the cooldown window.
    * ``watchdog_factor`` — flag launches whose wall clock exceeds N× the
      expected batch wall (max of modeled SLO and the bucket's measured
      p50 — the measured term calibrates interpret-mode wall clocks that
      dwarf the 100 MHz model).
    * ``output_sentinel`` — host-side finite check on every launch's
      logits; a trip re-serves the batch from the reference walk."""

    buckets: tuple[int, ...] = (1, 2, 4, 8)
    plan_cache_size: int = 16
    compute_dtype: str | None = None
    vmem_budget: int = VMEM_BUDGET_BYTES
    prefer_region: str = "largest"
    interpret: bool | None = None
    end_skip: bool = True
    guarded: bool = False
    require_finite: bool = True
    max_queue: int = 1024
    deadline_aware: bool = False
    shed_margin: float = 1.0
    breaker_threshold: int | None = None
    breaker_cooldown_s: float = 5.0
    watchdog_factor: float | None = None
    output_sentinel: bool = False

    def __post_init__(self):
        if not self.buckets or list(self.buckets) != sorted(set(self.buckets)):
            raise PreflightError(
                f"buckets must be ascending and unique, got {self.buckets}",
                buckets=list(self.buckets),
            )
        if self.shed_margin <= 0:
            raise PreflightError(
                f"shed_margin must be positive, got {self.shed_margin}",
                shed_margin=self.shed_margin,
            )
        if self.breaker_threshold is not None and self.breaker_threshold < 1:
            raise PreflightError(
                f"breaker_threshold must be >= 1, got"
                f" {self.breaker_threshold}",
                breaker_threshold=self.breaker_threshold,
            )
        if self.watchdog_factor is not None and self.watchdog_factor <= 1:
            raise PreflightError(
                f"watchdog_factor must exceed 1, got {self.watchdog_factor}",
                watchdog_factor=self.watchdog_factor,
            )


@dataclass(frozen=True)
class Request:
    """One admitted unit of work: ``rows`` real images awaiting a bucket.

    ``deadline_s`` is the absolute ``time.perf_counter`` deadline computed
    at admission from the caller's relative ``deadline_us`` (``None`` means
    no deadline); ``priority`` orders EDF batches — higher runs first."""

    id: int
    x: np.ndarray  # (rows, H, W, C), host-side
    rows: int
    enqueue_s: float
    deadline_us: float | None = None
    deadline_s: float | None = None
    priority: int = 0
    span: int = -1  # ring slot of its engine.admit span


@dataclass(frozen=True)
class RequestResult:
    """Terminal state of one submitted request.

    Exactly one of ``logits``/``error`` is set: rejected, shed, expired,
    and failed requests carry the typed
    :class:`~repro.robust.errors.RobustError` (``bucket``/``latency_ms``
    stay ``None`` unless the request reached a launch); completed requests
    carry their real rows' logits and the enqueue→complete wall clock."""

    id: int
    rows: int
    bucket: int | None = None
    logits: np.ndarray | None = None
    error: RobustError | None = None
    latency_ms: float | None = None

    @property
    def ok(self) -> bool:
        return self.error is None


@dataclass(frozen=True)
class _PlanEntry:
    """One plan+jit cache entry: everything a bucket needs to execute."""

    bucket: int
    plan: PartitionPlan
    prepared: Params
    compute_cycles: int
    staging_cycles: int

    @property
    def slo_us(self) -> float:
        """Modeled cold latency of one bucket execution: the host→device
        input copy plus the plan's launches, nothing overlapped — the
        latency bound the engine publishes per bucket."""
        return serve_stream_cycles(
            1, self.compute_cycles, self.staging_cycles, double_buffered=False
        ) / DEFAULT_PARAMS.freq_mhz

    @property
    def steady_us(self) -> float:
        """Modeled steady-state per-bucket latency under double buffering:
        ``max(compute, staging)`` — the throughput bound."""
        two = serve_stream_cycles(
            2, self.compute_cycles, self.staging_cycles, double_buffered=True
        )
        return (two - (self.compute_cycles + self.staging_cycles)) / (
            DEFAULT_PARAMS.freq_mhz
        )


@dataclass
class _BucketStats:
    requests: int = 0
    images: int = 0
    batches: int = 0
    wall_ms: float = 0.0
    latencies_ms: list = field(default_factory=list)
    # clean per-batch walls only (watchdog-tripped walls are excluded so an
    # injected stall cannot poison its own detection threshold)
    batch_walls_ms: list = field(default_factory=list)


@dataclass
class _Launch:
    """One dispatched batch, from its dispatch to its record."""

    batch: list[Request]
    bucket: int
    entry: _PlanEntry
    x_dev: jax.Array
    bid: int
    breaker: CircuitBreaker | None
    route: str
    t0: float  # dispatch start
    logits: jax.Array | None = None
    report: object = None  # the guarded RunReport, fused+guarded only
    err: RobustError | None = None


def _percentile(values: list, q: float) -> float:
    # the shared obs.stats helper, kept under the historical name
    return percentile(values, q)


# absolute floor of the watchdog's expected batch wall: steady-state
# interpret-mode walls are sub-millisecond once jax's jit cache is warm, and
# N x a sub-millisecond p50 is scheduler noise, not a stuck launch — the
# watchdog exists for launches stuck for 100s of ms, not 2 ms of jitter
WATCHDOG_FLOOR_MS = 10.0


class ServingEngine:
    """Continuous bucketed batching over one graph's fused-pyramid runner.

    ``submit`` admits (or rejects) requests under the engine lock — safe
    from any thread; ``drain`` forms buckets and executes them with up to
    two forwards in flight and the next input staged (one drain loop at a
    time — concurrent drains serialize); ``summary`` renders the
    bucket/SLO table.  The engine owns no device state beyond the batches
    in flight and the staged one — all heavy reuse
    lives in the plan+jit cache, so two engines over the same graph share
    compiled executables through jax's own cache.  Completion listeners
    (:meth:`add_listener`) observe every terminal :class:`RequestResult` —
    the hook :mod:`repro.net.frontend` turns into Future-style handles.
    """

    def __init__(
        self, graph: Graph, params: Params, config: ServeConfig | None = None
    ) -> None:
        self.graph = graph
        self.config = config or ServeConfig()
        self.master_params = params
        self.compute_dtype = canonical_dtype(
            self.config.compute_dtype or graph.compute_dtype
        )
        self.queue: deque[Request] = deque()
        self.results: dict[int, RequestResult] = {}
        self._cache: OrderedDict[tuple, _PlanEntry] = OrderedDict()
        self.cache_counters = {"hits": 0, "misses": 0, "evictions": 0}
        self._stats: dict[int, _BucketStats] = {}
        self._next_id = 0
        self._next_batch = 0
        self.rejected = 0
        self.resilience = {
            "shed": 0, "expired": 0, "failed": 0,
            "watchdog_trips": 0, "sentinel_trips": 0, "stalls": 0,
        }
        self._breakers: dict[tuple, CircuitBreaker] = {}
        self._breaker_emitted: dict[tuple, int] = {}
        # fused-route dispatches, and those made while the previous batch
        # was still in flight (``serve.batches`` / ``serve.dispatched_ahead``
        # in the tracer's counters)
        self.dispatches = {"batches": 0, "dispatched_ahead": 0}
        self._listeners: list = []
        self._lock = threading.RLock()
        self._drain_lock = threading.Lock()

    # -- listeners ----------------------------------------------------------

    def add_listener(self, fn) -> None:
        """Register ``fn(result)`` to be called with every terminal
        :class:`RequestResult` — completions, rejections, sheds, expiries,
        and batch failures alike.  Called under the engine lock, so
        listeners must be cheap and must not re-enter ``drain``."""
        with self._lock:
            self._listeners.append(fn)

    def _notify(self, result: RequestResult) -> None:
        for fn in self._listeners:
            fn(result)

    # -- admission ----------------------------------------------------------

    def submit(self, x, *, deadline_us: float | None = None,
               priority: int = 0) -> int:
        """Admit one request (a ``(H, W, C)`` image or ``(rows, H, W, C)``
        micro-batch); returns its request id.  Thread-safe.

        A request that fails admission — wrong shape, non-finite pixels,
        more rows than the largest bucket, a full queue, or (when
        ``deadline_aware``) a deadline the modeled queue ETA already blows
        — is *rejected*, not raised: its :class:`RequestResult` carries the
        typed error and the queue keeps moving.  Callers poll
        :attr:`results` (or register a listener / use the frontend)."""
        t0 = time.perf_counter()
        tracer = get_tracer()
        with self._lock:
            rid = self._next_id
            self._next_id += 1
            x = np.asarray(x)
            if x.ndim == 3:
                x = x[None]
            rows = int(x.shape[0]) if x.ndim == 4 else 0
            now = time.perf_counter()
            try:
                if len(self.queue) >= self.config.max_queue:
                    raise PreflightError(
                        f"queue is full ({self.config.max_queue} requests);"
                        " drain before submitting more",
                        max_queue=self.config.max_queue, field="queue",
                    )
                bucket_for(max(rows, 1), self.config.buckets)
                check_request(
                    x, self.graph, require_finite=self.config.require_finite
                )
                if self.config.deadline_aware and deadline_us is not None:
                    eta_us = self._eta_us(rows)
                    if eta_us * self.config.shed_margin > deadline_us:
                        raise DeadlineExceeded(
                            f"request shed at admission: modeled ETA"
                            f" {eta_us:.0f}us blows the {deadline_us:.0f}us"
                            " deadline",
                            request=rid, eta_us=round(eta_us, 1),
                            deadline_us=deadline_us,
                        )
            except RobustError as err:
                self.rejected += 1
                shed = isinstance(err, DeadlineExceeded)
                if shed:
                    self.resilience["shed"] += 1
                result = RequestResult(id=rid, rows=rows, error=err)
                self.results[rid] = result
                if tracer.enabled:
                    tracer.bump("serve_shed" if shed else "serve_reject")
                    tracer.record_event(
                        "serve_shed" if shed else "serve_reject",
                        request=rid, rows=rows,
                        error=type(err).__name__, message=str(err),
                    )
                self._notify(result)
                tracer.span(_ADMIT, t0, time.perf_counter(), rid, rows=rows)
                return rid
            # recorded before the request is queued, so staging can link
            # its slot to the batch without a race
            slot = tracer.span(
                _ADMIT, t0, time.perf_counter(), rid, rows=rows
            )
            self.queue.append(Request(
                id=rid, x=x, rows=rows, enqueue_s=now,
                deadline_us=deadline_us,
                deadline_s=(
                    now + deadline_us * 1e-6
                    if deadline_us is not None else None
                ),
                priority=priority,
                span=slot,
            ))
            return rid

    def submit_many(self, xs) -> list[int]:
        return [self.submit(x) for x in xs]

    # -- deadline math ------------------------------------------------------

    def _calibration(self) -> float:
        """Worst observed measured-vs-modeled wall ratio across buckets
        with traffic (1.0 before any batch lands).  The 100 MHz model
        prices launches in microseconds; interpret-mode kernels take
        milliseconds — this ratio maps modeled ETAs into the wall-clock
        domain the deadlines live in."""
        ratios = []
        for b, st in self._stats.items():
            entry = self._cache.get(self._key(b))
            if entry is not None and st.batch_walls_ms:
                ratios.append(
                    percentile(st.batch_walls_ms, 50) * 1e3
                    / max(entry.slo_us, 1e-9)
                )
        return max(ratios) if ratios else 1.0

    def _eta_us(self, rows: int) -> float:
        """Modeled completion ETA for a new ``rows``-row request: the queue
        delay of the work already admitted (costed at the largest bucket's
        steady period, :func:`queue_delay_cycles`) plus the request's own
        bucket SLO, scaled by :meth:`_calibration`."""
        bucket = bucket_for(max(rows, 1), self.config.buckets)
        entry = self._entry(bucket)
        limit = max(self.config.buckets)
        queued_rows = sum(r.rows for r in self.queue)
        wait_us = 0.0
        if queued_rows:
            big = self._entry(limit)
            pending_batches = -(-queued_rows // limit)
            wait_us = queue_delay_cycles(
                pending_batches, big.compute_cycles, big.staging_cycles
            ) / DEFAULT_PARAMS.freq_mhz
        return self._calibration() * (wait_us + entry.slo_us)

    # -- plan + jit cache ---------------------------------------------------

    def _key(self, bucket: int) -> tuple:
        # the memo key mirrors auto_partition's: identical graph structure,
        # budget, bucket batch, and dtype mean identical plans
        return (self.graph, self.config.vmem_budget, bucket,
                self.compute_dtype)

    def _launch_name(self, bucket: int) -> str:
        return f"serve:{self.graph.name}:bucket{bucket}"

    def _entry(self, bucket: int) -> _PlanEntry:
        key = self._key(bucket)
        tracer = get_tracer()
        with self._lock:
            hit = key in self._cache
            if hit:
                self._cache.move_to_end(key)
                self.cache_counters["hits"] += 1
            else:
                self.cache_counters["misses"] += 1
                plan = auto_partition(
                    self.graph,
                    vmem_budget=self.config.vmem_budget,
                    batch=bucket,
                    prefer_region=self.config.prefer_region,
                    compute_dtype=self.compute_dtype,
                )
                prepared = prepare_network_params(plan, self.master_params)
                in_bytes = DTYPE_BYTES[self.compute_dtype] * bucket * (
                    self.graph.input_size ** 2 * self.graph.in_channels
                )
                self._cache[key] = _PlanEntry(
                    bucket=bucket,
                    plan=plan,
                    prepared=prepared,
                    compute_cycles=plan.modeled_cycles(),
                    staging_cycles=host_staging_cycles(in_bytes),
                )
                while len(self._cache) > self.config.plan_cache_size:
                    self._cache.popitem(last=False)
                    self.cache_counters["evictions"] += 1
                    if tracer.enabled:
                        tracer.bump("serve_cache_eviction")
            entry = self._cache[key]
        if tracer.enabled:
            tracer.bump("serve_cache_hit" if hit else "serve_cache_miss")
            if not hit:
                tracer.record_event(
                    "serve_plan_cache",
                    model=self.graph.name, bucket=bucket, cache="miss",
                    compute_dtype=self.compute_dtype,
                    launches=entry.plan.n_launches(),
                    slo_us=entry.slo_us,
                )
        return entry

    # -- circuit breaker ----------------------------------------------------

    def _breaker(self, bucket: int) -> CircuitBreaker | None:
        if self.config.breaker_threshold is None:
            return None
        key = self._key(bucket)
        with self._lock:
            br = self._breakers.get(key)
            if br is None:
                br = CircuitBreaker(
                    threshold=self.config.breaker_threshold,
                    cooldown_s=self.config.breaker_cooldown_s,
                )
                self._breakers[key] = br
            return br

    def _flush_breaker(self, bucket: int, br: CircuitBreaker) -> None:
        """Emit any breaker transitions not yet traced as ``serve_breaker``
        events (the observable surface the chaos CI asserts on)."""
        key = self._key(bucket)
        with self._lock:
            seen = self._breaker_emitted.get(key, 0)
            fresh = br.transitions[seen:]
            self._breaker_emitted[key] = len(br.transitions)
        if not fresh:
            return
        tracer = get_tracer()
        if tracer.enabled:
            for t in fresh:
                tracer.bump("serve_breaker_transition")
                tracer.record_event(
                    "serve_breaker",
                    model=self.graph.name, bucket=bucket,
                    from_state=t["from"], to_state=t["to"], why=t["why"],
                    pinned_rung=br.pinned_rung,
                )

    @staticmethod
    def _pin_rung(report, sentinel_tripped: bool) -> str | None:
        """The rung to pin an opening breaker to, from what this launch
        learned: sentinel trips and replan/reference fallbacks need the
        reference walk; interpret/heal fallbacks pin the interpret path;
        ``None`` (no ladder info) keeps the previous pin."""
        if sentinel_tripped:
            return "reference"
        if report is not None and report.events:
            rungs = {e.rung for e in report.events}
            if rungs <= {"heal", "interpret"}:
                return "interpret"
            return "reference"
        return None

    # -- execution ----------------------------------------------------------

    def _form_batch(self) -> list[Request] | None:
        """Pop the next run of requests that fits the largest bucket.

        FIFO by default: strictly in admission order — no peeking past the
        head to fill a bucket with later small requests, so a large request
        is never starved by a stream of singles (the fairness property the
        tests assert).  When ``deadline_aware``, expired requests are first
        completed with :class:`DeadlineExceeded` (they never occupy a
        launch), then the same no-skip packing runs over EDF order
        (priority desc, deadline asc, id asc) — the nearest deadline is
        never starved by later submissions."""
        with self._lock:
            if not self.config.deadline_aware:
                if not self.queue:
                    return None
                batch, rows = [], 0
                limit = max(self.config.buckets)
                while self.queue and rows + self.queue[0].rows <= limit:
                    req = self.queue.popleft()
                    batch.append(req)
                    rows += req.rows
                return batch
            now = time.perf_counter()
            live = []
            for req in self.queue:
                if req.deadline_s is not None and now > req.deadline_s:
                    self._expire(req, now)
                else:
                    live.append(req)
            if not live:
                self.queue = deque()
                return None
            order = sorted(live, key=lambda r: (
                -r.priority,
                r.deadline_s if r.deadline_s is not None else float("inf"),
                r.id,
            ))
            batch, rows = [], 0
            limit = max(self.config.buckets)
            for req in order:
                if rows + req.rows > limit:
                    break
                batch.append(req)
                rows += req.rows
            taken = {r.id for r in batch}
            self.queue = deque(r for r in live if r.id not in taken)
            return batch

    def _expire(self, req: Request, now: float) -> None:
        late_us = (now - req.deadline_s) * 1e6
        err = DeadlineExceeded(
            f"request {req.id} expired in queue {late_us:.0f}us past its"
            " deadline",
            request=req.id, late_us=round(late_us, 1),
            deadline_us=req.deadline_us,
        )
        result = RequestResult(id=req.id, rows=req.rows, error=err)
        self.results[req.id] = result
        self.resilience["expired"] += 1
        tracer = get_tracer()
        if tracer.enabled:
            tracer.bump("serve_expired")
            tracer.record_event(
                "serve_expired", request=req.id, rows=req.rows,
                late_us=round(late_us, 1),
            )
        self._notify(result)

    def _stage(self, batch: list[Request]):
        """Pad the batch to its bucket and start the host→device copy —
        called for bucket ``n+1`` while bucket ``n`` computes, so the copy
        overlaps compute (the double-buffered input stage).  The injected
        ``stage`` fault fires here: a staging failure surfaces before any
        device work, and the caller fails the batch typed."""
        rows = sum(r.rows for r in batch)
        bucket = bucket_for(rows, self.config.buckets)
        entry = self._entry(bucket)
        inj = get_injector()
        if inj.enabled:
            inj.fire("stage", self._launch_name(bucket))
        host = np.concatenate([r.x for r in batch], axis=0)
        x_dev = jax.device_put(
            jnp.asarray(pad_to_bucket(host, bucket), dtype=jnp.float32)
        )
        return batch, bucket, entry, x_dev

    def _next_staged(self):
        """Form and stage the next batch, failing staging-faulted batches
        typed and moving on — a poisoned batch never wedges the loop.
        Returns ``(batch, bucket, entry, x_dev, batch_id)`` or ``None``; a
        staged batch records its ``engine.stage`` span and links each
        request's ``engine.admit`` span to the batch id."""
        while True:
            t0 = time.perf_counter()
            batch = self._form_batch()
            if batch is None:
                return None
            try:
                staged = self._stage(batch)
            except RobustError as err:
                rows = sum(r.rows for r in batch)
                bucket = bucket_for(rows, self.config.buckets)
                self._fail_batch(batch, bucket, err)
                continue
            bid = self._next_batch
            self._next_batch += 1
            tracer = get_tracer()
            tracer.span(_STAGE, t0, time.perf_counter(), bid,
                        bucket=staged[1], rows=sum(r.rows for r in batch))
            for r in batch:
                tracer.link(r.span, r.id, bid)
            return staged + (bid,)

    def _run_route(self, route: str, entry: _PlanEntry, x_dev):
        """Execute one staged bucket along ``route``; returns
        ``(logits, report)`` where ``report`` is the guarded
        :class:`~repro.robust.degrade.RunReport` (fused+guarded only).

        Routes: ``fused`` is the normal path (guarded when configured);
        ``interpret`` re-runs the same plan with interpret-mode kernels (a
        lowering/compile quarantine); ``reference`` is the node-by-node
        walk from the master params — no plan, no jit, degraded but
        correct."""
        if route == "reference":
            return reference_network(
                x_dev, self.graph, self.master_params
            ), None
        if route == "interpret":
            logits, _ = run_network(
                x_dev, entry.prepared, plan=entry.plan,
                end_skip=self.config.end_skip, interpret=True,
            )
            return logits, None
        if self.config.guarded:
            with guarding(
                GuardConfig(), source_params=self.master_params
            ) as guard:
                logits, _ = run_network(
                    x_dev, entry.prepared, plan=entry.plan,
                    end_skip=self.config.end_skip,
                    interpret=self.config.interpret,
                )
                return logits, guard.last_report
        logits, _ = run_network(
            x_dev, entry.prepared, plan=entry.plan,
            end_skip=self.config.end_skip,
            interpret=self.config.interpret,
        )
        return logits, None

    def _watchdog_threshold_ms(self, bucket: int, entry: _PlanEntry):
        """Expected batch wall for the watchdog: the max of the modeled
        SLO, the bucket's measured clean-batch p50, and
        :data:`WATCHDOG_FLOOR_MS`.  ``None`` until the bucket has one
        measured batch — the first launch calibrates (the modeled SLO
        alone is microseconds at the 100 MHz model and would flag every
        interpret-mode launch)."""
        with self._lock:
            st = self._stats.get(bucket)
            walls = list(st.batch_walls_ms) if st is not None else []
        if not walls:
            return None
        return max(
            entry.slo_us / 1e3, percentile(walls, 50), WATCHDOG_FLOOR_MS
        )

    def _fail_batch(
        self, batch: list[Request], bucket: int, err: RobustError,
        wall_ms: float | None = None,
    ) -> None:
        """Complete every request of a failed batch with the typed error —
        the batch is terminal, the queue keeps draining."""
        with self._lock:
            for req in batch:
                result = RequestResult(
                    id=req.id, rows=req.rows, bucket=bucket, error=err,
                )
                self.results[req.id] = result
                self._notify(result)
            self.resilience["failed"] += len(batch)
        tracer = get_tracer()
        if tracer.enabled:
            tracer.bump("serve_batch_error")
            tracer.record_event(
                "serve_batch_error",
                model=self.graph.name, bucket=bucket,
                requests=len(batch), error=type(err).__name__,
                message=str(err),
                wall_ms=wall_ms,
            )

    def _record(
        self, batch, bucket, logits, wall_ms, *, calibrate: bool = True,
    ) -> None:
        done_s = time.perf_counter()
        host_logits = np.asarray(logits)
        with self._lock:
            stats = self._stats.setdefault(bucket, _BucketStats())
            stats.batches += 1
            stats.wall_ms += wall_ms
            if calibrate:
                stats.batch_walls_ms.append(wall_ms)
            row = 0
            for req in batch:
                lat_ms = (done_s - req.enqueue_s) * 1e3
                result = RequestResult(
                    id=req.id,
                    rows=req.rows,
                    bucket=bucket,
                    logits=host_logits[row: row + req.rows],
                    latency_ms=lat_ms,
                )
                self.results[req.id] = result
                row += req.rows
                stats.requests += 1
                stats.images += req.rows
                stats.latencies_ms.append(lat_ms)
                self._notify(result)

    def _may_dispatch_ahead(self, bucket: int) -> bool:
        """Whether the next batch may be dispatched before this one (of
        ``bucket``) is blocked on: only where this batch's outcome cannot
        change the next one's route or launch — no circuit breaker, no
        guarded ladder — and no fault injector is armed, so the chaos
        faults (queue stall, slow launch, poisoned output) keep the order
        they are defined against (DESIGN.md §15)."""
        return (not self.config.guarded and not get_injector().enabled
                and self._breaker(bucket) is None)

    def _dispatch(self, staged, ahead: bool) -> _Launch:
        """Route and dispatch one staged batch (jax runs it asynchronously);
        a typed failure is kept on the launch, to be failed in its turn.
        ``ahead``: the previous batch is still in flight."""
        batch, bucket, entry, x_dev, bid = staged
        breaker = self._breaker(bucket)
        route = "fused"
        if breaker is not None and not breaker.allow():
            route = breaker.pinned_rung or "reference"
        launch = _Launch(batch, bucket, entry, x_dev, bid, breaker, route,
                         time.perf_counter())
        try:
            launch.logits, launch.report = self._run_route(
                route, entry, x_dev
            )
        except RobustError as e:
            launch.err = e
        tracer = get_tracer()
        tracer.span(_DISPATCH, launch.t0, time.perf_counter(), bid,
                    bucket=bucket, route=_ROUTES[route])
        if route == "fused":
            self.dispatches["batches"] += 1
            tracer.bump("serve.batches")
            if ahead:
                self.dispatches["dispatched_ahead"] += 1
                tracer.bump("serve.dispatched_ahead")
        return launch

    def _finish(self, launch: _Launch, t_free: float) -> float:
        """Block on a dispatched batch, then run its sentinel, watchdog and
        breaker bookkeeping and record (or fail) it.  ``t_free`` is when
        the previous batch's result was ready: the batch's ``wall_ms``
        runs from the later of that and its own dispatch start to its own
        result, so a batch dispatched ahead is not charged for the wait
        behind its predecessor.  Returns when its result was ready."""
        batch, bucket, entry, route = (
            launch.batch, launch.bucket, launch.entry, launch.route
        )
        err, logits, report = launch.err, launch.logits, launch.report
        inj = get_injector()
        tracer = get_tracer()
        sentinel_tripped = False
        if err is None:
            t_block = time.perf_counter()
            jax.block_until_ready(logits)
            tracer.span(_BLOCK, t_block, time.perf_counter(), launch.bid,
                        bucket=bucket)
            if inj.enabled:
                delay = inj.launch_delay(self._launch_name(bucket))
                if delay:
                    time.sleep(delay)
                if route == "fused":
                    logits = inj.corrupt_output(
                        self._launch_name(bucket), logits
                    )
            if self.config.output_sentinel and not np.isfinite(
                np.asarray(logits, dtype=np.float32)
            ).all():
                sentinel_tripped = True
                with self._lock:
                    self.resilience["sentinel_trips"] += 1
                if tracer.enabled:
                    tracer.bump("serve_sentinel_trip")
                    tracer.record_event(
                        "serve_sentinel",
                        model=self.graph.name, bucket=bucket,
                        route=route, action="reference_retry",
                    )
                logits = self._run_route("reference", entry, launch.x_dev)[0]
                jax.block_until_ready(logits)
        t_ready = time.perf_counter()
        wall_ms = (t_ready - max(launch.t0, t_free)) * 1e3
        wd_tripped = False
        if err is None and self.config.watchdog_factor is not None:
            thresh_ms = self._watchdog_threshold_ms(bucket, entry)
            if (thresh_ms is not None
                    and wall_ms > self.config.watchdog_factor * thresh_ms):
                wd_tripped = True
                with self._lock:
                    self.resilience["watchdog_trips"] += 1
                if tracer.enabled:
                    tracer.bump("serve_watchdog_trip")
                    tracer.record_event(
                        "serve_watchdog",
                        model=self.graph.name, bucket=bucket,
                        wall_ms=wall_ms,
                        threshold_ms=self.config.watchdog_factor * thresh_ms,
                        route=route,
                    )
        breaker = launch.breaker
        if breaker is not None and route == "fused":
            degraded = report is not None and report.degraded
            if err is not None or wd_tripped or sentinel_tripped or degraded:
                breaker.record_failure(
                    rung=self._pin_rung(report, sentinel_tripped)
                )
            else:
                breaker.record_success()
            self._flush_breaker(bucket, breaker)
        if err is not None:
            self._fail_batch(batch, bucket, err, wall_ms)
        else:
            t_rec = time.perf_counter()
            self._record(
                batch, bucket, logits, wall_ms,
                calibrate=not (wd_tripped or sentinel_tripped),
            )
            tracer.span(_RECORD, t_rec, time.perf_counter(), launch.bid,
                        bucket=bucket, rows=sum(r.rows for r in batch))
        return t_ready

    def drain(self) -> list[RequestResult]:
        """Execute the queue to empty; returns the drained batches' results
        in completion order (failed batches included, with typed errors).

        The loop keeps two forwards in flight.  In steady state it
        dispatches batch ``n+1`` (jax enqueues it behind ``n`` on the
        device), then blocks on ``n`` and records it, then stages ``n+2``
        (pad + ``device_put``) while ``n+1`` computes — so record, staging
        and the next dispatch all run under device time, and the cycle
        tends to ``max(host, device)`` rather than their sum.  Results
        still complete in batch order.  It falls back to depth 1 —
        dispatch ``n``, stage ``n+1``, block on and record ``n``, today's
        double-buffered input stage — wherever the loop observes that it
        must: no next batch is queued (a one-in-flight caller), a typed
        dispatch failure, or :meth:`_may_dispatch_ahead` says ``n``'s
        outcome could change ``n+1``'s route (a circuit breaker, the
        guarded ladder, an armed fault injector).  A batch's ``wall_ms``
        is its own service time: from the later of its dispatch start and
        its predecessor's result, to its own result.  Around the core sit
        the resilience hooks (each a no-op unless configured/armed):
        injected queue stalls, breaker routing, the slow-launch delay, the
        output sentinel, the watchdog, and typed batch failure.  It returns
        only with nothing in flight."""
        completed: list[RequestResult] = []
        inj = get_injector()

        def finish(launch: _Launch, t_free: float) -> float:
            t_ready = self._finish(launch, t_free)
            completed.extend(self.results[r.id] for r in launch.batch)
            return t_ready

        with self._drain_lock:
            staged = self._next_staged()
            ahead: _Launch | None = None  # dispatched, not yet blocked on
            t_free = -float("inf")  # when the previous result was ready
            while staged is not None:
                if inj.enabled and inj.queue_stalled():
                    with self._lock:
                        self.resilience["stalls"] += 1
                    tracer = get_tracer()
                    if tracer.enabled:
                        tracer.bump("serve_stall")
                        tracer.record_event(
                            "serve_stall", model=self.graph.name
                        )
                    time.sleep(0.001)
                    continue
                try:
                    launch = self._dispatch(staged, ahead is not None)
                finally:  # the batch in flight lands first, even on a raise
                    if ahead is not None:
                        t_free = finish(ahead, t_free)
                        ahead = None
                staged = self._next_staged()
                if (staged is not None and launch.err is None
                        and self._may_dispatch_ahead(launch.bucket)):
                    ahead = launch
                else:
                    t_free = finish(launch, t_free)
        return completed

    def serve(self, xs) -> list[RequestResult]:
        """Submit + drain in one call; results ordered by request id
        (admission order), rejected requests included with their errors."""
        ids = self.submit_many(xs)
        self.drain()
        return [self.results[i] for i in ids]

    # -- reporting ----------------------------------------------------------

    def cache_info(self) -> dict:
        return {
            **self.cache_counters,
            "currsize": len(self._cache),
            "maxsize": self.config.plan_cache_size,
        }

    def summary(self) -> dict:
        """The bucket/SLO/throughput table as one JSON-safe dict — modeled
        (``slo_us``/``steady_us``/``modeled_cycles``) next to measured
        (``p50_ms``/``p95_ms``/``imgs_per_s``) per bucket, the drain
        loop's ``dispatch`` counts (fused batches, and those dispatched
        while the previous one was in flight), the serve
        and partition cache counters and the resilience section (shed /
        expired / failed / watchdog / sentinel / stall counts and one
        breaker snapshot per bucket) — DESIGN.md §14/§15's observable
        surface."""
        from .partition import partition_cache_info
        from .runner import jit_trace_count

        with self._lock:
            rows = []
            for bucket in sorted(self._stats):
                st = self._stats[bucket]
                entry = self._cache.get(self._key(bucket))
                row = {
                    "bucket": bucket,
                    "batches": st.batches,
                    "requests": st.requests,
                    "images": st.images,
                    "p50_ms": _percentile(st.latencies_ms, 50),
                    "p95_ms": _percentile(st.latencies_ms, 95),
                    "imgs_per_s": (
                        st.images / (st.wall_ms / 1e3) if st.wall_ms else 0.0
                    ),
                }
                if entry is not None:  # evicted entries lose model columns
                    row.update(
                        slo_us=entry.slo_us,
                        steady_us=entry.steady_us,
                        modeled_cycles=entry.compute_cycles,
                        staging_cycles=entry.staging_cycles,
                        launches=entry.plan.n_launches(),
                        hbm_bytes=entry.plan.hbm_bytes(),
                    )
                rows.append(row)
            total_images = sum(st.images for st in self._stats.values())
            total_wall_ms = sum(st.wall_ms for st in self._stats.values())
            from dataclasses import asdict

            breakers = {
                str(key[2]): asdict(br.snapshot())
                for key, br in sorted(
                    self._breakers.items(), key=lambda kv: kv[0][2]
                )
            }
            return {
                "model": self.graph.name,
                "compute_dtype": self.compute_dtype,
                "guarded": self.config.guarded,
                "buckets": rows,
                "completed": sum(
                    1 for r in self.results.values() if r.ok
                ),
                "rejected": self.rejected,
                "dispatch": dict(self.dispatches),
                "images": total_images,
                "imgs_per_s": (
                    total_images / (total_wall_ms / 1e3)
                    if total_wall_ms else 0.0
                ),
                "cache": {
                    "serve": self.cache_info(),
                    "partition": partition_cache_info()._asdict(),
                    "jit_traces": jit_trace_count(),
                },
                "resilience": {
                    **self.resilience,
                    "breakers": breakers,
                },
            }


# ---------------------------------------------------------------------------
# CLI: synthetic request stream
# ---------------------------------------------------------------------------


def _synthetic_stream(graph: Graph, n: int, buckets, seed: int):
    """Deterministic request mix: row counts cycle through the bucket range
    so every bucket is exercised; pixels are seeded normals."""
    rng = np.random.default_rng(seed)
    limit = max(buckets)
    sizes = [(i % limit) + 1 for i in range(n)]
    return [
        rng.standard_normal(
            (r, graph.input_size, graph.input_size, graph.in_channels)
        ).astype(np.float32)
        for r in sizes
    ]


def _wave_delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def _cache_snapshot(engine: ServingEngine) -> dict:
    from .partition import partition_cache_info
    from .runner import jit_trace_count

    info = partition_cache_info()
    return {
        "serve_hits": engine.cache_counters["hits"],
        "serve_misses": engine.cache_counters["misses"],
        "partition_hits": info.hits,
        "partition_misses": info.misses,
        "jit_traces": jit_trace_count(),
    }


INJECT_MODES = ("slow_launch", "stage_fail", "poison", "stall")


def _armed_injector(mode: str, seed: int, breaker: int | None):
    """A :class:`FaultInjector` armed for the chosen chaos mode — fired
    during wave 2 only, so wave 1 calibrates the watchdog first."""
    from repro.robust.faults import FaultInjector

    inj = FaultInjector(seed=seed)
    if mode == "slow_launch":
        inj.slow_launch(0.25, times=max(breaker or 1, 1))
    elif mode == "stage_fail":
        inj.raise_at("stage", times=2, message="injected device_put failure")
    elif mode == "poison":
        inj.poison_output(times=2)
    elif mode == "stall":
        inj.stall_queue(3)
    return inj


def main(argv=None) -> int:
    from .graph import MODELS
    from .runner import init_network_params

    ap = argparse.ArgumentParser(
        prog="python -m repro.net.serve",
        description="Drive a synthetic request stream through the serving"
        " engine and print the bucket/SLO/throughput table.",
    )
    ap.add_argument("--model", default="lenet", choices=sorted(MODELS))
    ap.add_argument("--requests", type=int, default=32,
                    help="requests per wave (two waves are driven; the"
                    " second demonstrates plan/jit cache reuse)")
    ap.add_argument("--input", type=int, default=None,
                    help="override the model's input size")
    ap.add_argument("--dtype", default=None,
                    help="compute dtype (default: the graph's)")
    ap.add_argument("--buckets", default="1,2,4,8",
                    help="comma-separated ascending batch buckets")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--guarded", action="store_true",
                    help="run buckets under the degradation ladder")
    ap.add_argument("--dry-stream", action="store_true",
                    help="deterministic in-process stream sized for CI"
                    " smoke (interpret-mode kernels)")
    ap.add_argument("--inject", default=None, choices=INJECT_MODES,
                    help="arm a serving fault for wave 2 (wave 1 stays"
                    " clean to calibrate the watchdog); implies breaker 1,"
                    " watchdog 3, and the output sentinel unless given")
    ap.add_argument("--breaker", type=int, default=None, metavar="K",
                    help="open the per-bucket circuit breaker after K"
                    " consecutive failing launches")
    ap.add_argument("--breaker-cooldown", type=float, default=0.0,
                    metavar="S", help="breaker cooldown seconds before the"
                    " half-open probe (default 0: probe immediately)")
    ap.add_argument("--watchdog", type=float, default=None, metavar="N",
                    help="flag launches exceeding N x the expected batch"
                    " wall (modeled SLO or measured p50)")
    ap.add_argument("--deadline-us", type=float, default=None,
                    help="submit every request with this relative deadline"
                    " (enables deadline-aware EDF admission)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the summary (with per-wave cache deltas)"
                    " as JSON")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    kwargs = {"input_size": args.input} if args.input else {}
    graph = MODELS[args.model](**kwargs)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    breaker = args.breaker
    watchdog = args.watchdog
    sentinel = False
    if args.inject is not None:
        breaker = 1 if breaker is None else breaker
        watchdog = 3.0 if watchdog is None else watchdog
        sentinel = args.inject == "poison"
    config = ServeConfig(
        buckets=buckets,
        compute_dtype=args.dtype,
        guarded=args.guarded,
        interpret=True if args.dry_stream else None,
        deadline_aware=args.deadline_us is not None,
        breaker_threshold=breaker,
        breaker_cooldown_s=args.breaker_cooldown,
        watchdog_factor=watchdog,
        output_sentinel=sentinel,
    )
    params = init_network_params(graph, jax.random.PRNGKey(args.seed))
    engine = ServingEngine(graph, params, config)
    stream = _synthetic_stream(graph, args.requests, buckets, args.seed)

    from contextlib import nullcontext

    from repro.robust.faults import inject

    waves = []
    for wave in (1, 2):
        chaos = (
            inject(injector=_armed_injector(
                args.inject, args.seed, breaker
            ))
            if args.inject is not None and wave == 2 else nullcontext()
        )
        before = _cache_snapshot(engine)
        t0 = time.perf_counter()
        with chaos:
            for x in stream:
                engine.submit(x, deadline_us=args.deadline_us)
            engine.drain()
        wall_s = time.perf_counter() - t0
        delta = _wave_delta(before, _cache_snapshot(engine))
        delta["wall_s"] = wall_s
        waves.append(delta)

    summary = engine.summary()
    summary["waves"] = waves
    summary["submitted"] = 2 * len(stream)
    summary["terminal"] = len(engine.results)

    from repro.obs.explain import serve_table

    serve_table(summary)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2, default=str)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
