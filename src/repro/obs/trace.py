"""Tracing: host spans, launch spans, events, counters, and the global hook.

Host spans time the serving path where the work happens: one record
``(name, start_s, end_s, thread, id, parent)`` per admission, batch stage,
dispatch, device wait, result record and hand-off (``net/serve.py``,
``net/frontend.py``), on :func:`time.perf_counter`.  ``id`` is a request id
for request spans and a batch id for batch spans; ``parent`` links a
request's admission to the batch its queue wait ends in.  They go into a
fixed-capacity ring of preallocated columns: recording one allocates no
Python object, takes its slot with ``next()`` on an :func:`itertools.count`
(so the client and drain threads need no lock), and the ring's memory is
set when the collector is made.

One :class:`LaunchSpan` per fused-pyramid launch is recorded by
:func:`repro.net.runner.run_network_per_launch`, the explicit
launch-by-launch timed forward: the plan's static knobs and modeled costs
(what the planner promised) next to the measured wall clock (what the
launch did).  :class:`TraceEvent` covers rare things: ``auto_partition``
cache hits/misses, per-level END-skip counts, rejections, sheds, breaker
transitions.

The process-global tracer is an always-on, bounded
:class:`TraceCollector` (the flight recorder an operator keeps): host spans
in a ring of :data:`SPAN_CAPACITY`, events in a deque of
:data:`EVENT_CAPACITY`.  Installing a tracer never changes what runs.
``set_tracer(NULL_TRACER)`` turns recording off; :func:`tracing` scopes a
fresh collector whose events are unbounded::

    from repro.obs import tracing

    with tracing() as collector:
        engine.serve(images)
    print(collector.spans_between())
"""

from __future__ import annotations

import contextlib
import itertools
import math
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

# at least 2x the busiest benchmark cell's spans over warm-up plus a 51 s
# window (about 3,800 spans/s); the columns are zero-filled on allocation,
# so pages are committed as the ring fills
SPAN_CAPACITY = 1 << 19
EVENT_CAPACITY = 4096


@dataclass(frozen=True)
class LaunchSpan:
    """One fused-pyramid launch: planned knobs + modeled costs + measurement.

    ``start_s`` is :func:`time.perf_counter` at launch start (comparable
    only within one process); ``duration_ms`` is the measured wall clock of
    the launch with its results blocked until ready — in interpret mode the
    first call includes jit tracing, so callers wanting steady-state numbers
    warm up first (``repro.obs.explain --run`` does).  The modeled fields
    are the exact quantities the partitioner optimized, copied from the
    :class:`~repro.core.program.LaunchPlan` so model-vs-measured joins never
    re-derive them.
    """

    name: str  # pyramid name, e.g. "CL1..MPL2"
    model: str  # graph name, e.g. "lenet"
    regime: str  # resident / streamed_w2 / streamed_w2_c4 / ...
    out_region: int
    alpha: int
    q_convs: int
    x_slots: int
    w_slots: int
    c_tiles: int
    batch: int
    compute_dtype: str
    streamed: bool
    hbm_bytes: int  # modeled off-chip traffic of the launch (batch-scaled)
    vmem_bytes: int  # modeled resident working set
    modeled_cycles: int  # pipeline-aware cycle model (batch-scaled)
    modeled_us: float  # modeled_cycles at the cycle model's 100 MHz
    start_s: float
    duration_ms: float


@dataclass(frozen=True)
class TraceEvent:
    """A point event: cache hit/miss, skip stats, forward-level timing."""

    name: str
    ts_s: float
    args: dict


class HostSpan(NamedTuple):
    """One host span as read back from a :class:`TraceCollector`.

    The first three fields are the ``(name, start_s, end_s)`` form the
    benchmark builds from its client records; the times are
    :func:`time.perf_counter` seconds.  ``id``/``parent`` are ``-1`` where
    unset, ``route`` is ``""`` where unset."""

    name: str
    start_s: float
    end_s: float
    thread: int
    id: int
    parent: int
    bucket: int
    rows: int
    route: str


# the process's string table for span names and routes; code 0 is the
# empty slot
_STRINGS = [""]
_CODES = {"": 0}
_STRINGS_LOCK = threading.Lock()


def span_code(name: str) -> int:
    """The code under which ``name`` (a span name or a route) is stored in
    the span columns; call sites look theirs up once, at import."""
    with _STRINGS_LOCK:
        code = _CODES.get(name)
        if code is None:
            code = _CODES[name] = len(_STRINGS)
            _STRINGS.append(name)
        return code


class TraceCollector:
    """Host-span ring, launch spans, events and named counters.

    ``capacity`` (a power of two) fixes the host-span ring: once full, each
    new span overwrites the oldest.  ``max_events`` bounds the event deque
    (``None``: unbounded).  ``enabled`` is class-level ``True`` so the
    instrumented check (``get_tracer().enabled``) costs one attribute load
    either way.
    """

    enabled = True

    def __init__(self, capacity: int = SPAN_CAPACITY,
                 max_events: int | None = None) -> None:
        if capacity < 1 or capacity & (capacity - 1):
            raise ValueError(f"capacity must be a power of two, got {capacity}")
        self.capacity = capacity
        self.spans: list[LaunchSpan] = []
        self.events: deque[TraceEvent] = deque(maxlen=max_events)
        self.counters: dict[str, int] = {}
        self._mask = capacity - 1
        self._slots = itertools.count()
        self._cols = {
            "seq": np.zeros(capacity, np.int64),
            "code": np.zeros(capacity, np.int16),
            "start": np.zeros(capacity, np.float64),
            "end": np.zeros(capacity, np.float64),
            "thread": np.zeros(capacity, np.uint64),
            "id": np.zeros(capacity, np.int64),
            "parent": np.zeros(capacity, np.int64),
            "bucket": np.zeros(capacity, np.int32),
            "rows": np.zeros(capacity, np.int32),
            "route": np.zeros(capacity, np.int16),
        }
        # item stores through a memoryview are the cheapest way to write
        # one element of a numpy column from Python
        (self._seq, self._code, self._start, self._end, self._thread, self._id,
         self._parent, self._bucket, self._rows, self._route) = (
            memoryview(c) for c in self._cols.values()
        )

    def span(self, code: int, start_s: float, end_s: float, id: int = -1,
             parent: int = -1, bucket: int = 0, rows: int = 0,
             route: int = 0) -> int:
        """Record one host span (``code``/``route`` from :func:`span_code`);
        returns its ring slot, for :meth:`link`."""
        i = next(self._slots)
        j = i & self._mask
        self._seq[j] = i
        self._start[j] = start_s
        self._end[j] = end_s
        self._thread[j] = threading.get_ident()
        self._id[j] = id
        self._parent[j] = parent
        self._bucket[j] = bucket
        self._rows[j] = rows
        self._route[j] = route
        self._code[j] = code
        return j

    def link(self, slot: int, id: int, parent: int) -> None:
        """Set the ``parent`` of the span in ``slot`` if it still holds
        ``id`` (a wrapped ring may have reused the slot)."""
        if slot >= 0 and self._id[slot] == id:
            self._parent[slot] = parent

    def holds(self, t0: float) -> bool:
        """Whether every span that ended at or after ``t0`` is still in the
        ring (always, until it first wraps)."""
        live = self._cols["code"] != 0
        if not live.any() or self._cols["seq"][live].max() < self.capacity:
            return True
        return float(self._cols["end"][live].min()) <= t0

    def spans_between(self, t0: float = -math.inf,
                      t1: float = math.inf) -> list[HostSpan]:
        """The ring's host spans that start in ``[t0, t1)``, by start time."""
        c = self._cols
        code = c["code"].copy()
        start = c["start"].copy()
        idx = np.flatnonzero((code != 0) & (start >= t0) & (start < t1))
        idx = idx[np.argsort(start[idx], kind="stable")]
        cols = [c[k][idx].tolist() for k in (
            "end", "thread", "id", "parent", "bucket", "rows", "route")]
        return [
            HostSpan(_STRINGS[n], s, e, th, i, p, b, r, _STRINGS[ro])
            for n, s, e, th, i, p, b, r, ro in zip(
                code[idx].tolist(), start[idx].tolist(), *cols)
        ]

    def record_span(self, span: LaunchSpan) -> None:
        self.spans.append(span)

    def record_event(self, name: str, **args) -> None:
        self.events.append(
            TraceEvent(name=name, ts_s=time.perf_counter(), args=args)
        )

    def bump(self, counter: str, n: int = 1) -> None:
        self.counters[counter] = self.counters.get(counter, 0) + n


class _NullTracer:
    """Recording off: nothing is recorded, nothing is kept.

    Instrumented sites gate events on ``enabled``; the record methods exist
    (as no-ops) so a site that doesn't bother gating stays correct."""

    enabled = False
    spans: tuple = ()
    events: tuple = ()
    counters: dict = {}

    def span(self, *args, **kwargs) -> int:
        return -1

    def link(self, slot: int, id: int, parent: int) -> None:
        pass

    def holds(self, t0: float) -> bool:
        return False

    def spans_between(self, t0: float = -math.inf,
                      t1: float = math.inf) -> list[HostSpan]:
        return []

    def record_span(self, span: LaunchSpan) -> None:
        pass

    def record_event(self, name: str, **args) -> None:
        pass

    def bump(self, counter: str, n: int = 1) -> None:
        pass


NULL_TRACER = _NullTracer()

# the always-on flight recorder, made once per process
DEFAULT_TRACER = TraceCollector(SPAN_CAPACITY, max_events=EVENT_CAPACITY)

_tracer = DEFAULT_TRACER


def get_tracer():
    """The process-global tracer: :data:`DEFAULT_TRACER` unless another was
    installed via :func:`set_tracer` / :func:`tracing`."""
    return _tracer


def set_tracer(tracer) -> None:
    """Install ``tracer`` globally: :data:`NULL_TRACER` turns recording
    off, ``None`` restores :data:`DEFAULT_TRACER`."""
    global _tracer
    _tracer = DEFAULT_TRACER if tracer is None else tracer


@contextlib.contextmanager
def tracing(collector: TraceCollector | None = None):
    """Scope a collector as the global tracer; yields the collector.

    Nesting restores the previous tracer on exit, so a traced benchmark can
    call traced helpers without clobbering the outer collection.
    """
    col = TraceCollector() if collector is None else collector
    prev = get_tracer()
    set_tracer(col)
    try:
        yield col
    finally:
        set_tracer(prev)
