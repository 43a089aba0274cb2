"""Concurrent serving front end: Future-style handles over one drain loop.

:class:`~repro.net.serve.ServingEngine` is deliberately synchronous — its
``submit``/``drain`` split keeps the execution path testable and the jax
work single-threaded.  Production traffic is neither: requests arrive from
many threads and callers want to *wait on their own result*, not poll a
results dict.  This module is the bridge (DESIGN.md §15):

* :class:`ServingFrontend` wraps an engine with a **background drain
  thread**: producer threads call :meth:`ServingFrontend.submit` (the
  engine's locked admission path — shape checks, shedding, typed
  rejection all still apply) and get back a :class:`RequestHandle`; a
  daemon thread wakes on every submit and runs ``engine.drain()``, so
  batches keep the engine's pipeline (the next batch dispatched before
  the current one is blocked on, whenever one is queued) and all jax
  calls stay on one thread.
* :class:`RequestHandle` is a minimal Future: :meth:`RequestHandle.result`
  blocks (with timeout) until the request is terminal and returns the
  :class:`~repro.net.serve.RequestResult` — completed, rejected, shed,
  expired, or failed, always typed, never an exception from the engine's
  internals.

Delivery rides the engine's completion listeners: every terminal result
fires the frontend's listener, which resolves the matching handle.  A
request can complete *before* its handle is registered (the drain thread
races the submit return path), so results with no handle yet are parked
and claimed at registration — no result is ever lost to the race, which
is exactly what the multi-threaded hammer test asserts.

Two host spans go to the process's tracer (``repro.obs.get_tracer()``):
``frontend.idle`` while the drain thread waits for work, and
``frontend.deliver`` per request, from the listener resolving its handle to
:meth:`RequestHandle.result` returning in the caller's thread.

Use::

    frontend = ServingFrontend(engine)
    with frontend:
        handles = [frontend.submit(x, deadline_us=5e5) for x in stream]
        results = [h.result(timeout=30.0) for h in handles]
"""

from __future__ import annotations

import threading
import time

from repro.obs.trace import get_tracer, span_code

from .serve import RequestResult, ServingEngine

_IDLE = span_code("frontend.idle")
_DELIVER = span_code("frontend.deliver")


class RequestHandle:
    """A Future-style handle for one submitted request."""

    def __init__(self, rid: int) -> None:
        self.id = rid
        self._event = threading.Event()
        self._result: RequestResult | None = None
        self._resolved_s: float | None = None  # None again once delivered

    def _resolve(self, result: RequestResult) -> None:
        self._resolved_s = time.perf_counter()
        self._result = result
        self._event.set()

    def done(self) -> bool:
        """True once the request is terminal (result available)."""
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> RequestResult:
        """Block until the request is terminal; returns its
        :class:`RequestResult`.  Raises ``TimeoutError`` if ``timeout``
        seconds pass first — the request may still complete later."""
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.id} not terminal after {timeout}s"
            )
        t0, self._resolved_s = self._resolved_s, None
        if t0 is not None:
            get_tracer().span(_DELIVER, t0, time.perf_counter(), self.id)
        return self._result


class ServingFrontend:
    """Thread-safe async layer over one :class:`ServingEngine`.

    ``start()`` launches the daemon drain thread (the context manager does
    it for you); ``submit`` admits from any thread and returns a
    :class:`RequestHandle`; ``stop()`` drains outstanding work and joins
    the thread.  The engine must not be drained by anyone else while the
    frontend owns it — the engine's drain lock enforces serialization, but
    a foreign drain would steal completions the frontend expects to
    observe (it still would via the listener; it just wastes a wake-up).

    While producers keep more than one batch queued, the drain thread
    keeps two forwards in flight: it dispatches batch ``n+1`` before
    blocking on ``n``, so delivering ``n``'s results and staging ``n+2``
    run under ``n+1``'s device time.  With one request in flight it
    blocks on each batch as it is dispatched (depth 1); a configured
    breaker or guarded ladder also keeps depth 1 (``ServingEngine.drain``).
    Handles still resolve in batch order.
    """

    def __init__(self, engine: ServingEngine) -> None:
        self.engine = engine
        self._handles: dict[int, RequestHandle] = {}
        self._early: dict[int, RequestResult] = {}
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._stopping = threading.Event()
        self._thread: threading.Thread | None = None
        engine.add_listener(self._on_result)

    # -- result delivery ----------------------------------------------------

    def _on_result(self, result: RequestResult) -> None:
        # called by the engine (under its lock) for every terminal result;
        # park results whose handle is not registered yet — submit() may
        # still be between engine.submit() and _register()
        with self._lock:
            handle = self._handles.pop(result.id, None)
            if handle is None:
                self._early[result.id] = result
                return
        handle._resolve(result)

    def _register(self, rid: int) -> RequestHandle:
        handle = RequestHandle(rid)
        with self._lock:
            early = self._early.pop(rid, None)
            if early is None:
                self._handles[rid] = handle
        if early is not None:
            handle._resolve(early)
        return handle

    # -- producer API -------------------------------------------------------

    def submit(self, x, *, deadline_us: float | None = None,
               priority: int = 0) -> RequestHandle:
        """Admit one request from any thread; returns its handle.

        Rejections (bad shape, full queue, admission shed) resolve the
        handle immediately with the typed error result — ``submit`` itself
        never raises for a bad request."""
        rid = self.engine.submit(x, deadline_us=deadline_us,
                                 priority=priority)
        handle = self._register(rid)
        self._work.set()
        return handle

    # -- drain loop ---------------------------------------------------------

    def _loop(self) -> None:
        while not self._stopping.is_set():
            t0 = time.perf_counter()
            self._work.wait(timeout=0.05)
            get_tracer().span(_IDLE, t0, time.perf_counter())
            self._work.clear()
            self.engine.drain()
        self.engine.drain()  # final sweep: nothing submitted is abandoned

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stopping.clear()
        self._thread = threading.Thread(
            target=self._loop, name="serve-drain", daemon=True
        )
        self._thread.start()

    def stop(self, timeout: float | None = 30.0) -> None:
        """Signal the drain thread, let it finish outstanding work, join."""
        if self._thread is None:
            return
        self._stopping.set()
        self._work.set()
        self._thread.join(timeout)
        self._thread = None

    def __enter__(self) -> ServingFrontend:
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
