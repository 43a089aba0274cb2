"""Serving chaos suite (DESIGN.md §15): every serving fault class must
terminate with typed per-request results while subsequent requests keep
being served.

The engine-level counterpart of ``tests/test_chaos.py``: where that suite
proves the *ladder* absorbs launch faults, this one proves the *service*
around it — blown deadlines, stuck launches, repeated kernel failure,
queue overflow, staging failure, poisoned outputs, and drain-loop stalls
— never hangs a wave, never loses or duplicates a request, and surfaces
every transition (watchdog, breaker, sentinel, shed/expiry) as typed
results, counters, and trace events.  Also home of the breaker unit
tests (fake clock), the deadline/EDF admission tests, the overload
shedding acceptance (EDF+shedding vs FIFO under the same injected slow
launches), the multi-threaded frontend hammer, and the PR 9 equivalence
guarantee (all resilience knobs off == the plain engine).
"""

import threading
import time

import jax
import numpy as np
import pytest

from repro.net.frontend import ServingFrontend
from repro.net.graph import lenet5
from repro.net.runner import init_network_params, reference_network
from repro.net.serve import (
    Request,
    ServeConfig,
    ServingEngine,
)
from repro.obs import tracing
from repro.obs.stats import percentile
from repro.robust.breaker import CLOSED, HALF_OPEN, OPEN, CircuitBreaker
from repro.robust.errors import (
    DeadlineExceeded,
    FaultInjected,
    NumericError,
    PreflightError,
)
from repro.robust.faults import FaultInjector, inject

KEY = jax.random.PRNGKey(0)
GRAPH = lenet5()
PARAMS = init_network_params(GRAPH, KEY)


def _images(rows: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal(
        (rows, GRAPH.input_size, GRAPH.input_size, GRAPH.in_channels)
    ).astype(np.float32)


def _engine(**overrides) -> ServingEngine:
    cfg = ServeConfig(**{"buckets": (1, 2, 4), **overrides})
    return ServingEngine(GRAPH, PARAMS, cfg)


def _events(collector, name):
    return [e for e in collector.events if e.name == name]


# ---------------------------------------------------------------------------
# circuit breaker unit tests (fake clock — no sleeping)
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


class TestCircuitBreaker:
    def test_opens_after_threshold_consecutive_failures(self):
        br = CircuitBreaker(threshold=3, cooldown_s=5.0, clock=FakeClock())
        for _ in range(2):
            br.record_failure()
            assert br.state == CLOSED and br.allow()
        br.record_failure()
        assert br.state == OPEN and not br.allow()
        assert br.opens == 1
        assert br.transitions[-1]["why"] == "3 consecutive failures"

    def test_success_resets_consecutive_count(self):
        br = CircuitBreaker(threshold=2, clock=FakeClock())
        br.record_failure()
        br.record_success()
        br.record_failure()
        assert br.state == CLOSED  # never two *consecutive* failures

    def test_cooldown_grants_one_half_open_probe(self):
        clock = FakeClock()
        br = CircuitBreaker(threshold=1, cooldown_s=5.0, clock=clock)
        br.record_failure(rung="interpret")
        assert br.state == OPEN and br.pinned_rung == "interpret"
        assert not br.allow()  # cooldown not elapsed
        clock.t = 5.0
        assert br.allow()  # the probe
        assert br.state == HALF_OPEN
        assert not br.allow()  # only one probe outstanding

    def test_probe_success_closes_and_unpins(self):
        clock = FakeClock()
        br = CircuitBreaker(threshold=1, cooldown_s=1.0, clock=clock)
        br.record_failure(rung="reference")
        clock.t = 1.0
        assert br.allow()
        br.record_success()
        assert br.state == CLOSED and br.pinned_rung is None
        states = [(t["from"], t["to"]) for t in br.transitions]
        assert states == [
            (CLOSED, OPEN), (OPEN, HALF_OPEN), (HALF_OPEN, CLOSED),
        ]

    def test_probe_failure_reopens_and_restarts_cooldown(self):
        clock = FakeClock()
        br = CircuitBreaker(threshold=1, cooldown_s=2.0, clock=clock)
        br.record_failure()
        clock.t = 2.0
        assert br.allow()
        br.record_failure(rung="reference")
        assert br.state == OPEN and br.opens == 2
        clock.t = 3.0  # only 1s since reopen: still open
        assert not br.allow()
        clock.t = 4.0
        assert br.allow() and br.state == HALF_OPEN

    def test_snapshot_and_validation(self):
        br = CircuitBreaker(threshold=2, clock=FakeClock())
        br.record_failure()
        snap = br.snapshot()
        assert snap.state == CLOSED and snap.failures == 1
        assert snap.threshold == 2 and snap.opens == 0
        with pytest.raises(ValueError):
            CircuitBreaker(threshold=0)
        with pytest.raises(ValueError):
            CircuitBreaker(cooldown_s=-1.0)


# ---------------------------------------------------------------------------
# deadlines: expiry, shedding, EDF order
# ---------------------------------------------------------------------------


class TestDeadlines:
    def test_expired_request_completes_typed_never_launches(self):
        eng = _engine(deadline_aware=True)
        # generous vs the modeled ETA (so admission passes), tiny vs the
        # wall clock (so it blows while queued before the drain)
        deadline_us = 20 * eng._entry(1).slo_us
        with tracing() as col:
            dead = eng.submit(_images(1, seed=1), deadline_us=deadline_us)
            live = eng.submit(_images(1, seed=2))
            time.sleep(deadline_us * 1e-6 + 0.01)
            eng.drain()
        res = eng.results[dead]
        assert not res.ok and isinstance(res.error, DeadlineExceeded)
        assert res.error.context["late_us"] > 0
        assert res.bucket is None  # never occupied a launch
        assert eng.results[live].ok
        assert eng.resilience["expired"] == 1
        assert len(_events(col, "serve_expired")) == 1

    def test_admission_shed_is_typed_and_counted(self):
        # a margin this large makes any finite deadline unmeetable, so the
        # request is shed at the door — no queue entry, no launch
        eng = _engine(deadline_aware=True, shed_margin=1e12)
        with tracing() as col:
            rid = eng.submit(_images(1), deadline_us=1e6)
        res = eng.results[rid]
        assert not res.ok and isinstance(res.error, DeadlineExceeded)
        assert res.error.context["eta_us"] > 0
        assert res.error.context["deadline_us"] == 1e6
        assert not eng.queue
        assert eng.resilience["shed"] == 1 and eng.rejected == 1
        assert len(_events(col, "serve_shed")) == 1

    def test_no_deadline_requests_never_shed_or_expire(self):
        eng = _engine(deadline_aware=True, shed_margin=1e12)
        res = eng.serve([_images(1, seed=s) for s in range(3)])
        assert all(r.ok for r in res)
        assert eng.resilience["shed"] == eng.resilience["expired"] == 0

    def test_edf_order_priority_then_deadline(self):
        eng = _engine(deadline_aware=True)
        now = time.perf_counter()
        specs = [  # (priority, deadline_s offset or None)
            (0, 10.0), (0, 1.0), (1, 10.0), (0, None),
        ]
        for i, (prio, off) in enumerate(specs):
            eng.queue.append(Request(
                id=i, x=np.zeros((1, 1, 1, 1)), rows=1, enqueue_s=now,
                deadline_us=None if off is None else off * 1e6,
                deadline_s=None if off is None else now + off,
                priority=prio,
            ))
        batch = eng._form_batch()
        # priority desc first, then nearest deadline, deadline-less last
        assert [r.id for r in batch] == [2, 1, 0, 3]

    def test_fifo_engine_ignores_deadlines(self):
        # PR 9 equivalence: without deadline_aware, a deadline rides along
        # inert — no shed, no expiry, strict FIFO formation
        eng = _engine()
        rid = eng.submit(_images(1), deadline_us=1.0)
        time.sleep(0.002)
        eng.drain()
        assert eng.results[rid].ok
        assert eng.resilience["shed"] == eng.resilience["expired"] == 0


class TestOverloadShedding:
    """The acceptance: under overload, deadline-aware admission sheds what
    cannot meet its deadline and what it admits completes on time, while
    the FIFO engine serves everything late.  Injected slow launches make
    the batch wall ~60ms, dwarfing scheduler noise."""

    DELAY_S = 0.06

    def _slow(self):
        inj = FaultInjector(seed=0)
        inj.slow_launch(self.DELAY_S, times=999)
        return inj

    def _warmed(self, **overrides):
        eng = _engine(**overrides)
        # clean pass first: jit compiles land outside the measured-walls
        # median, then two injected passes per bucket put the p50 batch
        # wall at the ~60ms injected delay — calibration now maps the
        # modeled us-scale SLO into the wall-clock domain
        for r in (1, 2, 4):
            eng.serve([_images(r, seed=r)])
        with inject(injector=self._slow()):
            for rep in range(2):
                for r in (1, 2, 4):
                    eng.serve([_images(r, seed=10 * rep + r)])
        for b in (1, 2, 4):
            p50 = percentile(eng._stats[b].batch_walls_ms, 50)
            assert p50 >= self.DELAY_S * 1e3
        return eng

    def test_edf_sheds_and_admitted_meet_deadlines(self):
        # shed_margin > 1 keeps admission conservative: what the engine
        # lets in, it is confident it can finish before the deadline
        eng = self._warmed(deadline_aware=True, shed_margin=1.6)
        deadline_us = 2.6 * self.DELAY_S * 1e6  # room for ~2 slow batches
        with inject(injector=self._slow()):
            ids = [
                eng.submit(_images(1, seed=s), deadline_us=deadline_us)
                for s in range(20)
            ]
            eng.drain()
        results = [eng.results[i] for i in ids]
        completed = [r for r in results if r.ok]
        typed = [
            r for r in results
            if not r.ok and isinstance(r.error, DeadlineExceeded)
        ]
        shed = [r for r in typed if "eta_us" in r.error.context]
        assert len(completed) + len(typed) == 20  # every request typed
        assert completed and shed  # overload actually shed load
        on_time = [
            r for r in completed if r.latency_ms * 1e3 <= deadline_us
        ]
        assert len(on_time) / len(completed) >= 0.95

    def test_fifo_baseline_misses_deadlines(self):
        eng = self._warmed()
        deadline_us = 2.6 * self.DELAY_S * 1e6
        with inject(injector=self._slow()):
            ids = [
                eng.submit(_images(1, seed=s), deadline_us=deadline_us)
                for s in range(20)
            ]
            eng.drain()
        results = [eng.results[i] for i in ids]
        assert all(r.ok for r in results)  # FIFO serves everything...
        late = [r for r in results if r.latency_ms * 1e3 > deadline_us]
        # ...but 20 rows over bucket-4 batches at ~60ms each puts the
        # tail far past the deadline: most of the stream is late
        assert len(late) >= len(results) // 2


# ---------------------------------------------------------------------------
# serving fault classes
# ---------------------------------------------------------------------------


class TestStagingFailure:
    def test_staging_fault_fails_batch_typed_queue_drains(self):
        eng = _engine()
        inj = FaultInjector(seed=0)
        inj.raise_at("stage", times=2, message="injected device_put failure")
        with tracing() as col, inject(injector=inj):
            res = eng.serve([_images(4, seed=s) for s in range(3)])
        assert [r.ok for r in res] == [False, False, True]
        for r in res[:2]:
            assert isinstance(r.error, FaultInjected)
            assert r.error.context["stage"] == "stage"
            assert r.bucket == 4
        assert eng.resilience["failed"] == 2
        assert len(_events(col, "serve_batch_error")) == 2
        # the engine is healthy afterwards, not wedged
        after = eng.serve([_images(1, seed=7)])
        assert after[0].ok


class TestStuckLaunch:
    def test_watchdog_trips_and_breaker_cycles(self):
        eng = _engine(watchdog_factor=3.0, breaker_threshold=1,
                      breaker_cooldown_s=0.0)
        eng.serve([_images(4, seed=0)])  # clean wall calibrates the watchdog
        inj = FaultInjector(seed=0)
        inj.slow_launch(0.25, times=1)
        with tracing() as col, inject(injector=inj):
            stuck = eng.serve([_images(4, seed=1)])
        assert stuck[0].ok  # slow, not wrong: the result still lands
        assert eng.resilience["watchdog_trips"] == 1
        wd = _events(col, "serve_watchdog")
        assert len(wd) == 1 and wd[0].args["wall_ms"] >= 250
        # breaker_threshold=1: the trip opened the breaker
        snap = eng.summary()["resilience"]["breakers"]["4"]
        assert snap["opens"] == 1 and snap["state"] == "open"
        # cooldown 0: the next launch is the half-open probe; clean run
        # closes the breaker — the full open -> half_open -> closed cycle
        with tracing() as col2:
            probe = eng.serve([_images(4, seed=2)])
        assert probe[0].ok
        trans = [
            (e.args["from_state"], e.args["to_state"])
            for e in _events(col2, "serve_breaker")
        ]
        assert trans == [("open", "half_open"), ("half_open", "closed")]
        snap = eng.summary()["resilience"]["breakers"]["4"]
        assert snap["state"] == "closed" and snap["pinned_rung"] is None

    def test_tripped_wall_not_used_for_calibration(self):
        eng = _engine(watchdog_factor=3.0)
        eng.serve([_images(4, seed=0)])
        clean_walls = list(eng._stats[4].batch_walls_ms)
        inj = FaultInjector(seed=0)
        inj.slow_launch(0.25, times=1)
        with inject(injector=inj):
            eng.serve([_images(4, seed=1)])
        assert eng.resilience["watchdog_trips"] == 1
        # the 250ms wall is excluded: a stall cannot raise its own bar
        assert eng._stats[4].batch_walls_ms == clean_walls


class TestRepeatedKernelFailure:
    def test_degraded_launches_open_breaker_and_pin_rung(self):
        # every guarded fused attempt hits the injected run fault and
        # degrades; two such launches open the breaker, which pins the
        # bucket to the gentlest rung that worked (interpret) for the
        # whole cooldown — no more failed fused attempts per batch
        eng = _engine(guarded=True, breaker_threshold=2,
                      breaker_cooldown_s=600.0)
        ref = np.asarray(
            reference_network(_images(4, seed=3), GRAPH, PARAMS)
        )
        inj = FaultInjector(seed=0)
        with tracing() as col, inject(injector=inj):
            # one run fault per batch: each fused attempt fails once and
            # the ladder lands on the interpret rung (a repeated fault,
            # not a permanent one — the breaker is what stops paying the
            # failed fused attempt per batch)
            inj.raise_at("run", times=1)
            r1 = eng.serve([_images(4, seed=1)])
            inj.raise_at("run", times=1)
            r2 = eng.serve([_images(4, seed=2)])
            r3 = eng.serve([_images(4, seed=3)])
        assert all(r[0].ok for r in (r1, r2, r3))
        snap = eng.summary()["resilience"]["breakers"]["4"]
        assert snap["state"] == "open"
        assert snap["pinned_rung"] == "interpret"
        opens = [
            e for e in _events(col, "serve_breaker")
            if e.args["to_state"] == "open"
        ]
        assert len(opens) == 1 and opens[0].args["bucket"] == 4
        # the third batch rode the pinned rung, not another fused attempt
        routes = [s.route for s in col.spans_between()
                  if s.name == "engine.dispatch"]
        assert routes[-1] == "interpret"
        np.testing.assert_allclose(r3[0].logits, ref, atol=1e-4)


class TestPoisonedOutput:
    def test_sentinel_reserves_from_reference(self):
        eng = _engine(output_sentinel=True, breaker_threshold=1,
                      breaker_cooldown_s=600.0)
        x = _images(2, seed=5)
        ref = np.asarray(reference_network(x, GRAPH, PARAMS))
        inj = FaultInjector(seed=0)
        inj.poison_output(times=1)
        with tracing() as col, inject(injector=inj):
            res = eng.serve([x])
        assert res[0].ok  # degraded-but-correct, never silent garbage
        assert np.isfinite(res[0].logits).all()
        np.testing.assert_allclose(res[0].logits, ref, atol=1e-4)
        assert eng.resilience["sentinel_trips"] == 1
        sent = _events(col, "serve_sentinel")
        assert len(sent) == 1
        assert sent[0].args["action"] == "reference_retry"
        # a sentinel trip is a fused-path failure: breaker opens pinned
        # to the reference walk
        snap = eng.summary()["resilience"]["breakers"]["2"]
        assert snap["state"] == "open"
        assert snap["pinned_rung"] == "reference"
        # while open, traffic serves from the pin and stays correct
        with tracing() as col2:
            res2 = eng.serve([x.copy()])
        assert res2[0].ok
        routes = [s.route for s in col2.spans_between()
                  if s.name == "engine.dispatch"]
        assert routes == ["reference"]
        np.testing.assert_allclose(res2[0].logits, ref, atol=1e-4)


class TestQueueOverflow:
    def test_overflow_rejects_typed_then_recovers(self):
        eng = _engine(max_queue=2)
        ids = [eng.submit(_images(1, seed=s)) for s in range(3)]
        res = eng.results[ids[2]]
        assert not res.ok and isinstance(res.error, PreflightError)
        assert res.error.context["field"] == "queue"
        eng.drain()
        assert eng.results[ids[0]].ok and eng.results[ids[1]].ok
        # capacity freed: the queue admits again
        after = eng.serve([_images(1, seed=9)])
        assert after[0].ok


class TestQueueStall:
    def test_stalls_delay_but_never_drop(self):
        eng = _engine()
        inj = FaultInjector(seed=0)
        inj.stall_queue(2)
        with tracing() as col, inject(injector=inj):
            res = eng.serve([_images(1, seed=s) for s in range(3)])
        assert all(r.ok for r in res)
        assert eng.resilience["stalls"] == 2
        assert len(_events(col, "serve_stall")) == 2
        assert inj.fired.count(("stall", "<queue>", "skip")) == 2


# ---------------------------------------------------------------------------
# concurrent frontend: hammer + handle semantics
# ---------------------------------------------------------------------------


class TestFrontend:
    def test_handle_resolves_with_result(self):
        eng = _engine()
        with ServingFrontend(eng) as fe:
            h = fe.submit(_images(2, seed=1))
            res = h.result(timeout=60.0)
        assert res.ok and res.id == h.id and h.done()

    def test_rejection_resolves_immediately(self):
        eng = _engine()
        fe = ServingFrontend(eng)  # not even started: rejection is sync
        h = fe.submit(np.zeros((1, 8, 8, 1), np.float32))
        res = h.result(timeout=1.0)
        assert not res.ok and isinstance(res.error, PreflightError)

    def test_multithreaded_hammer_no_lost_no_duplicate(self):
        eng = _engine()
        eng.serve([_images(4, seed=0)])  # pre-warm: hammer reuses the plan
        misses_before = eng.cache_counters["misses"]
        n_threads, per_thread = 6, 8
        results: dict[int, list] = {}
        res_lock = threading.Lock()
        errors: list = []

        def producer(tid: int) -> None:
            try:
                for i in range(per_thread):
                    h = fe.submit(_images(1, seed=tid * 100 + i))
                    r = h.result(timeout=120.0)
                    with res_lock:
                        results.setdefault(r.id, []).append(r)
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        with ServingFrontend(eng) as fe:
            threads = [
                threading.Thread(target=producer, args=(t,))
                for t in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120.0)
        assert not errors, errors
        # no lost, no duplicated results
        assert len(results) == n_threads * per_thread
        assert all(len(v) == 1 for v in results.values())
        assert all(v[0].ok for v in results.values())
        # cache counters stayed stable: the hammer added zero plan misses
        # (1-row traffic packs into already-planned buckets)
        assert eng.cache_counters["misses"] <= misses_before + 2
        assert eng.cache_counters["evictions"] == 0


# ---------------------------------------------------------------------------
# PR 9 equivalence: all resilience knobs off == the plain engine
# ---------------------------------------------------------------------------


class TestDefaultConfigEquivalence:
    def test_default_engine_is_the_plain_engine(self):
        """With every new knob at its default, nothing new runs: no
        breakers, no watchdog, no sentinel, no shed/expiry — and the
        logits are bit-identical between two default engines."""
        xs = [_images(r, seed=r) for r in (1, 4, 2)]
        eng_a = _engine()
        eng_b = _engine()
        res_a = eng_a.serve(xs)
        res_b = eng_b.serve([x.copy() for x in xs])
        for a, b in zip(res_a, res_b):
            assert a.ok and b.ok and a.bucket == b.bucket
            assert np.array_equal(a.logits, b.logits)
        summary = eng_a.summary()
        assert all(
            v == 0 for k, v in summary["resilience"].items()
            if k != "breakers"
        )
        assert summary["resilience"]["breakers"] == {}
        assert eng_a._breakers == {}

    def test_default_engine_surfaces_kernel_failure(self, monkeypatch):
        """A kernel that fails to compile fails the run: the default engine
        has no interpret or reference rung to fall to."""
        import repro.net.runner as runner

        def broken(*args, **kwargs):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        monkeypatch.setattr(runner, "fused_pyramid", broken)
        eng = _engine(buckets=(3,))  # a batch shape no other test traced
        with pytest.raises(RuntimeError, match="Mosaic failed"):
            eng.serve([_images(3, seed=9)])
        assert not any(eng.resilience.values())

    def test_config_validation(self):
        with pytest.raises(PreflightError):
            ServeConfig(shed_margin=0.0)
        with pytest.raises(PreflightError):
            ServeConfig(breaker_threshold=0)
        with pytest.raises(PreflightError):
            ServeConfig(watchdog_factor=1.0)


# ---------------------------------------------------------------------------
# admission hardening: check_request edge cases (satellite of §15)
# ---------------------------------------------------------------------------


class TestAdmissionHardening:
    def _field(self, exc_info) -> str:
        return exc_info.value.context["field"]

    def test_non_contiguous_view_accepted(self):
        from repro.robust.validate import check_request

        base = _images(8, seed=1)
        view = base[::2]  # stride trick: valid shape, not contiguous
        assert not view.flags["C_CONTIGUOUS"]
        check_request(view, GRAPH)  # does not raise
        eng = _engine()
        res = eng.serve([view])
        assert res[0].ok and res[0].rows == 4

    def test_f64_finite_accepted_f64_overflow_rejected(self):
        from repro.robust.validate import check_request

        ok64 = _images(1).astype(np.float64)
        check_request(ok64, GRAPH)  # finite f64 casts cleanly: admitted
        big = ok64.copy()
        big[0, 0, 0, 0] = 1e200  # finite in f64, Inf after the f32 cast
        with pytest.raises(NumericError) as ei:
            check_request(big, GRAPH)
        assert self._field(ei) == "range"

    def test_f64_nan_named_values_not_range(self):
        from repro.robust.validate import check_request

        bad = _images(1).astype(np.float64)
        bad[0, 1, 1, 0] = np.nan
        with pytest.raises(NumericError) as ei:
            check_request(bad, GRAPH)
        assert self._field(ei) == "values"

    def test_zero_row_batch_rejected(self):
        from repro.robust.validate import check_request

        empty = np.zeros(
            (0, GRAPH.input_size, GRAPH.input_size, GRAPH.in_channels),
            np.float32,
        )
        with pytest.raises(PreflightError) as ei:
            check_request(empty, GRAPH)
        assert self._field(ei) == "batch"

    def test_rejection_fields_name_the_offender(self):
        from repro.robust.validate import check_request

        cases = [
            (np.zeros((32, 32, 1), np.float32), "rank"),
            (np.zeros((1, 8, 8, 1), np.float32), "spatial"),
            (np.zeros((1, 32, 32, 3), np.float32), "channels"),
            (np.array([[[["x"]]]], dtype=object), None),  # dtype below
        ]
        for x, field in cases[:3]:
            with pytest.raises(PreflightError) as ei:
                check_request(x, GRAPH)
            assert self._field(ei) == field
        bad_dtype = np.empty(
            (1, GRAPH.input_size, GRAPH.input_size, GRAPH.in_channels),
            dtype=object,
        )
        with pytest.raises(PreflightError) as ei:
            check_request(bad_dtype, GRAPH)
        assert self._field(ei) == "dtype"

    def test_engine_rejection_carries_field_context(self):
        eng = _engine()
        rid = eng.submit(np.zeros((1, 8, 8, 1), np.float32))
        res = eng.results[rid]
        assert not res.ok
        assert res.error.context["field"] == "spatial"
