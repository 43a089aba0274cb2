"""Serving engine (DESIGN.md §14): pad-to-bucket bitwise parity, FIFO
admission/fairness, plan+jit cache accounting (hits/misses/evictions and
zero replans/retraces on a repeated wave), typed admission rejections that
never stall the queue, the host spans the engine and frontend record, the
batch-aware costing knobs, and the host-staging serving cost model."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.cycle_model import (
    HOST_BYTES_PER_CYCLE,
    host_staging_cycles,
    serve_stream_cycles,
)
from repro.net import runner
from repro.net.graph import lenet5
from repro.net.partition import (
    auto_partition,
    clear_partition_cache,
    partition_cache_info,
)
from repro.net.runner import (
    init_network_params,
    prepare_network_params,
    run_network,
)
from repro.net.serve import (
    Request,
    ServeConfig,
    ServingEngine,
    bucket_for,
    pad_to_bucket,
)
from repro.net.frontend import ServingFrontend
from repro.obs.trace import tracing
from repro.robust.errors import NumericError, PreflightError

KEY = jax.random.PRNGKey(0)
GRAPH = lenet5()
PARAMS = init_network_params(GRAPH, KEY)
CFG = ServeConfig(buckets=(1, 2, 4))


def _images(rows: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal(
        (rows, GRAPH.input_size, GRAPH.input_size, GRAPH.in_channels)
    ).astype(np.float32)


def _engine(**overrides) -> ServingEngine:
    cfg = ServeConfig(**{"buckets": (1, 2, 4), **overrides})
    return ServingEngine(GRAPH, PARAMS, cfg)


# ---------------------------------------------------------------------------
# bucketing helpers
# ---------------------------------------------------------------------------


class TestBucketing:
    def test_bucket_for_picks_smallest_fit(self):
        assert bucket_for(1, (1, 2, 4, 8)) == 1
        assert bucket_for(3, (1, 2, 4, 8)) == 4
        assert bucket_for(8, (1, 2, 4, 8)) == 8
        # unsorted config still resolves smallest-fit
        assert bucket_for(3, (8, 4, 2, 1)) == 4

    def test_bucket_for_overflow_is_typed(self):
        with pytest.raises(PreflightError):
            bucket_for(9, (1, 2, 4, 8))

    def test_pad_to_bucket_shapes(self):
        x = _images(3)
        padded = pad_to_bucket(x, 4)
        assert padded.shape[0] == 4
        assert np.array_equal(padded[:3], x)
        assert not padded[3:].any()
        assert pad_to_bucket(x, 3) is not None  # exact fit: unchanged
        assert np.array_equal(pad_to_bucket(x, 3), x)
        with pytest.raises(PreflightError):
            pad_to_bucket(x, 2)

    def test_config_rejects_bad_buckets(self):
        with pytest.raises(PreflightError):
            ServeConfig(buckets=(4, 2))
        with pytest.raises(PreflightError):
            ServeConfig(buckets=())


# ---------------------------------------------------------------------------
# pad-to-bucket bitwise parity
# ---------------------------------------------------------------------------


class TestPadParity:
    """The property the whole engine rests on: a padded batch's real rows
    are bit-identical to the unpadded run under the same bucket plan."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_padded_rows_bit_identical(self, dtype):
        rows, bucket = 3, 4
        x = _images(rows, seed=7)
        plan = auto_partition(GRAPH, batch=bucket, compute_dtype=dtype)
        prepared = prepare_network_params(plan, PARAMS)
        full, _ = run_network(
            jnp.asarray(pad_to_bucket(x, bucket)), prepared, plan=plan
        )
        part, _ = run_network(jnp.asarray(x), prepared, plan=plan)
        assert np.array_equal(np.asarray(full)[:rows], np.asarray(part))

    def test_neighbor_content_does_not_leak(self):
        """Row i's logits depend only on row i: swapping the *other* rows
        of the bucket leaves it bitwise unchanged."""
        bucket = 4
        a, b = _images(1, seed=1), _images(bucket - 1, seed=2)
        c = _images(bucket - 1, seed=3)
        plan = auto_partition(GRAPH, batch=bucket)
        prepared = prepare_network_params(plan, PARAMS)
        with_b, _ = run_network(
            jnp.asarray(np.concatenate([a, b])), prepared, plan=plan
        )
        with_c, _ = run_network(
            jnp.asarray(np.concatenate([a, c])), prepared, plan=plan
        )
        assert np.array_equal(np.asarray(with_b)[0], np.asarray(with_c)[0])

    def test_engine_matches_manual_padded_run(self):
        """The engine's packed bucket (two requests + zero pad) returns
        exactly the rows a hand-built padded ``run_network`` produces."""
        x1, x2 = _images(2, seed=4), _images(1, seed=5)
        eng = _engine()
        r1, r2 = eng.serve([x1, x2])
        assert r1.ok and r2.ok and r1.bucket == r2.bucket == 4
        plan = auto_partition(GRAPH, batch=4)
        prepared = prepare_network_params(plan, PARAMS)
        manual, _ = run_network(
            jnp.asarray(pad_to_bucket(np.concatenate([x1, x2]), 4)),
            prepared, plan=plan,
        )
        manual = np.asarray(manual)
        assert np.array_equal(r1.logits, manual[:2])
        assert np.array_equal(r2.logits, manual[2:3])


# ---------------------------------------------------------------------------
# admission order / fairness
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_results_in_submission_order(self):
        eng = _engine()
        sizes = [1, 4, 2, 1, 3]
        results = eng.serve([_images(r, seed=r) for r in sizes])
        assert [r.rows for r in results] == sizes
        assert [r.id for r in results] == sorted(r.id for r in results)
        assert all(r.ok for r in results)

    def test_large_request_not_starved(self):
        """A 4-row request at the head is dispatched in the first batch —
        FIFO packing never skips the head to fill with later singles."""
        eng = _engine()
        eng.submit_many([_images(4, seed=0)] + [_images(1, seed=i)
                                                for i in range(1, 5)])
        first = eng._form_batch()
        assert [r.rows for r in first] == [4]

    @given(st.lists(st.integers(min_value=1, max_value=4), min_size=1,
                    max_size=12))
    @settings(max_examples=25, deadline=None)
    def test_packing_properties(self, sizes):
        """FIFO packing invariants, checked without executing kernels:
        batches preserve admission order exactly, each batch fits the
        largest bucket, and every batch is the *greedy* prefix (the next
        request would not have fit)."""
        eng = _engine()
        for i, r in enumerate(sizes):
            eng.queue.append(
                Request(id=i, x=np.zeros((r, 1, 1, 1)), rows=r, enqueue_s=0.0)
            )
        limit = max(eng.config.buckets)
        seen = []
        while True:
            batch = eng._form_batch()
            if batch is None:
                break
            rows = sum(r.rows for r in batch)
            assert rows <= limit
            if eng.queue:  # greedy: the next head would overflow the bucket
                assert rows + eng.queue[0].rows > limit
            seen.extend(r.id for r in batch)
        assert seen == list(range(len(sizes)))


# ---------------------------------------------------------------------------
# rejection path
# ---------------------------------------------------------------------------


class TestRejection:
    def test_nonfinite_request_rejected_not_raised(self):
        eng = _engine()
        bad = _images(1)
        bad[0, 0, 0, 0] = np.nan
        rid = eng.submit(bad)
        res = eng.results[rid]
        assert not res.ok and isinstance(res.error, NumericError)
        assert not eng.queue  # never enqueued

    def test_bad_shape_and_oversize_rejected(self):
        eng = _engine()
        r1 = eng.results[eng.submit(np.zeros((1, 8, 8, 1), np.float32))]
        assert isinstance(r1.error, PreflightError)
        r2 = eng.results[eng.submit(_images(5))]  # > max bucket (4)
        assert isinstance(r2.error, PreflightError)
        assert eng.rejected == 2

    def test_rejection_does_not_stall_queue(self):
        eng = _engine()
        good1 = eng.submit(_images(1, seed=1))
        bad = _images(1)
        bad[0] = np.inf
        bad_id = eng.submit(bad)
        good2 = eng.submit(_images(1, seed=2))
        eng.drain()
        assert eng.results[good1].ok and eng.results[good2].ok
        assert not eng.results[bad_id].ok
        summary = eng.summary()
        assert summary["completed"] == 2 and summary["rejected"] == 1

    def test_queue_backpressure(self):
        eng = _engine(max_queue=1)
        eng.submit(_images(1))
        res = eng.results[eng.submit(_images(1))]
        assert isinstance(res.error, PreflightError)
        eng.drain()
        assert eng.results[0].ok


# ---------------------------------------------------------------------------
# plan + jit cache accounting
# ---------------------------------------------------------------------------


class TestPlanCache:
    def test_second_wave_zero_replans_zero_retraces(self):
        """The acceptance criterion: wave 2 of the same bucket mix performs
        zero partition replans and zero jit retraces, visible in
        ``partition_cache_info()`` and the engine counters."""
        clear_partition_cache()
        eng = _engine()
        # one serve call per size so each drains as its own bucket
        # (a single FIFO drain would coalesce them all into bucket 4)
        wave = [[_images(r, seed=r)] for r in (1, 2, 3)]

        for w in wave:
            eng.serve(w)
        part1 = partition_cache_info()
        traces1 = runner.jit_trace_count()
        misses1 = eng.cache_counters["misses"]
        assert misses1 == 3  # buckets 1, 2, 4 (3 rounds up)

        for w in wave:
            eng.serve([x.copy() for x in w])
        part2 = partition_cache_info()
        assert eng.cache_counters["misses"] == misses1  # zero replans
        assert eng.cache_counters["hits"] >= 3
        assert part2.misses == part1.misses
        assert runner.jit_trace_count() == traces1  # zero recompiles

    def test_second_engine_reuses_partition_and_jit_caches(self):
        """Plan reuse crosses engine instances: the memoized auto_partition
        returns the *same plan object*, so jax's executable cache hits on
        identical (plan, shape) keys."""
        eng1 = _engine()
        eng1.serve([_images(2, seed=0)])
        part = partition_cache_info()
        traces = runner.jit_trace_count()
        eng2 = _engine()
        eng2.serve([_images(2, seed=9)])
        assert partition_cache_info().hits == part.hits + 1
        assert partition_cache_info().misses == part.misses
        assert runner.jit_trace_count() == traces

    def test_eviction_counter(self):
        eng = _engine(plan_cache_size=1, buckets=(1, 2))
        eng.serve([_images(1, seed=0)])
        eng.serve([_images(2, seed=1)])  # evicts bucket-1 entry
        eng.serve([_images(1, seed=2)])  # evicts bucket-2 entry
        info = eng.cache_info()
        assert info["evictions"] == 2
        assert info["currsize"] == 1
        assert info["misses"] == 3

    def test_partition_cache_info_has_eviction_field(self):
        clear_partition_cache()
        info = partition_cache_info()
        assert info.evictions == 0
        auto_partition(GRAPH)
        assert partition_cache_info().evictions == 0  # plenty of room
        clear_partition_cache()
        assert partition_cache_info() == partition_cache_info()._replace(
            hits=0, misses=0, evictions=0, currsize=0
        )


class TestJitRetrace:
    def test_distinct_batch_sizes_retrace_same_plan(self):
        """The failure mode bucketing amortizes: one plan, two batch sizes,
        two jit traces — then replaying either shape adds none."""
        plan = auto_partition(GRAPH, batch=1)
        prepared = prepare_network_params(plan, PARAMS)
        runner.reset_jit_trace_count()
        for rows in (3, 5, 3, 5):
            out, _ = run_network(
                jnp.asarray(_images(rows)), prepared, plan=plan
            )
            jax.block_until_ready(out)
        assert runner.jit_trace_count() == 2
        runner.reset_jit_trace_count()
        out, _ = run_network(jnp.asarray(_images(3)), prepared, plan=plan)
        jax.block_until_ready(out)
        assert runner.jit_trace_count() == 0  # reset counts, cache survives


# ---------------------------------------------------------------------------
# SLO / summary / renderer
# ---------------------------------------------------------------------------


class TestSummary:
    def test_bucket_rows_publish_slo_and_measured(self):
        eng = _engine()
        eng.serve([_images(r, seed=r) for r in (1, 2, 4)])
        summary = eng.summary()
        assert summary["model"] == "lenet"
        assert summary["buckets"], "no bucket rows"
        for row in summary["buckets"]:
            assert row["slo_us"] > 0
            assert row["steady_us"] > 0
            assert row["steady_us"] <= row["slo_us"]
            assert row["p50_ms"] > 0 and row["p95_ms"] >= row["p50_ms"]
            assert row["imgs_per_s"] > 0
            assert row["modeled_cycles"] > 0
        assert summary["cache"]["serve"]["misses"] == len(summary["buckets"])

    def test_slo_scales_with_bucket(self):
        """A bigger bucket models strictly more work: SLO is monotone in
        bucket for the same model/dtype."""
        eng = _engine()
        e1, e4 = eng._entry(1), eng._entry(4)
        assert e4.compute_cycles > e1.compute_cycles
        assert e4.staging_cycles > e1.staging_cycles
        assert e4.slo_us > e1.slo_us

    def test_serve_table_renders(self):
        from repro.obs.explain import serve_table

        eng = _engine()
        eng.serve([_images(2, seed=0)])
        summary = eng.summary()
        summary["waves"] = [
            {"serve_hits": 0, "serve_misses": 1, "partition_hits": 0,
             "partition_misses": 1, "jit_traces": 1, "wall_s": 0.5},
        ]
        lines = []
        serve_table(summary, out=lines.append)
        text = "\n".join(lines)
        assert "slo_us" in text and "p50_ms" in text
        assert "wave 1" in text and "jit traces" in text

    def test_guarded_engine_completes(self):
        eng = _engine(guarded=True)
        res = eng.serve([_images(1, seed=3)])
        assert all(r.ok for r in res)
        # guarded (launch-by-launch) and unguarded (whole-graph jit) paths
        # agree to the runner's documented f32 closeness — XLA fuses the
        # two graphs differently, so bitwise equality is not the contract
        ref = _engine().serve([_images(1, seed=3)])
        np.testing.assert_allclose(
            res[0].logits, ref[0].logits, atol=1e-4
        )


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

BATCH_SPANS = ("engine.stage", "engine.dispatch", "engine.block",
               "engine.record")


class TestHostSpans:
    def test_frontend_run_spans_every_batch_and_request(self):
        """Per batch one each of stage/dispatch/block/record under one
        batch id, whose requests are the admit spans linked to it; per
        request one engine.admit and one frontend.deliver with its id."""
        eng = _engine(buckets=(4,))
        eng.serve([_images(1, seed=0)])  # compiles outside the collection
        with tracing() as col:
            with ServingFrontend(eng) as fe:
                handles = [fe.submit(_images(1, seed=s)) for s in range(10)]
                results = [h.result(timeout=60.0) for h in handles]
        assert all(r.ok for r in results)
        spans = col.spans_between()
        by_name: dict = {}
        for sp in spans:
            by_name.setdefault(sp.name, []).append(sp)
        ids = sorted(h.id for h in handles)
        for name in ("engine.admit", "frontend.deliver"):
            assert sorted(sp.id for sp in by_name[name]) == ids, name
        batches = sorted({sp.id for sp in by_name["engine.stage"]})
        for name in BATCH_SPANS:
            assert sorted(sp.id for sp in by_name[name]) == batches, name
        assert {sp.route for sp in by_name["engine.dispatch"]} == {"fused"}
        members: dict = {}
        for sp in by_name["engine.admit"]:
            members.setdefault(sp.parent, []).append(sp.id)
        assert sorted(members) == batches
        for st in by_name["engine.stage"]:
            assert st.rows == len(members[st.id]) and st.bucket == 4
            # a request's queue wait ends where its batch's stage starts
            admits = [a for a in by_name["engine.admit"] if a.parent == st.id]
            assert all(a.end_s <= st.start_s for a in admits)
        for sp in spans:
            assert sp.end_s >= sp.start_s
        deliver = {sp.id: sp for sp in by_name["frontend.deliver"]}
        record = {sp.id: sp for sp in by_name["engine.record"]}
        admit = {sp.id: sp for sp in by_name["engine.admit"]}
        for rid in ids:
            # the hand-back starts inside its batch's record span
            rec = record[admit[rid].parent]
            assert rec.start_s <= deliver[rid].start_s <= rec.end_s
        assert "frontend.idle" in by_name

    def test_rejected_request_has_an_admit_span_and_no_batch(self):
        eng = _engine()
        with tracing() as col:
            rid = eng.submit(np.zeros((1, 8, 8, 1), np.float32))
        (sp,) = col.spans_between()
        assert (sp.name, sp.id, sp.parent) == ("engine.admit", rid, -1)

    def test_spans_go_to_the_default_recorder(self):
        from repro.obs.trace import get_tracer

        eng = _engine()
        t0 = time.perf_counter()
        eng.serve([_images(2, seed=4)])
        names = {sp.name for sp in get_tracer().spans_between(t0)}
        assert {"engine.admit", *BATCH_SPANS} <= names


# ---------------------------------------------------------------------------
# two forwards in flight: dispatch n+1 before blocking on n
# ---------------------------------------------------------------------------


def _by_batch(spans, name: str) -> dict:
    return {sp.id: sp for sp in spans if sp.name == name}


class TestDispatchAhead:
    @pytest.mark.parametrize("overrides, requests, ahead", [
        ({}, 3, 2),  # default config, a next batch queued: two in flight
        ({}, 1, 0),  # one request: never a batch n+1 to dispatch ahead
        ({"breaker_threshold": 3}, 3, 0),  # n's outcome may reroute n+1
        ({"guarded": True}, 3, 0),  # the ladder runs eagerly on the host
    ], ids=["default", "one-request", "breaker", "guarded"])
    def test_dispatch_order(self, overrides, requests, ahead):
        """Where a next batch is queued and nothing needs n's outcome
        first, batch n+1's dispatch starts before batch n's block; a
        breaker, the guarded ladder or an empty queue keep depth 1, where
        each dispatch starts after the previous block ended."""
        eng = _engine(**overrides)
        eng.serve([_images(4, seed=9)])  # compiles outside the collection
        before = dict(eng.summary()["dispatch"])
        with tracing() as col:
            t0 = time.perf_counter()
            eng.submit_many([_images(4, seed=s) for s in range(requests)])
            done = eng.drain()
            elapsed_ms = (time.perf_counter() - t0) * 1e3
        assert [r.id for r in done] == sorted(r.id for r in done)
        assert all(r.ok for r in done) and len(done) == requests
        assert col.counters.get("serve.batches", 0) == requests
        assert col.counters.get("serve.dispatched_ahead", 0) == ahead
        after = eng.summary()["dispatch"]
        assert after["batches"] - before["batches"] == requests
        assert (after["dispatched_ahead"] - before["dispatched_ahead"]
                == ahead)
        spans = col.spans_between()
        dispatch = _by_batch(spans, "engine.dispatch")
        block = _by_batch(spans, "engine.block")
        record = _by_batch(spans, "engine.record")
        bids = sorted(dispatch)
        assert len(bids) == requests
        for n, m in zip(bids, bids[1:]):
            if ahead:
                assert dispatch[m].start_s < block[n].start_s
            else:
                assert dispatch[m].start_s >= block[n].end_s
            assert record[n].end_s <= record[m].start_s  # batch order
        # each wall is the batch's own service time: the walls of
        # consecutive batches never overlap, so they fit in the drain
        walls = eng._stats[4].batch_walls_ms[-requests:]
        assert sum(walls) <= elapsed_ms

    def test_logits_bitwise_equal_to_each_request_alone(self):
        """Pipelined batches run the same executable on the same inputs:
        every request's logits equal serving it alone, bit for bit, and
        the drain returns them in completion (= batch) order."""
        xs = [_images(4, seed=20 + s) for s in range(4)]
        alone = [_engine().serve([x])[0] for x in xs]
        eng = _engine()
        ids = eng.submit_many(xs)
        done = eng.drain()
        assert [r.id for r in done] == ids
        assert eng.summary()["dispatch"]["dispatched_ahead"] == len(xs) - 1
        for r, a in zip(done, alone):
            assert r.ok and r.bucket == a.bucket == 4
            assert np.array_equal(r.logits, a.logits)

    @pytest.mark.parametrize("typed", [True, False], ids=["typed", "untyped"])
    def test_dispatch_failure_lands_after_the_batch_in_flight(
            self, monkeypatch, typed):
        """An error from batch n+1's dispatch while n is in flight: n is
        still blocked on and recorded ok first.  A RobustError then fails
        n+1 typed, the order holds and the drain returns with nothing in
        flight; any other error propagates once n has landed."""
        from repro.robust.errors import PlanError

        eng = _engine()
        eng.serve([_images(4, seed=9)])
        run_route = eng._run_route
        calls = []

        def failing_second(route, entry, x_dev):
            calls.append(route)
            if len(calls) == 2:
                raise (PlanError if typed else RuntimeError)("dispatch failed")
            return run_route(route, entry, x_dev)

        monkeypatch.setattr(eng, "_run_route", failing_second)
        order = []
        eng.add_listener(lambda res: order.append(res.id))
        ids = eng.submit_many([_images(4, seed=s) for s in range(3)])
        if not typed:
            with pytest.raises(RuntimeError, match="dispatch failed"):
                eng.drain()
            assert order == ids[:1] and eng.results[ids[0]].ok
            return
        with tracing() as col:
            done = eng.drain()
        assert order == ids and [r.id for r in done] == ids
        assert [r.ok for r in done] == [True, False, True]
        assert isinstance(done[1].error, PlanError)
        assert done[1].bucket == 4 and eng.resilience["failed"] == 1
        # batch 1 was dispatched ahead of batch 0; batch 2 followed a
        # failed launch, so nothing was in flight when it was dispatched
        assert col.counters["serve.dispatched_ahead"] == 1
        assert not eng.queue
        assert all(eng.results[i] is r for i, r in zip(ids, done))


# ---------------------------------------------------------------------------
# batch-aware costing + serving cost model
# ---------------------------------------------------------------------------


class TestBatchAwareCosting:
    def test_plan_launch_accepts_batch(self):
        from repro.core.cnn_models import LENET5_FUSION
        from repro.core.program import plan_launch

        p1 = plan_launch(LENET5_FUSION)
        p8 = plan_launch(LENET5_FUSION, batch=8)
        # the ladder is cost-monotone in batch: same rung either way
        assert p1.regime == p8.regime
        assert p8.modeled_cycles(8) == 8 * p8.modeled_cycles(1)

    def test_modeled_us_matches_cycles(self):
        from repro.core.cycle_model import DEFAULT_PARAMS

        plan = auto_partition(GRAPH, batch=4)
        lp = plan.pyramids[0].launch
        assert lp.modeled_us(4) == pytest.approx(
            lp.modeled_cycles(4) / DEFAULT_PARAMS.freq_mhz
        )
        assert plan.modeled_us() == pytest.approx(
            plan.modeled_cycles() / DEFAULT_PARAMS.freq_mhz
        )

    def test_partition_shifts_with_batch(self):
        """The reason batch-aware costing matters: streamed re-reads scale
        with batch while resident loads amortize, so the resnet18 cut
        points differ between batch 1 and batch 8."""
        from repro.net.graph import resnet18

        g = resnet18()
        p1 = auto_partition(g, batch=1)
        p8 = auto_partition(g, batch=8)
        assert [p.launch.regime for p in p1.pyramids] != [
            p.launch.regime for p in p8.pyramids
        ]


class TestServeCycleModel:
    def test_host_staging_cycles_ceil(self):
        assert host_staging_cycles(0) == 0
        assert host_staging_cycles(1) == 1
        assert host_staging_cycles(HOST_BYTES_PER_CYCLE) == 1
        assert host_staging_cycles(HOST_BYTES_PER_CYCLE + 1) == 2

    def test_serve_stream_cycles_shapes(self):
        c, s = 100, 30
        assert serve_stream_cycles(0, c, s, double_buffered=True) == 0
        assert serve_stream_cycles(1, c, s, double_buffered=True) == c + s
        # serial pays staging+compute per batch
        assert serve_stream_cycles(3, c, s, double_buffered=False) == 3 * (c + s)
        # double-buffered hides staging behind compute after the first
        assert serve_stream_cycles(3, c, s, double_buffered=True) == (
            s + c + 2 * max(c, s)
        )

    @given(st.integers(1, 32), st.integers(1, 10**6), st.integers(1, 10**6))
    @settings(max_examples=50, deadline=None)
    def test_double_buffering_never_worse(self, batches, compute, staging):
        db = serve_stream_cycles(
            batches, compute, staging, double_buffered=True
        )
        serial = serve_stream_cycles(
            batches, compute, staging, double_buffered=False
        )
        assert db <= serial
        # and never better than the compute/staging lower bounds
        assert db >= batches * max(compute, staging)
