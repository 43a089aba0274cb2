"""Observability subsystem (DESIGN.md §12): the always-on recorder (host
span ring, wrap, window reads), a tracer never switching the forward path,
the per-launch timed run's span schema, END-skip count events vs reference dead tiles,
timeline/cycle-model consistency, Chrome-trace export across the zoo, the
drift report, partition-cache counters, and the benchmark satellites
(p50/p95 stats, regression diff table)."""

import json
import pathlib
import sys
import threading

import jax
import numpy as np
import pytest

from repro.core.cnn_models import LENET5_FUSION, VGG_FUSION, resnet18_fusions
from repro.core.cycle_model import timeline_end
from repro.core.program import plan_launch
from repro.net.graph import MODELS, lenet5
from repro.net.partition import (
    CHAINED_CONV_MACS,
    CONV_MACS,
    auto_partition,
    clear_partition_cache,
    conv_macs,
    partition_cache_info,
)
from repro.net import runner
from repro.net.runner import (
    init_network_params,
    jit_trace_count,
    prepare_network_params,
    run_network,
    run_network_per_launch,
)
from repro.obs.report import (
    drift_report,
    drift_rows_from_bench,
    drift_rows_from_spans,
)
from repro.obs.timeline import chrome_trace, validate_chrome_trace
from repro.obs.trace import (
    DEFAULT_TRACER,
    EVENT_CAPACITY,
    NULL_TRACER,
    SPAN_CAPACITY,
    TraceCollector,
    get_tracer,
    set_tracer,
    span_code,
    tracing,
)

from test_pyramid_kernel import _expected_skip_maps

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    # benchmarks/ is a namespace package at the repo root (run via
    # ``python -m benchmarks.run``); make it importable for the satellites
    sys.path.insert(0, str(REPO))

KEY = jax.random.PRNGKey(0)


def _traced_lenet(batch=2, reps=1, bias_shift=0.0, sparse=False):
    """One launch-by-launch timed LeNet forward (plus optional extra reps)
    returning (collector, plan, skips, raw_params, x)."""
    import jax.numpy as jnp

    graph = lenet5()
    raw = init_network_params(graph, KEY)
    if bias_shift:
        raw = {k: (w, b + bias_shift) for k, (w, b) in raw.items()}
    if sparse:
        blob = graph.input_size // 3
        x = jnp.zeros((batch, graph.input_size, graph.input_size, 1))
        x = x.at[:, :blob, :blob, :].set(5.0)
    else:
        x = jax.random.normal(
            jax.random.PRNGKey(1),
            (batch, graph.input_size, graph.input_size, 1),
        )
    plan = auto_partition(graph, batch=batch)
    params = prepare_network_params(plan, raw)
    collector = TraceCollector()
    for _ in range(reps):
        _, skips = run_network_per_launch(
            x, params, plan=plan, collector=collector
        )
    return collector, plan, skips, raw, x


def _lenet_inputs(batch):
    graph = lenet5()
    raw = init_network_params(graph, KEY)
    plan = auto_partition(graph, batch=batch)
    params = prepare_network_params(plan, raw)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 32, 32, 1))
    return x, params, plan


class TestTracerDispatch:
    def test_default_tracer_is_noop(self):
        """The default tracer is the always-on bounded recorder; recording
        is turned off by installing NULL_TRACER, which keeps nothing."""
        t = get_tracer()
        assert t is DEFAULT_TRACER and t.enabled
        assert t.capacity == SPAN_CAPACITY
        assert t.events.maxlen == EVENT_CAPACITY
        set_tracer(NULL_TRACER)
        try:
            off = get_tracer()
            assert not off.enabled
            assert off.span(span_code("engine.stage"), 1.0, 2.0, 7) == -1
            assert off.spans_between() == [] and not off.holds(0.0)
        finally:
            set_tracer(None)
        assert get_tracer() is DEFAULT_TRACER

    def test_disabled_tracing_uses_unchanged_jit_path(self, monkeypatch):
        """With recording off, on (the default) or scoped by tracing(), the
        public run_network hits the jit fast path without touching the
        launch-by-launch timed implementation."""

        def boom(*a, **k):
            raise AssertionError("per-launch path must not run")

        monkeypatch.setattr(runner, "run_network_per_launch", boom)
        x, params, plan = _lenet_inputs(1)
        logits, _ = run_network(x, params, plan=plan)
        assert logits.shape == (1, 10)
        with tracing():
            logits, _ = run_network(x, params, plan=plan)
        set_tracer(NULL_TRACER)
        try:
            logits, _ = run_network(x, params, plan=plan)
        finally:
            set_tracer(None)
        assert logits.shape == (1, 10)

    def test_traced_path_matches_jit_path(self):
        """The launch-by-launch timed forward changes scheduling, never
        numerics: same logits as the jit forward."""
        x, params, plan = _lenet_inputs(2)
        fast, _ = run_network(x, params, plan=plan)
        timed, _ = run_network_per_launch(
            x, params, plan=plan, collector=TraceCollector()
        )
        np.testing.assert_allclose(
            np.asarray(fast), np.asarray(timed), atol=1e-6
        )

    def test_tracing_keeps_the_jit_forward(self):
        """A tracer installed with tracing() leaves run_network on the jit
        path: no new trace of the compiled forward, identical logits, and
        no launch spans recorded."""
        x, params, plan = _lenet_inputs(2)
        fast, _ = run_network(x, params, plan=plan)
        traces = jit_trace_count()
        with tracing() as col:
            traced, _ = run_network(x, params, plan=plan)
        assert jit_trace_count() == traces
        assert col.spans == []
        np.testing.assert_array_equal(np.asarray(fast), np.asarray(traced))

    def test_tracing_context_restores_previous(self):
        with tracing() as outer:
            with tracing() as inner:
                assert get_tracer() is inner
            assert get_tracer() is outer
        assert get_tracer() is DEFAULT_TRACER


class TestHostSpanRing:
    CODE = span_code("test.span")

    def test_spans_read_back_in_start_order(self):
        col = TraceCollector(capacity=8)
        route = span_code("fused")
        col.span(self.CODE, 2.0, 3.0, 5, 9, bucket=8, rows=7, route=route)
        col.span(self.CODE, 1.0, 4.0, 6)
        spans = col.spans_between()
        assert [s.start_s for s in spans] == [1.0, 2.0]
        a = spans[1]
        assert (a.name, a.end_s, a.id, a.parent) == ("test.span", 3.0, 5, 9)
        assert (a.bucket, a.rows, a.route) == (8, 7, "fused")
        assert spans[0].parent == -1 and spans[0].route == ""
        assert a.thread == threading.get_ident()
        assert tuple(a[:3]) == ("test.span", 2.0, 3.0)
        assert [s.id for s in col.spans_between(1.5, 3.0)] == [5]

    def test_link_sets_parent_only_while_the_slot_holds_the_id(self):
        col = TraceCollector(capacity=2)
        slot = col.span(self.CODE, 1.0, 2.0, 11)
        col.link(slot, 11, 42)
        assert col.spans_between()[0].parent == 42
        col.span(self.CODE, 3.0, 4.0, 12)
        col.span(self.CODE, 5.0, 6.0, 13)  # overwrites the slot of id 11
        col.link(slot, 11, 99)
        assert [s.parent for s in col.spans_between()] == [-1, -1]

    def test_ring_wraps_at_capacity(self):
        """The ring keeps the newest ``capacity`` spans in fixed memory,
        and reports that it no longer holds a start time that was lost."""
        col = TraceCollector(capacity=16)
        before = {k: (c.nbytes, c.ctypes.data) for k, c in col._cols.items()}
        for i in range(16):
            col.span(self.CODE, float(i), i + 0.5, i)
        assert col.holds(0.0)  # full but never wrapped
        for i in range(16, 100):
            col.span(self.CODE, float(i), i + 0.5, i)
        kept = col.spans_between()
        assert len(kept) <= 16 and kept[-1].id == 99
        assert [s.id for s in kept] == list(range(kept[0].id, 100))
        assert not col.holds(0.0)
        assert col.holds(float(kept[0].start_s) + 1.0)
        after = {k: (c.nbytes, c.ctypes.data) for k, c in col._cols.items()}
        assert after == before

    def test_capacity_must_be_a_power_of_two(self):
        with pytest.raises(ValueError):
            TraceCollector(capacity=12)

    def test_concurrent_writers_lose_no_span(self):
        """Slots come from one itertools.count: threads writing at once
        never share a slot."""
        col = TraceCollector(capacity=1 << 12)
        switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            def write(base):
                for i in range(500):
                    col.span(self.CODE, float(i), i + 1.0, base + i)

            threads = [threading.Thread(target=write, args=(k * 1000,))
                       for k in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(switch)
        ids = sorted(s.id for s in col.spans_between())
        assert ids == sorted(k * 1000 + i for k in range(6) for i in range(500))


class TestTracedSpans:
    def test_spans_have_modeled_and_measured_fields(self):
        collector, plan, _, _, _ = _traced_lenet(batch=2, reps=2)
        assert len(collector.spans) == 2 * plan.n_launches()
        for s in collector.spans:
            assert s.model == "lenet" and s.name
            assert s.regime and s.compute_dtype == "float32"
            assert s.hbm_bytes > 0 and s.vmem_bytes > 0
            assert s.modeled_cycles > 0 and s.modeled_us > 0
            assert s.duration_ms > 0 and s.start_s > 0
            assert s.batch == 2 and s.alpha > 0 and s.q_convs > 0

    def test_run_network_summary_event(self):
        collector, plan, _, _, _ = _traced_lenet(batch=1, reps=1)
        summaries = [e for e in collector.events if e.name == "run_network"]
        assert len(summaries) == 1
        args = summaries[0].args
        assert args["launches"] == plan.n_launches()
        assert args["wallclock_ms"] > 0
        assert args["modeled_cycles"] == plan.modeled_cycles()


class TestEndSkipEvents:
    def test_skip_counts_match_reference_dead_tiles(self):
        """End-to-end satellite: the runner's per-level END-skip counts must
        equal the reference count of post-ReLU all-zero tiles, per batch
        element, on a seeded sparse input with mixed live/dead tiles.

        LeNet's auto plan covers the whole 5x5 output in one movement
        (alpha=1), so the pyramid is re-planned at out_region=1 — a 5x5
        movement grid whose border tiles go dead under the sparse blob."""
        import dataclasses

        import jax.numpy as jnp

        graph = lenet5()
        raw = init_network_params(graph, KEY)
        raw = {k: (w, b - 0.5) for k, (w, b) in raw.items()}
        blob = graph.input_size // 3
        x = jnp.zeros((2, graph.input_size, graph.input_size, 1))
        x = x.at[:, :blob, :blob, :].set(5.0)
        plan = auto_partition(graph, batch=2)
        assert len(plan.pyramids) == 1  # LeNet fuses its whole conv trunk
        pyr = dataclasses.replace(
            plan.pyramids[0],
            launch=plan_launch(
                plan.pyramids[0].spec, prefer_region="smallest"
            ),
        )
        assert pyr.launch.out_region == 1
        plan = dataclasses.replace(plan, pyramids=(pyr,))
        params = prepare_network_params(plan, raw)
        collector = TraceCollector()
        _, skips = run_network_per_launch(
            x, params, plan=plan, collector=collector
        )
        conv_names = [
            m for m in pyr.node_names if plan.graph.node(m).op == "conv"
        ]
        weights = [np.asarray(raw[m][0]) for m in conv_names]
        biases = [np.asarray(raw[m][1]) for m in conv_names]
        got = np.asarray(skips[pyr.name])
        expected = np.stack(
            [
                _expected_skip_maps(
                    pyr.spec, weights, biases, x[b : b + 1],
                    pyr.launch.out_region,
                )[0]
                for b in range(x.shape[0])
            ]
        )
        np.testing.assert_array_equal(got, expected)
        assert 0 < expected[..., 1].sum() < expected[..., 1].size, (
            "test needs mixed live/dead tiles to be meaningful"
        )
        # and the traced event aggregates the same counts
        evs = [e for e in collector.events if e.name == "end_skip_counts"]
        assert len(evs) == 1 and evs[0].args["launch"] == pyr.name
        assert evs[0].args["per_level"] == [
            int(c) for c in expected.sum(axis=(0, 1, 2))
        ]
        assert evs[0].args["cells"] == expected[..., 0].size


class TestTimelines:
    SPECS = {
        "lenet_q2": LENET5_FUSION,
        "vgg_q4": VGG_FUSION,
        "resnet18_b7": resnet18_fusions()[7],
    }

    @pytest.mark.parametrize("name", sorted(SPECS))
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_timeline_end_equals_modeled_cycles(self, name, dtype):
        """The exported timeline is a *twin* of the cycle model: its last
        bar ends exactly at modeled_cycles (and the per-cell detail at
        body_cycles), at any elision level."""
        import dataclasses

        lp = plan_launch(self.SPECS[name], compute_dtype=dtype)
        for launch in (lp, dataclasses.replace(lp, x_slots=1, w_slots=1)):
            assert timeline_end(
                launch.modeled_timeline()
            ) == launch.modeled_cycles()
            assert timeline_end(
                launch.modeled_timeline(max_cells=4)
            ) == launch.modeled_cycles()
            detail = launch.body_detail_timeline()
            assert timeline_end(detail) == launch.body_cycles()
            for seg in launch.modeled_timeline():
                assert seg.lane in ("mxu", "dma")
                assert seg.start >= 0 and seg.duration >= 0


class TestChromeTrace:
    @pytest.mark.parametrize("model", sorted(MODELS))
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_zoo_modeled_trace_validates(self, model, dtype):
        """Acceptance: a Perfetto-loadable trace for every zoo model at
        both compute dtypes (modeled tracks are analytic — no kernels)."""
        plan = auto_partition(MODELS[model](), compute_dtype=dtype)
        trace = chrome_trace(
            launches=[(p.name, p.launch) for p in plan.pyramids]
        )
        assert validate_chrome_trace(trace) == []
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert len(xs) > 0
        assert all(e["cat"] in ("modeled", "modeled-detail") for e in xs)

    def test_measured_trace_round_trips(self, tmp_path):
        from repro.obs.timeline import write_chrome_trace

        collector, plan, _, _, _ = _traced_lenet(batch=1, reps=1)
        trace = chrome_trace(
            collector, launches=[(p.name, p.launch) for p in plan.pyramids]
        )
        assert validate_chrome_trace(trace) == []
        cats = {e.get("cat") for e in trace["traceEvents"]}
        assert {"modeled", "measured", "event"} <= cats
        path = tmp_path / "trace.json"
        write_chrome_trace(str(path), trace)
        assert validate_chrome_trace(json.loads(path.read_text())) == []

    def test_host_spans_render_one_track_per_thread(self, tmp_path):
        """A live recorder's host spans export as Perfetto tracks, one per
        thread, named after the thread."""
        from repro.obs.timeline import HOST_PID, write_chrome_trace

        col = TraceCollector(capacity=64)
        code = span_code("engine.stage")
        col.span(code, 1.0, 1.5, 3, bucket=8, rows=8)
        worker = threading.Thread(
            target=lambda: col.span(code, 1.2, 1.4, 4), name="serve-drain"
        )
        worker.start()
        worker.join(timeout=10)
        trace = chrome_trace(col)
        assert validate_chrome_trace(trace) == []
        host = [e for e in trace["traceEvents"] if e.get("cat") == "host"]
        assert [e["args"]["id"] for e in host] == [3, 4]
        assert host[0]["ts"] == 0.0 and host[0]["dur"] == pytest.approx(5e5)
        assert {e["pid"] for e in host} == {HOST_PID}
        assert host[0]["tid"] != host[1]["tid"]
        names = {e["args"]["name"] for e in trace["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert threading.current_thread().name in names
        path = tmp_path / "live.json"
        write_chrome_trace(str(path), trace)
        assert validate_chrome_trace(json.loads(path.read_text())) == []

    def test_validator_rejects_malformed(self):
        assert validate_chrome_trace({"traceEvents": [{"ph": "Z"}]})
        assert validate_chrome_trace({"traceEvents": "nope"})
        bad_span = {"ph": "X", "name": "s", "pid": 1, "tid": 0, "ts": -1,
                    "dur": 1}
        assert validate_chrome_trace({"traceEvents": [bad_span]})


class TestDriftReport:
    def test_rows_from_traced_spans(self):
        collector, plan, _, _, _ = _traced_lenet(batch=1, reps=3)
        rows = drift_rows_from_spans(collector.spans)
        assert len(rows) == plan.n_launches()  # reps collapse to medians
        for r in rows:
            assert r["reps"] == 3
            assert r["modeled_ms"] > 0 and r["measured_ms"] > 0
        rep = drift_report(rows)
        assert rep["median_ratio"] > 0
        assert all("drift" in r and "flagged" in r for r in rep["rows"])

    def test_committed_bench_file_joins(self):
        """Acceptance: the drift report runs on BENCH_pyramid.json data."""
        with open(REPO / "BENCH_pyramid.json") as f:
            bench = json.load(f)
        rows = drift_rows_from_bench(bench)
        assert len(rows) >= 1
        rep = drift_report(rows)
        assert rep["median_ratio"] > 0

    def test_outlier_is_flagged(self):
        def row(name, measured):
            return {
                "launch": name, "regime": "resident",
                "compute_dtype": "float32", "batch": 1, "reps": 3,
                "modeled_cycles": 1000, "modeled_ms": 0.01,
                "measured_ms": measured,
            }

        rows = [row("a", 1.0), row("b", 1.1), row("c", 0.9),
                row("d", 50.0)]
        rep = drift_report(rows, flag_factor=3.0)
        assert rep["flagged"] == ["d"]

    def test_old_bench_files_skip_gracefully(self):
        """Workload rows without modeled_cycles (pre-PR-7 files) are
        skipped, not crashed on."""
        bench = {"workloads": {"old": {"wallclock_ms": 1.0}}}
        assert drift_rows_from_bench(bench) == []


class TestPartitionCacheCounters:
    def test_counters_track_hits_and_reset_on_clear(self):
        clear_partition_cache()
        info = partition_cache_info()
        assert info.hits == 0 and info.misses == 0
        g = lenet5()
        p1 = auto_partition(g, batch=3)
        p2 = auto_partition(g, batch=3)
        assert p1 is p2  # cached plan object
        info = partition_cache_info()
        assert info.misses >= 1 and info.hits >= 1
        assert info.currsize >= 1
        clear_partition_cache()
        info = partition_cache_info()
        assert info.hits == 0 and info.misses == 0 and info.currsize == 0

    def test_cache_events_traced(self):
        clear_partition_cache()
        g = lenet5()
        with tracing() as collector:
            auto_partition(g, batch=3)
            auto_partition(g, batch=3)
        evs = [e for e in collector.events if e.name == "auto_partition"]
        assert [e.args["cache"] for e in evs] == ["miss", "hit"]
        assert all(e.args["model"] == "lenet" for e in evs)


class TestExplainCLI:
    def test_table_and_trace_for_lenet(self, tmp_path, capsys):
        from repro.obs.explain import main

        out = tmp_path / "t.json"
        assert main(["--model", "lenet", "--trace", str(out)]) == 0
        text = capsys.readouterr().out
        assert "regime" in text and "partition cache" in text
        assert validate_chrome_trace(json.loads(out.read_text())) == []

    def test_conv_mac_counters_and_activations(self, capsys):
        """Plan build bumps ``fused.conv_macs`` by every planned conv
        level's per-image MACs and ``fused.chained_conv_macs`` by those in
        multi-conv pyramids; the table names each level's activation."""
        from repro.obs.explain import main

        clear_partition_cache()
        with tracing() as collector:
            plan = auto_partition(MODELS["resnet18"](), batch=1)
        single = sum(conv_macs(p.spec) for p in plan.pyramids if p.q_convs == 1)
        total = collector.counters[CONV_MACS]
        assert total == 1_813_561_344  # ResNet-18's convs at 224x224
        assert collector.counters[CHAINED_CONV_MACS] == total - single
        clear_partition_cache()
        argv = ["--model", "resnet50", "--dtype", "bfloat16", "--batch", "8"]
        assert main(argv) == 0
        text = capsys.readouterr().out
        assert text.count("relu relu linear") == 16
        assert "fused.conv_macs +4,087,136,256" in text
        assert "fused.chained_conv_macs +3,609,460,736 (88.3% chained)" in text

    @pytest.mark.parametrize("model", sorted(MODELS))
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_zoo_tables_render(self, model, dtype, capsys):
        """Acceptance: the plan table renders for every zoo model at both
        dtypes (analytic — no --run)."""
        from repro.obs.explain import main

        assert main(["--model", model, "--dtype", dtype]) == 0
        text = capsys.readouterr().out
        assert "total:" in text and "launches" in text


class TestBenchmarkSatellites:
    def test_timed_stats_keys_and_ordering(self):
        from benchmarks.run import _percentile_ms, _timed_stats_ms

        stats = _timed_stats_ms(lambda: None, reps=7)
        assert set(stats) == {"p50_ms", "p95_ms", "reps"}
        assert stats["reps"] == 7
        assert 0 <= stats["p50_ms"] <= stats["p95_ms"]
        assert _percentile_ms([1.0, 2.0, 3.0, 4.0], 50.0) == 2.5
        assert _percentile_ms([1.0, 2.0, 3.0, 4.0], 100.0) == 4.0
        assert _percentile_ms([5.0], 95.0) == 5.0

    @staticmethod
    def _mini_bench(hbm=100.0, cycles=50.0):
        return {
            "kernel_dataflow": {
                "launches": {
                    "w1": {
                        "hbm_bytes_total": hbm,
                        "modeled_cycles": cycles,
                        "input_bytes_halo": 10,
                        "slice_bytes": 0,
                    }
                }
            },
            "partition": {
                "m": {
                    "auto": {"hbm_bytes": 1000, "modeled_latency_us": 5.0},
                    "auto_bf16": {"hbm_bytes": 500,
                                  "modeled_latency_us": 3.0},
                }
            },
        }

    def test_diff_table_statuses(self):
        from benchmarks.check_regression import compare, diff_table

        base = self._mini_bench()
        cur = self._mini_bench(hbm=200.0, cycles=40.0)
        rows = {r["metric"]: r for r in diff_table(cur, base, 0.10)}
        assert len(rows) == 8  # every gated metric gets a row
        assert rows["kernel_dataflow/w1/hbm_bytes_total"]["status"] == "FAIL"
        assert rows["kernel_dataflow/w1/modeled_cycles"]["status"] == (
            "improved"
        )
        assert rows["partition/m/auto/hbm_bytes"]["status"] == "ok"
        assert rows["kernel_dataflow/w1/hbm_bytes_total"]["threshold"] == (
            pytest.approx(110.0)
        )
        assert len(compare(cur, base, 0.10)) == 1

    def test_diff_table_missing_metric(self):
        from benchmarks.check_regression import compare, diff_table

        base = self._mini_bench()
        cur = self._mini_bench()
        del cur["kernel_dataflow"]["launches"]["w1"]["slice_bytes"]
        rows = {r["metric"]: r for r in diff_table(cur, base, 0.10)}
        row = rows["kernel_dataflow/w1/slice_bytes"]
        assert row["status"] == "MISSING" and row["current"] is None
        assert any("missing" in line for line in compare(cur, base, 0.10))

    def test_format_diff_table_renders_every_row(self, capsys):
        from benchmarks.check_regression import diff_table, format_diff_table

        base = self._mini_bench()
        cur = self._mini_bench(hbm=200.0)
        format_diff_table(diff_table(cur, base, 0.10))
        text = capsys.readouterr().out
        assert text.count("\n") == 9  # header + 8 metric rows
        assert "FAIL" in text and "ok" in text
