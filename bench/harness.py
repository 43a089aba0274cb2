"""One run of one benchmark cell.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
harness finds ``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``
and, for each metric the cell reports, ``bench/metrics/<reader>.py``
(``glue_us_per_image.offline`` is read by ``glue_us_per_image.py``); each
layer's op is ``bench/ops/<op>.py`` (``bench/model.py``).  A new cell,
metric or layer op is new files and new entries, never an edit here.

The run: check the device, build the program's graph and check it against
the configuration's layer list, make weights and images from the seed,
serve the traffic through ``ServingFrontend(ServingEngine(...))``, warm up,
measure a window, then compare every answer the client received with the
benchmark's own float32 reference.
"""

from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from bench import client, model, trace
from bench.registry import BenchError, load_module

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_SECONDS = 2.0  # length of the profiled stretch in a --trace 1 run

# The comparison that decides ``correct``.  logit_rms_err is, over every
# answer the client received, the root-mean-square logit difference from the
# float32 reference as a share of that image's root-mean-square reference
# logit; its limit is the configuration's own (``correct`` in its file, set
# from readings of the program and of the int8 control, see PERF.md).
EXACT_LIMITS = {"unanswered": 0, "compiles_in_window": 0}


def limits(config: dict) -> dict:
    return {**config["correct"], **EXACT_LIMITS}


def load_spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    with open(BENCH_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


def cell_metrics(spec: dict, cell: str, per_layer: bool) -> list[dict]:
    """The metrics ``cell`` reports: end-to-end ones, or per-layer ones."""
    entries = spec["per_layer"] if per_layer else spec["end_to_end"]
    e2e = {m["name"] for m in cell_metrics(spec, cell, False)} if per_layer else None
    out = []
    for m in entries:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif e2e is None or m["moves"] in e2e:
            out.append(m)
    return out


def reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<reader>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric.split('.')[0]}.py"
    return load_module(path, f"metric {metric}").read


@dataclass
class Context:
    """What a metric reader may read."""

    config: dict
    traffic: dict
    peak: dict
    seconds: float
    setup_s: float
    run: client.Run
    counters: dict  # engine counts over the window: images, slots, batches
    bucket: int
    trace: trace.Reduction | None = None
    trace_images: int = 0  # images that reached the client in the traced stretch


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; a run needs ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if info["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX found {info}; the benchmark runs on a TPU only")
    if info["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {info['count']}")
    return info


def load_peak(kind: str) -> dict:
    with open(BENCH_DIR / "peaks.json") as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks[kind]


def use_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path in the checkout,
    keeping every program so that only a cell's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def import_program():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        from repro.net import frontend, graph, partition, runner, serve
    except ImportError as err:
        raise BenchError(f"the system under test cannot be imported: {err}")
    return frontend, graph, partition, runner, serve


def check_graph(g, config: dict) -> None:
    """The program's graph has exactly the configuration's layer list: each
    node's name, op, inputs and the fields its op module names."""
    if (g.input_size, g.in_channels) != (config["input_size"], config["in_channels"]):
        raise BenchError(f"{g.name}: input {g.input_size}x{g.in_channels} differs from the config")
    if g.nodes[0].name != model.INPUT:
        raise BenchError(f"{g.name}: input node {g.nodes[0].name!r}, config says {model.INPUT!r}")
    for i, (n, layer) in enumerate(zip(g.nodes[1:], config["layers"])):
        have = {"name": n.name, "op": n.op, "inputs": list(n.inputs)}
        have.update((f, getattr(n, f, None)) for f in model.op(layer["op"]).FIELDS)
        if have != layer:
            raise BenchError(f"{g.name} layer {i}: program has {have}, config has {layer}")
    if len(g.nodes) - 1 != len(config["layers"]):
        raise BenchError(f"{g.name}: {len(g.nodes) - 1} layers, config lists {len(config['layers'])}")


def profile_options():
    """Device events only: no Python function tracer and no host tracer,
    both of which slow the host threads that serve the traffic."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    options.enable_hlo_proto = False
    return options


def bench_marker(x):
    return x + 1


class Profile(threading.Thread):
    """Takes one device trace of ``TRACE_SECONDS``, bounded by two runs of
    the marker program (``bench/trace.py``).  Made at set-up, so that the
    marker compiles there; started at the window's start, it traces the
    stretch that ends a second before the window closes, so that what the
    profiler does after it stops falls outside the window."""

    def __init__(self, seconds: float):
        import jax
        import jax.numpy as jnp

        super().__init__(name="bench-profile")
        self.delay_s = max(0.0, seconds - TRACE_SECONDS - 1.0)
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._marker = jax.jit(bench_marker)
        self._x = jnp.zeros((8, 128), jnp.float32)
        self._mark()  # compiled before the trace
        self.span = (None, None)  # the traced stretch, on the host clock
        self.error = None

    def _mark(self) -> float:
        self._marker(self._x).block_until_ready()
        return time.perf_counter()

    def run(self) -> None:
        import jax

        try:
            time.sleep(self.delay_s)
            jax.profiler.start_trace(self.dir, profiler_options=profile_options())
            try:
                t0 = self._mark()
                time.sleep(TRACE_SECONDS)
                self.span = (t0, time.perf_counter())
                self._mark()
            finally:
                jax.profiler.stop_trace()
        except Exception as err:  # reported by the harness after the window
            self.error = err

    def reduce(self, program: str, records: list) -> trace.Reduction:
        """The trace's numbers, with the client's spans for the idle gaps."""
        try:
            events = trace.load_events(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        lo, hi, offset = trace.marker_window(events, self.span[0])
        spans = []
        for r in records:
            for name, s, e in (("client.submit", r.submit_s, r.admitted_s),
                               ("client.wait", r.wait_s, r.done_s)):
                if s is not None and e is not None:
                    spans.append((name, (s - offset) * 1e9, (e - offset) * 1e9))
        return trace.reduce_events(events, lo, hi, program=program, spans=spans)


def _window_counts(before: dict, after: dict) -> dict:
    def totals(summary):
        rows = summary["buckets"]
        return (sum(r["images"] for r in rows), sum(r["batches"] * r["bucket"] for r in rows),
                sum(r["batches"] for r in rows))
    (i0, s0, b0), (i1, s1, b1) = totals(before), totals(after)
    return {"images": i1 - i0, "slots": s1 - s0, "batches": b1 - b0}


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell_name: str, seed: int, seconds: float, traced: bool,
             t_start: float, *,
             config: dict | None = None, traffic: dict | None = None,
             require_tpu: bool = True, say=print) -> dict:
    """Run one cell once and return the contract's result object.

    ``config`` and ``traffic`` override the files the cell names (the
    tests use a smaller image); ``require_tpu=False`` lets the tests drive
    the rest of a run on the CPU."""
    import jax

    spec = load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise BenchError(f"unknown workload {cell_name!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[cell_name]
    config = config or model.load_config(cell["config"])
    traffic = traffic or load_traffic(cell["traffic"])
    if require_tpu:
        device = device_info(cell["chips"])
        peak = load_peak(device["kind"])
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}
        peak = json.loads((BENCH_DIR / "peaks.json").read_text())["TPU v5 lite"]
    say(f"device: {json.dumps(device)}")
    phases = {"devices": time.perf_counter()}
    use_cache()
    frontend_mod, graph_mod, partition_mod, runner_mod, serve_mod = import_program()
    phases["import"] = time.perf_counter()

    g = graph_mod.MODELS[config["model"]](
        input_size=config["input_size"], num_classes=config["num_classes"],
        compute_dtype=config["compute_dtype"],
    )
    check_graph(g, config)
    params = model.init_params(config, seed)
    jax.block_until_ready(params)
    pool = model.make_images(config, seed, traffic["pool_images"])
    phases["weights"] = time.perf_counter()
    engine_knobs = dict(traffic.get("engine", {}))
    engine_knobs["buckets"] = tuple(engine_knobs["buckets"])
    serve_cfg = serve_mod.ServeConfig(compute_dtype=config["compute_dtype"], **engine_knobs)
    bucket = max(serve_cfg.buckets)
    plan = partition_mod.auto_partition(
        g, vmem_budget=serve_cfg.vmem_budget, batch=bucket,
        prefer_region=serve_cfg.prefer_region, compute_dtype=serve_cfg.compute_dtype,
    )
    say(f"plan: bucket {bucket}, {plan.n_launches()} launches: " + "; ".join(
        f"{p.name} {p.launch.regime} region {p.launch.out_region}" for p in plan.pyramids))

    phases["plan"] = time.perf_counter()
    engine = serve_mod.ServingEngine(g, params, serve_cfg)
    frontend = frontend_mod.ServingFrontend(engine)
    marks: dict = {}
    profile = Profile(seconds) if traced else None

    def on_window_start(t: float) -> None:
        # what set-up left behind (tracing and compiling the forward makes
        # millions of objects) is collected now and frozen out of later
        # collections, not swept up by a full collection inside the window
        gc.collect()
        gc.freeze()
        marks["traces"] = runner_mod.jit_trace_count()
        marks["summary"] = engine.summary()
        if profile is not None:
            profile.start()
        marks["setup_s"] = time.perf_counter() - t_start

    frontend.start()
    try:
        run = client.closed_loop(frontend, pool, traffic, seed, seconds, on_window_start)
        window_traces = runner_mod.jit_trace_count() - marks.get("traces", 0)
        after = engine.summary()
    finally:
        frontend.stop()
        if profile is not None and profile.is_alive():
            profile.join()
    if run.window_start_s is None:
        raise BenchError("the window never opened: the warm-up did not complete")
    counters = _window_counts(marks["summary"], after)
    phases["first answer"] = run.records[0].done_s
    phases["warm-up"] = run.window_start_s
    last = t_start
    for name, t in phases.items():
        phases[name], last = round(t - last, 3), t
    say(f"set-up seconds by phase: {phases}")
    device["memory_peak_bytes"] = memory_peak(jax.devices()[: cell["chips"]])

    ctx = Context(config=config, traffic=traffic, peak=peak, seconds=seconds,
                  setup_s=marks["setup_s"], run=run, counters=counters, bucket=bucket)
    if traced:
        if profile.error is not None:
            raise BenchError(f"profiling failed: {profile.error!r}")
        ctx.trace = profile.reduce("run_network", run.records)
        lo, hi = profile.span
        ctx.trace_images = sum(len(r.images) for r in run.records
                               if r.logits is not None and lo <= r.done_s < hi)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s

    timed = [r for r in run.records if r.submit_s >= run.window_start_s]
    failed = [r for r in timed if r.logits is None]
    say(f"requests: attempted {len(timed)}, completed {len(timed) - len(failed)},"
        f" failed {len(failed)} (window {seconds} s; warm-up"
        f" {len(run.records) - len(timed)}); engine over the window: {counters}")
    per_second = [0] * int(np.ceil(seconds))
    for r in run.records:
        if r.logits is not None and run.in_window(r.done_s):
            per_second[int(r.done_s - run.window_start_s)] += len(r.images)
    say(f"images answered in each second of the window: {per_second}")
    say(f"compiles inside the window: {window_traces}")
    say(f"memory_peak_bytes: {device['memory_peak_bytes']}")
    for r in failed[:3]:
        say(f"failed request: {r.error}")

    # the program's state goes before the reference runs
    del engine, frontend
    gc.collect()
    checks = compare(config, params, pool, run.records)
    checks["compiles_in_window"] = window_traces

    metrics = {}
    for m in cell_metrics(spec, cell_name, per_layer=traced):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    limit = limits(config)
    result = {
        "correct": all(checks[k] <= limit[k] for k in limit),
        "attempted": len(timed),
        "failed": len(failed),
        "metrics": metrics,
        "device": device,
    }
    if traced:
        result["breakdown"] = {"device_ops": ctx.trace.device_ops,
                               "idle_gaps": ctx.trace.idle_gaps}
    result["checks"] = {k: {"value": checks[k], "limit": limit[k]} for k in limit}
    return result


def compare(config: dict, params, pool: np.ndarray, records: list) -> dict:
    """Every answer the client received against the float32 reference of
    its images."""
    reference = model.logits_in_blocks(config, params, pool)
    worst = 0.0
    unanswered = 0
    for r in records:
        if r.logits is None:
            unanswered += 1
            continue
        err = float(model.logit_rms_error(r.logits, reference[list(r.images)]).max())
        worst = max(worst, err if np.isfinite(err) else float("inf"))
    return {"logit_rms_err": worst, "unanswered": unanswered}
