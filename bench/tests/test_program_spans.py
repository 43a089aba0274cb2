"""The readers of the program's host spans, on a short CPU run.

A small ResNet-18 (32x32, Pallas kernels in interpret mode) is served
through ``ServingFrontend(ServingEngine(...))`` by the benchmark's own
client; the readers then read the process's recorder as they do after a
chip run.  The engine's spans are also put on a recorded device trace's
clock, as the harness puts the client's, to name that trace's idle gap.
"""

import json
import time
from pathlib import Path

import pytest

from bench import client, harness, model, trace
from test_correct import SEED, TRAFFIC, small

DATA = Path(__file__).parent / "data"
BATCH_SPANS = ("engine.stage", "engine.dispatch", "engine.block", "engine.record")
READERS = {
    "offline": ["stage_us", "dispatch_us", "record_us"],
    "single": ["stage_us", "dispatch_us", "record_us", "engine_admit_us",
               "queue_wait_us", "handback_us"],
}
SINGLE = dict(TRAFFIC, in_flight=1, engine={"buckets": [1]})


def serve(traffic: dict, seconds: float = 1.0) -> harness.Context:
    """Serve ``traffic`` for a window of ``seconds`` and return the readers'
    context.  The window the readers see is the run's whole window: the
    part a ``--trace 1`` run leaves to the profiler is added to its end."""
    _, graph_mod, _, _, serve_mod = harness.import_program()
    from repro.net.frontend import ServingFrontend

    config = small("resnet18-bf16")
    g = graph_mod.MODELS[config["model"]](
        input_size=config["input_size"], num_classes=config["num_classes"],
        compute_dtype=config["compute_dtype"])
    params = model.init_params(config, SEED)
    pool = model.make_images(config, SEED, traffic["pool_images"])
    engine = serve_mod.ServingEngine(g, params, serve_mod.ServeConfig(
        compute_dtype=config["compute_dtype"], buckets=tuple(traffic["engine"]["buckets"])))
    with ServingFrontend(engine) as frontend:
        run = client.closed_loop(frontend, pool, traffic, SEED, seconds)
    assert all(r.logits is not None for r in run.records)
    run = client.Run(run.records, run.window_start_s,
                     run.window_end_s + harness.TRACE_SECONDS + 1.0)
    return harness.Context(config=config, traffic=traffic, peak={}, seconds=seconds,
                           setup_s=0.0, run=run, counters={},
                           bucket=max(traffic["engine"]["buckets"]))


def read(name: str, ctx) -> float | None:
    return harness.reader(f"{name}.x")(ctx)


@pytest.mark.parametrize("mix", ["offline", "single"])
def test_readers_give_positive_numbers(mix):
    ctx = serve(TRAFFIC if mix == "offline" else SINGLE)
    for name in READERS[mix]:
        value = read(name, ctx)
        assert value is not None and value > 0, name


def test_readers_give_nothing_outside_the_ring():
    from repro.obs.trace import TraceCollector, tracing

    with tracing(TraceCollector(capacity=64)):
        ctx = serve(SINGLE)  # far more than 64 spans: the ring wraps
        for name in READERS["single"]:
            assert read(name, ctx) is None, name
    later = time.perf_counter() + 3600.0
    ctx.run = client.Run(ctx.run.records, later, later + 10.0)
    for name in READERS["single"]:
        assert read(name, ctx) is None, name


def test_engine_spans_name_a_recorded_idle_gap():
    """One batch's engine spans, shifted so that its ``engine.record``
    span is open where the recorded bucket-8 forward leaves the chip idle,
    name that gap: the mapping the harness applies to its client spans
    (``Profile.reduce``) carries the program's spans too."""
    from repro.obs.trace import TraceCollector, tracing

    with tracing(TraceCollector()) as col:
        serve(TRAFFIC)
    spans = col.spans_between()
    bid = next(s.id for s in spans if s.name == "engine.record")
    batch = [s for s in spans if s.name in BATCH_SPANS and s.id == bid]
    assert sorted(s.name for s in batch) == sorted(BATCH_SPANS)
    record = next(s for s in batch if s.name == "engine.record")

    data = json.loads((DATA / "resnet18_offline_trace.json").read_text())
    ops = [e for e in data["events"] if e["kind"] == "op"]
    gap_start_ns = max(e["start_ns"] + e["dur_ns"] for e in ops)
    offset = (record.start_s + record.end_s) / 2 - gap_start_ns * 1e-9
    on_device = [(name, (s - offset) * 1e9, (e - offset) * 1e9)
                 for name, s, e, *_ in batch]
    r = trace.reduce_events(data["events"], data["lo"], data["hi"],
                            program="run_network", spans=on_device)
    assert r.idle_gaps[0] == ["engine.record", pytest.approx(0.300001e-3)]
