"""The trace reduction, on a recorded trace and on hand-made events."""

import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def _op(name, start, dur, plane="/device:TPU:0", kernel=False):
    text = f"%{name} = ..." + (' custom_call_target="tpu_custom_call"' if kernel else "")
    return {"kind": "op", "plane": plane, "name": text, "start_ns": start, "dur_ns": dur}


def test_recorded_forward():
    """One bucket-8 ResNet-18 forward recorded on a TPU v5e, then 0.3 ms of
    idle time: 12 fused kernels, no XLA convolution, the stem kernel first."""
    data = json.loads((DATA / "resnet18_offline_trace.json").read_text())
    r = trace.reduce_events(data["events"], data["lo"], data["hi"],
                            program="run_network",
                            spans=[("client.wait", -1e12, 1e12)])
    assert r.chips == 1
    assert r.forwards == pytest.approx(1.0)
    assert sum(trace.is_kernel(e) for e in data["events"]) == 12
    assert r.conv_s == 0.0
    assert r.window_s == pytest.approx(3.246532e-3)
    assert r.busy_s == pytest.approx(2.926038e-3)
    assert r.kernel_s == pytest.approx(2.607406e-3)
    assert r.glue_s == pytest.approx(0.318632e-3)
    # kernels and glue never overlap here, so they add up to the busy time
    assert r.kernel_s + r.glue_s == pytest.approx(r.busy_s)
    assert r.device_ops[0] == ["fused_pyramid.12", pytest.approx(1.460576e-3)]
    assert r.idle_gaps[0] == ["client.wait", pytest.approx(0.300001e-3)]


def test_union_clipping_and_gap_names():
    events = [
        _op("fused_pyramid.1", 100, 300, kernel=True),  # clipped to [200, 400)
        _op("pad.1", 350, 100),  # overlaps the kernel: busy to 450
        _op("add.1", 600, 100),
        _op("copy.1", 950, 200),  # clipped to [950, 1000)
        {"kind": "module", "plane": "/device:TPU:0", "name": "jit__run_network_jit(1)",
         "start_ns": 100, "dur_ns": 600},
    ]
    spans = [("client.submit", 440, 500), ("client.wait", 0, 2000)]
    r = trace.reduce_events(events, 200, 1000, program="run_network", spans=spans)
    assert r.window_s == pytest.approx(800e-9)
    assert r.busy_s == pytest.approx((250 + 100 + 50) * 1e-9)
    assert r.kernel_s == pytest.approx(200e-9)
    assert r.glue_s == pytest.approx((100 + 100 + 50) * 1e-9)
    assert r.forwards == pytest.approx(500 / 600)
    # gaps [450, 600) and [700, 950): the innermost span open at the start
    assert r.idle_gaps == [["client.wait", pytest.approx(250e-9)],
                           ["client.submit", pytest.approx(150e-9)]]


def test_busy_is_averaged_over_chips():
    events = [_op("a.1", 0, 100, plane="/device:TPU:0"),
              _op("a.1", 0, 50, plane="/device:TPU:1")]
    r = trace.reduce_events(events, 0, 100)
    assert r.chips == 2
    assert r.busy_s == pytest.approx(75e-9)
    assert r.idle_gaps == [[trace.NO_SPAN, pytest.approx(50e-9)]]


def test_marker_window():
    def module(name, start, dur):
        return {"kind": "module", "plane": "/device:TPU:0", "name": name,
                "start_ns": start, "dur_ns": dur}

    events = [module("jit_bench_marker(1)", 1000, 500),
              module("jit__run_network_jit(2)", 2000, 100),
              module("jit_bench_marker(1)", 9000, 500)]
    lo, hi, offset = trace.marker_window(events, host_first_s=10.0)
    assert (lo, hi) == (1500, 9000)
    assert offset == pytest.approx(10.0 - 1500e-9)
    with pytest.raises(RuntimeError):
        trace.marker_window(events[:2], host_first_s=10.0)
