"""Reduction of a profiler trace to the benchmark's device numbers.

The trace is taken with the host tracer off: on a TPU v5e its host events
(one per chunk of the host-side input layout transposes) slowed the served
traffic five-fold, while a device-only trace left it unchanged (PERF.md).
So the trace holds device events only, and the benchmark's host spans come
from its own records, put on the device clock by two marker programs: a
tiny jitted ``bench_marker`` run just after the profiler starts and just
before it stops.  The first marker's end and the second's start bound the
traced window on the device clock; the host time at which the first
returned gives the offset between the clocks.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load_events` reads it with
``jax.profiler.ProfileData`` and keeps, from each ``/device:TPU:<n>``
plane, the ``XLA Ops`` line (``op``: one operation on the chip) and the
``XLA Modules`` line (``module``: one execution of a compiled program).
:func:`reduce_events` turns those into busy and idle time, kernel against
glue time, the most expensive operations and the longest idle gaps.  It
works on plain dicts, so the tests feed it a small recorded trace.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

MARKER = "bench_marker"  # the marker program's name (``jit_bench_marker``)
NO_SPAN = "engine (no spans yet)"
TOP = 10


def load_events(trace_dir: str) -> list[dict]:
    """Every device op and program execution of the one ``.xplane.pb``
    under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found {paths}")
    events = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            kind = "op" if line.name == "XLA Ops" else "module"
            for ev in line.events:
                events.append({
                    "kind": kind,
                    "plane": plane.name,
                    "name": ev.name,
                    "start_ns": float(ev.start_ns),
                    "dur_ns": float(ev.duration_ns),
                })
    return events


def marker_window(events: list[dict], host_first_s: float) -> tuple[float, float, float]:
    """``(lo_ns, hi_ns, offset_s)``: the device-clock window between the end
    of the first marker and the start of the second, and the host time
    minus the device time (``host_first_s`` is the host time at which the
    first marker's result was back)."""
    marks = sorted(
        (e for e in events if e["kind"] == "module" and MARKER in e["name"]),
        key=lambda e: e["start_ns"],
    )
    planes = {e["plane"] for e in marks}
    if len(marks) != 2 * len(planes) or not marks:
        raise RuntimeError(f"expected two {MARKER} runs per chip, found {len(marks)}")
    first, last = marks[0], marks[-1]
    lo = first["start_ns"] + first["dur_ns"]
    return lo, last["start_ns"], host_first_s - lo * 1e-9


def op_name(ev: dict) -> str:
    """The HLO instruction's name, from the event's ``%name = ...`` text."""
    return ev["name"].split(" = ")[0].lstrip("%")


def is_kernel(ev: dict) -> bool:
    """A Pallas kernel: a custom call to ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in ev["name"]


def is_conv(ev: dict) -> bool:
    """An XLA convolution, alone or inside a fusion."""
    return "convolution" in ev["name"]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(ev: dict, lo: float, hi: float) -> tuple[float, float] | None:
    s, e = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


@dataclass
class Reduction:
    """Device time inside the traced window, in seconds."""

    window_s: float
    busy_s: float  # union of op intervals, averaged over the chips
    kernel_s: float  # Pallas kernels
    conv_s: float  # XLA convolutions
    glue_s: float  # every other op
    chips: int
    forwards: float  # executions of the timed program, clipped ones in part
    device_ops: list = field(default_factory=list)  # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)  # [[span, seconds]]


def reduce_events(events: list[dict], lo: float, hi: float, *,
                  program: str | None = None,
                  spans: list[tuple[str, float, float]] = ()) -> Reduction:
    """Reduce ``events`` to device time inside ``[lo, hi)`` (device ns).

    ``program`` is a substring of the timed program's module name; its
    executions inside the window are counted, a clipped one in part.
    ``spans`` are the benchmark's host spans ``(name, start_ns, end_ns)`` on
    the device clock; each idle gap is named by the one open at its start."""
    ops = [e for e in events if e["kind"] == "op"]
    chips = sorted({e["plane"] for e in ops})
    totals: dict[str, float] = {}
    kernel = conv = glue = busy = 0.0
    gaps: list[tuple[float, float]] = []
    for chip in chips:
        busy_here = []
        for ev in ops:
            if ev["plane"] != chip:
                continue
            iv = _clip(ev, lo, hi)
            if iv is None:
                continue
            busy_here.append(iv)
            dur = iv[1] - iv[0]
            totals[op_name(ev)] = totals.get(op_name(ev), 0.0) + dur
            if is_kernel(ev):
                kernel += dur
            elif is_conv(ev):
                conv += dur
            else:
                glue += dur
        merged = _union(busy_here)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = max(len(chips), 1)
    forwards = 0.0
    if program is not None:
        for ev in events:
            if ev["kind"] == "module" and program in ev["name"] and ev["dur_ns"] > 0:
                iv = _clip(ev, lo, hi)
                if iv is not None:
                    forwards += (iv[1] - iv[0]) / ev["dur_ns"]
        forwards /= n
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return Reduction(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy * 1e-9 / n,
        kernel_s=kernel * 1e-9 / n,
        conv_s=conv * 1e-9 / n,
        glue_s=glue * 1e-9 / n,
        chips=len(chips),
        forwards=forwards,
        device_ops=[[k, v * 1e-9 / n] for k, v in
                    sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=[[_open_span(spans, s), (e - s) * 1e-9] for s, e in longest],
    )


def _open_span(spans, t: float) -> str:
    """The innermost benchmark span open at ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s > best[1]):
            best = (name, s)
    return best[0] if best is not None else NO_SPAN
