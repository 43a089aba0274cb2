"""Kernels: the share of the plan's conv multiply-adds that run in
pyramids of two or more conv levels, in percent, from the counters the
program bumps as it builds a plan (``fused.conv_macs`` and
``fused.chained_conv_macs`` in the process-wide recorder of ``repro.obs``).
No number where the program keeps no such counters."""


def read(ctx):
    try:
        from repro.obs.trace import get_tracer
    except ImportError:
        return None
    counters = getattr(get_tracer(), "counters", None) or {}
    total = counters.get("fused.conv_macs")
    chained = counters.get("fused.chained_conv_macs")
    if not total or chained is None:
        return None
    return 100.0 * chained / total
