"""Kernels: the share of the plan's conv multiply-adds that run in levels
whose kernel contracts two or more taps per MXU pass, in percent, from the
counters the program bumps as it builds a plan (``fused.conv_macs`` and
``fused.folded_conv_macs`` in the process-wide recorder of ``repro.obs``).
No number where the program keeps no such counters."""


def read(ctx):
    try:
        from repro.obs.trace import get_tracer
    except ImportError:
        return None
    counters = getattr(get_tracer(), "counters", None) or {}
    total = counters.get("fused.conv_macs")
    folded = counters.get("fused.folded_conv_macs")
    if not total or folded is None:
        return None
    return 100.0 * folded / total
