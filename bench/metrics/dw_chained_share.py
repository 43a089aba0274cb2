"""Kernels: the share of the plan's depthwise multiply-adds that run in a
launch which also holds the level after them (a ConvNeXt block's first
1x1), so that the block's maps never leave VMEM, in percent, from the
counters the program bumps as it builds a plan (``fused.dw_macs`` and
``fused.chained_dw_macs`` in the process-wide recorder of ``repro.obs``).
No number where the program keeps no such counters."""


def read(ctx):
    try:
        from repro.obs.trace import get_tracer
    except ImportError:
        return None
    counters = getattr(get_tracer(), "counters", None) or {}
    total = counters.get("fused.dw_macs")
    chained = counters.get("fused.chained_dw_macs")
    if not total or chained is None:
        return None
    return 100.0 * chained / total
