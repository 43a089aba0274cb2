"""Pallas kernels: ``fused_conv`` (fused conv pyramids), ``online_sop`` (SOP + END)."""
