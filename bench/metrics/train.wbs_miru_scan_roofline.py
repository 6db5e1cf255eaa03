"""Roofline share of the fused recurrence kernel, %."""
from bench import readers


def read(ctx):
    return readers.roofline_pct(ctx, "wbs_miru_scan")
