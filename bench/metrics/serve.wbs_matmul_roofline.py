"""Roofline share of the WBS input-drive kernel, %."""
from bench import readers


def read(ctx):
    return readers.roofline_pct(ctx, "wbs_matmul")
