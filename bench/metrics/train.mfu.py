"""Model operations of the window's calls over their time and the chip's bf16 peak, %."""
from bench import readers


def read(ctx):
    return readers.mfu_pct(ctx)
