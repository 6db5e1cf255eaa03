"""Model operations of the frames served over the window and the chip's bf16 peak, %."""
from bench import readers


def read(ctx):
    return readers.mfu_pct(ctx)
