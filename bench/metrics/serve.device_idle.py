"""Share of the traced window in which the chip ran nothing, %."""
from bench import readers


def read(ctx):
    return readers.idle_pct(ctx)
