"""Share of the traced window in which a chip ran nothing, mean over the
host's chips, %."""
from bench import readers


def read(ctx):
    return readers.idle_pct(ctx)
