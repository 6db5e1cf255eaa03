"""Mean ``fleet.summarize`` span of ``run_fleet`` (the host work after
the program: per-chip summaries, write-count accounting, the endurance
tracker), ms. None on a program without the span."""
from bench import readers


def read(ctx):
    return readers.span_mean_ms(ctx, "fleet.summarize")
