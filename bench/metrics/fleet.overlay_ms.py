"""Mean ``fleet.overlay`` span of ``run_fleet`` (the heterogeneity draw,
every chip's re-programming under its own draw, the fleet fault
overlay), ms. None on a program without the span."""
from bench import readers


def read(ctx):
    return readers.span_mean_ms(ctx, "fleet.overlay")
