"""Mean ``schedule`` span of ``run_fleet`` (every chip's host batch stream and initial state), ms."""
from bench import readers


def read(ctx):
    return readers.span_mean_ms(ctx, "schedule")
