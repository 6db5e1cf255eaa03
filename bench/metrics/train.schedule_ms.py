"""Mean ``schedule`` span of ``run_compiled`` (host batch stream and initial state), ms."""
from bench import readers


def read(ctx):
    return readers.span_mean_ms(ctx, "schedule")
