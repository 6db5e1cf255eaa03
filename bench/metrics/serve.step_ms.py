"""Mean ``serve.step`` span of the engine inside the window, ms."""
from bench import readers


def read(ctx):
    return readers.span_mean_ms(ctx, "serve.step")
