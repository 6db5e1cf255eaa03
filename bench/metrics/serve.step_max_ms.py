"""The longest ``serve.step`` span inside the window, ms: the host stall
that sets the serving tail."""
from bench import span_readers


def read(ctx):
    return span_readers.max_ms(ctx, "serve.step")
