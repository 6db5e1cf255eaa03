"""Mean ``compile`` span of ``run_compiled`` (trace, lower, and compile or load from the persistent cache), ms."""
from bench import readers


def read(ctx):
    return readers.span_mean_ms(ctx, "compile")
