"""Mean ``compile.lower`` span of ``run_compiled`` (trace and lower the
program), ms."""
from bench import span_readers


def read(ctx):
    return span_readers.sum_per_ms(ctx, "compile.lower", per="compile.lower")
