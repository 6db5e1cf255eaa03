"""XLA compile time per ``run_compiled`` call: summed ``compile.xla``
spans (backend compiles the persistent cache did not answer) per
``compile`` span, ms."""
from bench import span_readers


def read(ctx):
    return span_readers.sum_per_ms(ctx, "compile.xla", per="compile",
                                   shown_by="compile.lower")
