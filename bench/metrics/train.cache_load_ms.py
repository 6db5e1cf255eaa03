"""Persistent-cache load time per ``run_compiled`` call: summed
``compile.cache_load`` spans per ``compile`` span, ms."""
from bench import span_readers


def read(ctx):
    return span_readers.sum_per_ms(ctx, "compile.cache_load", per="compile",
                                   shown_by="compile.lower")
