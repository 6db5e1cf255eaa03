"""Share of ``run_fleet`` calls that ran a cached program: top-level
``compile.reuse`` spans per top-level ``compile`` span, %."""
from bench import span_readers


def read(ctx):
    calls = len(span_readers.top_level(ctx.spans, "compile"))
    if not calls:
        return None
    return 100.0 * len(span_readers.top_level(ctx.spans, "compile.reuse")) \
        / calls
