"""Host side of the metering ``io_callback`` per ``run_compiled`` call:
summed ``telemetry.callback`` spans per ``execute`` span, ms."""
from bench import span_readers


def read(ctx):
    return span_readers.sum_per_ms(ctx, "telemetry.callback", per="execute")
