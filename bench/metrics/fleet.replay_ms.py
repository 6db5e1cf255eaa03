"""Replay unit time per fleet schedule: summed ``replay.quantize`` spans
(key chain, quantize dispatch and blocking fetch) of every chip per
``schedule`` span, ms."""
from bench import span_readers


def read(ctx):
    return span_readers.sum_per_ms(ctx, "replay.quantize", per="schedule")
