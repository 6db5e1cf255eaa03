"""Replay unit time per batch schedule: summed ``replay.quantize`` spans
(key chain, quantize dispatch and blocking fetch) per ``schedule`` span,
ms."""
from bench import span_readers


def read(ctx):
    return span_readers.sum_per_ms(ctx, "replay.quantize", per="schedule")
