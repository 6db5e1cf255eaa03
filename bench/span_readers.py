"""Arithmetic of the per-layer readers that break a program span into
its children: sums of the program's spans inside the window, per span
of another name (per call, per engine step).

Only top-level spans of a name count: one that lies inside another of
the same name is part of that one's time. Each reader returns None
where its run has nothing to read: no span it divides by, or, on a
program that does not emit the child spans, no span that shows them.
"""
from __future__ import annotations


def top_level(spans, name: str) -> list[float]:
    """Durations (s) of the ``name`` spans that start outside every
    earlier ``name`` span (as ``repro.obs.Tracer.summary`` counts them);
    ``spans`` are ``(name, start_s, dur_s)``."""
    out, end = [], float("-inf")
    for s, d in sorted((s, d) for n, s, d in spans if n == name):
        if s >= end:
            out.append(d)
            end = s + d
    return out


def sum_per_ms(ctx, name: str, per: str, shown_by: str = None):
    """Summed top-level ``name`` spans inside the window per top-level
    ``per`` span, ms. With ``shown_by``, a window that holds such spans
    but no ``name`` span reads 0; without it, None."""
    n = len(top_level(ctx.spans, per))
    if not n or not top_level(ctx.spans, shown_by or name):
        return None
    return 1e3 * sum(top_level(ctx.spans, name)) / n


def max_ms(ctx, name: str):
    """The longest top-level ``name`` span inside the window, ms."""
    durs = top_level(ctx.spans, name)
    return 1e3 * max(durs) if durs else None
