"""Roofline share of the WBS crossbar kernel on the conductance-domain
substrate, where it runs every product of the recurrence (input drive
and β·h·U_h), %."""
from bench import readers


def read(ctx):
    return readers.roofline_pct(ctx, "wbs_matmul")
