"""Model operations of every emulated chip of the window's calls
(forward, DFA, evaluations) over their time and the host chips' bf16
peak, %."""
from bench import readers


def read(ctx):
    return readers.mfu_pct(ctx)
