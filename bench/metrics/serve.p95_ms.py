"""95th percentile latency of every request due in the window, ms, each
timed from its due time to the retire of its last frame's logits. Read
per layer: below the knee the tail is the queue of the most popular
user, whose requests wait on each other at a load near 0.85, so one
host stall of a tenth of a second leaves a backlog that takes about a
second to clear, and the tail swings from run to run with the stalls."""
import numpy as np


def read(ctx):
    r = ctx.data.get("requests")
    if r is None or not len(r["due"]):
        return None
    return 1e3 * float(np.percentile(r["done"] - r["due"], 95))
