"""95th percentile of how late the load generator submitted a request
after it was due, ms (a starved generator would otherwise read as a fast
server)."""
import numpy as np


def read(ctx):
    r = ctx.data.get("requests")
    if r is None or not len(r["due"]):
        return None
    return 1e3 * float(np.percentile(r["submit"] - r["due"], 95))
