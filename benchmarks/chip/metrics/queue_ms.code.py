"""Median wait from a request's due time to the start of its prefill
(the loop's admission stamp), in ms."""
import numpy as np


def read(ctx):
    q = ctx.observed.get("queue_s")
    return 1e3 * float(np.median(q)) if q else None
