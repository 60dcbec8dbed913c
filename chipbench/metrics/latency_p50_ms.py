"""Median request latency over every request of the window: from when
it was sent (closed loop) to when its last answer was on the host."""
import numpy as np


def read(ctx):
    return float(np.percentile([t1 - t0 for _, t0, t1, _ in ctx.served],
                               50)) * 1e3
