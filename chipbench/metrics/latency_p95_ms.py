"""95th percentile of the same request latencies as ``latency_p50_ms``."""
import numpy as np


def read(ctx):
    return float(np.percentile([t1 - t0 for _, t0, t1, _ in ctx.served],
                               95)) * 1e3
