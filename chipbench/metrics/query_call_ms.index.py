"""Mean host time per request inside ``index.query`` (hash, estimate,
the route sync, per-segment dispatch of both routes)."""
import numpy as np


def read(ctx):
    spans = ctx.spans.get("query")
    return float(np.mean(spans)) * 1e3 if spans else None
