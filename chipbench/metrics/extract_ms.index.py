"""Mean host time per request getting every row's answer to the host
(``QueryResult.reported`` for each row)."""
import numpy as np


def read(ctx):
    spans = ctx.spans.get("extract")
    return float(np.mean(spans)) * 1e3 if spans else None
