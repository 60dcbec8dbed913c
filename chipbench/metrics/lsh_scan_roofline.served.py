"""The LSH-route kernel's roofline share (``lsh_scan_roofline``, read
as it reads every cell) on the served path's counts: the query vectors
the index searched and their distinct candidates, at the encoder's
width."""
from chipbench import harness

_SHARE = harness.load_module(harness.BENCH / "metrics"
                             / "lsh_scan_roofline.py")


def read(ctx):
    return _SHARE.read(ctx)
