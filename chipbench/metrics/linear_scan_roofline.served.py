"""The linear-route kernel's roofline share (``linear_scan_roofline``,
read as it reads every cell) on the served path's counts: the rows the
router sent linear, each compared with every corpus row at the
encoder's width."""
from chipbench import harness

_SHARE = harness.load_module(harness.BENCH / "metrics"
                             / "linear_scan_roofline.py")


def read(ctx):
    return _SHARE.read(ctx)
