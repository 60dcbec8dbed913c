"""Share of the traced window in which no operation ran on the device
(``device_idle_share.index``, read as it reads every cell)."""
from chipbench import harness

_SHARE = harness.load_module(harness.BENCH / "metrics"
                             / "device_idle_share.index.py")


def read(ctx):
    return _SHARE.read(ctx)
