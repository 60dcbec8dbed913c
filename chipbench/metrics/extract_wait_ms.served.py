"""Mean host time per served request waiting for the whole result's copy
to the host, count and packed pairs (``extract_wait_ms.index``'s span,
in the traced window), over the window's requests, cache hits
included."""
from chipbench import harness

_SPAN = harness.load_module(harness.BENCH / "metrics"
                            / "extract_wait_ms.index.py").SPAN
_MS = harness.load_module(harness.BENCH / "metrics"
                          / "index_query_ms.served.py")


def read(ctx):
    return _MS.ms_a_request(ctx, _SPAN)
