"""Mean host time per served request dispatching the encoder (the
program's ``repro.service.embed`` spans in the traced window, over the
window's requests, cache hits included)."""
from chipbench import harness

SPAN = "repro.service.embed"
_MS = harness.load_module(harness.BENCH / "metrics"
                          / "index_query_ms.served.py")


def read(ctx):
    return _MS.ms_a_request(ctx, SPAN)
