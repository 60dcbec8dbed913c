"""Mean host time per request waiting for the whole result's copy to the
host, count and packed pairs (the program's ``repro.result.copy.sync``
spans in the traced window)."""

SPAN = "repro.result.copy.sync"


def read(ctx):
    return None if ctx.trace is None else ctx.trace.ms_per_request(SPAN)
