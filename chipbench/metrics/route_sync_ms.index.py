"""Mean host time per request in the router's one device-to-host read,
the routes of the request's rows (the program's
``repro.engine.route.sync`` spans in the traced window)."""

SPAN = "repro.engine.route.sync"


def read(ctx):
    return None if ctx.trace is None else ctx.trace.ms_per_request(SPAN)
