"""Mean host time per request dispatching the search of both routes
over every segment (the program's ``repro.engine.search`` spans in the
traced window)."""

SPAN = "repro.engine.search"


def read(ctx):
    return None if ctx.trace is None else ctx.trace.ms_per_request(SPAN)
