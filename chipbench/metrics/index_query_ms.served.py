"""Mean host time per served request inside the index's query (the
program's ``repro.index.query`` spans in the traced window: hash,
estimate, the route sync, the search dispatch), over the window's
requests, cache hits included."""

SPAN = "repro.index.query"


def ms_a_request(ctx, span: str):
    """Host ms in the program's ``span`` spans of the traced window,
    over the window's requests (a drain serves a round of them)."""
    if ctx.trace is None or not ctx.served:
        return None
    durs = [e.dur_ns for e in ctx.trace.program if e.name == span]
    return sum(durs) * 1e-6 / len(ctx.served) if durs else None


def read(ctx):
    return ms_a_request(ctx, SPAN)
