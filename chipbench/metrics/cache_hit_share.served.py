"""Share of the window's requests the result cache answered: ``hits``
over ``hits + misses`` of the program's ``repro.service.cache`` spans in
the traced window, in %."""

SPAN = "repro.service.cache"


def read(ctx):
    if ctx.trace is None:
        return None
    spans = [e.args for e in ctx.trace.program if e.name == SPAN]
    hits = sum(a.get("hits", 0) for a in spans)
    total = hits + sum(a.get("misses", 0) for a in spans)
    return 100.0 * hits / total if total else None
