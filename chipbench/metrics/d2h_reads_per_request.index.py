"""Blocking device-to-host reads per request: the ``reads`` the program
gives each of its ``repro.*.sync`` spans in the traced window, summed,
over the window's requests."""

SYNC = ".sync"


def read(ctx):
    if ctx.trace is None:
        return None
    reads = [e.args.get("reads", 0) for e in ctx.trace.program
             if e.name.endswith(SYNC)]
    return sum(reads) / ctx.trace.n_requests if reads else None
