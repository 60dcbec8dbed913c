"""Share of the bytes copied to the host for answers that are answers:
8 B (an int32 id and a float32 distance) for each id the program's
``repro.result.reported`` spans report, over the ``bytes`` they copied,
in %."""

SPAN = "repro.result.reported"
PAIR_BYTES = 8


def read(ctx):
    if ctx.trace is None:
        return None
    rows = [e.args for e in ctx.trace.program if e.name == SPAN]
    copied = sum(a.get("bytes", 0) for a in rows)
    if not copied:
        return None
    return 100.0 * PAIR_BYTES * sum(a.get("reported", 0)
                                    for a in rows) / copied
