"""Share of the window's query rows the router sent to the linear scan
(``QueryResult.lin_idx``, pad repeats dropped)."""


def read(ctx):
    rows = sum(c["rows"] for _, _, _, c in ctx.served)
    lin = sum(int(c["linear"].sum()) for _, _, _, c in ctx.served)
    return 100.0 * lin / rows if rows else None
