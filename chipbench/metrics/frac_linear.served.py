"""Share of the query rows the router routed in the window (the rows of
requests not served from the cache) that it sent to the linear scan
(``RequestResult.linear``), in %."""


def read(ctx):
    miss = [c for _, _, _, c in ctx.served if not c["cached"]]
    rows = sum(c["rows"] for c in miss)
    lin = sum(int(c["linear"].sum()) for c in miss)
    return 100.0 * lin / rows if rows else None
