"""Query rows whose whole answer reached the caller, over the window
(from the first send to the last answer on the host)."""


def read(ctx):
    return sum(c["rows"] for _, _, _, c in ctx.served) / ctx.window_s
