"""Process start to the first timed request: data, index build and
churn, warm-up of every request shape, compilation or cache loads."""


def read(ctx):
    return ctx.setup_s
