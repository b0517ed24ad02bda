"""Set-up: process start to window open (loading, warm-up, compiles on a
cold run, and the fill of the lanes before the window)."""


def read(ctx):
    return ctx.setup_s
