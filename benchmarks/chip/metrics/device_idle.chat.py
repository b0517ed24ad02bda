"""Device: share of the traced window in which no op ran on the chip."""

from chipbench.readers import idle_pct


def read(ctx):
    return idle_pct(ctx.trace)
