"""Median of the gaps between consecutive output tokens of a
request, both inside the window, pooled over all requests: the plain
decode tick."""

from chipbench.readers import itls_s, percentile


def read(ctx):
    v = percentile(itls_s(ctx.win), 50)
    return None if v is None else v * 1e3
