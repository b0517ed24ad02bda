"""Median time to first token over the requests due in the window, from
each due time (a request with no first token by the close counts its
wait)."""

from chipbench.readers import percentile, ttfts_s


def read(ctx):
    v = percentile(ttfts_s(ctx.win), 50)
    return None if v is None else v * 1e3
