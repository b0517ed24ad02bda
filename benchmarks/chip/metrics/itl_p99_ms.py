"""99th percentile of the gaps between consecutive output tokens of a
request, both inside the window, pooled over all requests: the ticks that
also carry an admission prefill (about 6% of the gaps at the chat cell's
rate, so the 95th percentile would sit on the edge of that cluster)."""

from chipbench.readers import itls_s, percentile


def read(ctx):
    v = percentile(itls_s(ctx.win), 99)
    return None if v is None else v * 1e3
