"""Scheduler: 90th percentile of due time to admission into a lane, over
the requests due in the window (host clock, step hook and lane table)."""

from chipbench.readers import percentile, queue_waits_s


def read(ctx):
    v = percentile(queue_waits_s(ctx.win), 90)
    return None if v is None else v * 1e3
