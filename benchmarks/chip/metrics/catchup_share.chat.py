"""Scheduler admission: share of the lane-steps of the requests finished
in the window that teacher-force prompt tokens past the prefill bucket,
sum(L - bucket) / sum(L - bucket + generated)."""


def read(ctx):
    win = ctx.win
    catch = work = 0
    for o in win.reqs:
        if not (o.finished and o.bucket is not None
                and win.in_window(o.times[-1])):
            continue
        catch += o.prompt_len - o.bucket
        work += o.prompt_len - o.bucket + o.max_new
    return 100.0 * catch / work if work else None
