"""Quantities the metric readers (`metrics/<name>.py`) share.

A reader is ``read(ctx) -> float | None``: ``None`` when the run gave it
nothing to read, and the harness then leaves the metric out. ``ctx`` is a
`cli.Context`: the window's host observations (``ctx.win``), the trace
summary (``ctx.trace``, ``--trace 1`` only), the configuration's model
sizes (``ctx.model``), the chip's published peaks (``ctx.peak``, empty
off the chip) and ``ctx.chips``.
"""

from __future__ import annotations

from collections import Counter

import numpy as np


def percentile(values, q: float) -> float | None:
    """Linear-interpolated ``q``-th percentile; None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def due_in_window(win) -> list:
    return [o for o in win.reqs if win.in_window(o.due)]


def ttfts_s(win) -> list:
    """Due time to first token, for every request due in the window; one
    with no first token by the close counts the time it has waited."""
    out = []
    for o in due_in_window(win):
        first = o.times[0] if o.times else None
        if first is None or first >= win.t_close:
            first = win.t_close
        out.append(first - o.due)
    return out


def itls_s(win) -> list:
    """Every gap between consecutive output tokens of a request, both
    inside the window, pooled over requests."""
    out = []
    for o in win.reqs:
        t = [x for x in o.times if win.in_window(x)]
        out.extend(np.diff(t).tolist())
    return out


def tokens_in_window(win) -> int:
    return sum(1 for o in win.reqs for t in o.times if win.in_window(t))


def queue_waits_s(win) -> list:
    """Due time to admission, for every request due in the window; one not
    admitted by the close counts the time it has waited."""
    out = []
    for o in due_in_window(win):
        adm = o.admitted if o.admitted is not None else win.t_close
        out.append(min(adm, win.t_close) - o.due)
    return out


def decode_dispatches(win) -> list:
    """Dispatches of the decode program submitted inside the window: the
    program dispatched most often there (one call per scheduler tick)."""
    recs = [d for d in win.dispatches if win.in_window(d.submit)]
    if not recs:
        return []
    key, _ = Counter(d.key for d in recs).most_common(1)[0]
    return [d for d in recs if d.key == key]


def module_ms_per_call(trace, needle: str) -> float | None:
    """Mean device time per call of the programs whose name holds
    ``needle``."""
    if trace is None:
        return None
    calls = secs = 0.0
    for name, (c, s) in trace.modules.items():
        if needle in name:
            calls += c
            secs += s
    return secs / calls * 1e3 if calls else None


def idle_pct(trace) -> float | None:
    if trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
