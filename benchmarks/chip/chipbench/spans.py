"""The program's own host spans, on the profiler's clock: the device's idle
time split by what the serve loop was doing.

The program writes these spans itself, in any profiler session, with
`jax.profiler.TraceAnnotation` (`launch/scheduler.py`,
`core/dispatch.py`); the benchmark's ``bench.*`` spans (`tracing.py`)
stand around them. This module reads the names in `PROGRAM_SPANS` only,
over the ``bench.window`` span of a ``--trace 1`` run's trace:

  * ``idle``: per TPU plane, the device's idle time in the window (the
    window less the union of its ``XLA Ops``), each moment charged to the
    first name of `PROGRAM_SPANS` open on the host then, and to ``none``
    where none is. The order is the spans' nesting, innermost first, so
    this is the innermost span;
  * ``host``: per span name, the host time it was open in the window;
  * ``admit_s``: the durations of the ``serve.admit`` spans that start in
    the window (one admission or prefill chunk each);
  * ``launch_wait_share``: the share of the decode program's runs on the
    device (``XLA Modules`` named ``*decode_step*``) that lie between the
    start of a ``stream.launch`` and the end of the ``stream.wait`` after
    it. Near 1 it shows that host and device share one clock.

The functions after `reduce` turn these, the trace summary of `tracing`
and the program's `ServeCounters` into the quantities PERF.md reports.
"""

from __future__ import annotations

import bisect
import dataclasses
import statistics

from chipbench import tracing

PROGRAM_SPANS = ("stream.launch", "stream.wait", "serve.fetch",
                 "serve.frames", "serve.advance", "serve.admit",
                 "serve.hook", "serve.tick")
WINDOW = "bench.window"
DECODE = "decode_step"

# idle the serve loop's own Python causes: the tick less its children,
# building the frames, and the lanes' host state with sampling
LOOP_SPANS = ("serve.tick", "serve.frames", "serve.advance")


@dataclasses.dataclass
class ProgramSpans:
    window_s: float
    n_spans: int                  # program spans open in the window
    host: dict                    # span name -> seconds open in the window
    idle: dict                    # span name or "none" -> idle seconds
    admit_s: list                 # serve.admit durations in the window
    launch_wait_share: float | None

    @property
    def idle_s(self) -> float:
        return sum(self.idle.values())


def _launch_wait(launch: list, wait: list) -> list:
    """[start of each stream.launch, end of the first stream.wait that
    starts after it ends], sorted."""
    wait = sorted(wait)
    starts = [s for s, _ in wait]
    out = []
    for s, e in sorted(launch):
        k = bisect.bisect_left(starts, e)
        if k < len(wait):
            out.append([s, wait[k][1]])
    return out


def _inside_share(runs: list, pairs: list) -> float | None:
    """Share of ``runs`` that lie inside some interval of ``pairs``."""
    if not runs or not pairs:
        return None
    starts = [s for s, _ in pairs]
    inside = 0
    for s, e in runs:
        k = bisect.bisect_right(starts, s) - 1
        inside += k >= 0 and pairs[k][1] >= e
    return inside / len(runs)


def reduce(path: str) -> ProgramSpans:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: dict = {n: [] for n in PROGRAM_SPANS + (WINDOW,)}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans:
                    spans[ev.name].append([ev.start_ns, ev.end_ns])
    if len(spans[WINDOW]) != 1:
        raise RuntimeError(f"want one {WINDOW} span in the trace, found "
                           f"{len(spans[WINDOW])}")
    lo, hi = spans[WINDOW][0]
    host = {n: tracing._union(tracing._clip(spans[n], lo, hi))
            for n in PROGRAM_SPANS}
    planes = tracing.device_planes(pd)
    if not planes:
        raise RuntimeError("the trace has no TPU plane")
    idle_by = {n: 0.0 for n in PROGRAM_SPANS + ("none",)}
    runs = []
    for plane in planes:
        busy = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                busy.extend([max(ev.start_ns, lo), min(ev.end_ns, hi)]
                            for ev in line.events
                            if ev.end_ns > lo and ev.start_ns < hi)
            elif line.name == "XLA Modules":
                runs.extend([ev.start_ns, ev.end_ns] for ev in line.events
                            if DECODE in ev.name
                            and lo <= ev.start_ns < hi)
        idle = tracing._subtract([[lo, hi]], tracing._union(busy))
        for n in PROGRAM_SPANS:
            idle_by[n] += tracing._length(
                tracing._intersect(idle, host[n])) * 1e-9
            idle = tracing._subtract(idle, host[n])
        idle_by["none"] += tracing._length(idle) * 1e-9
    k = len(planes)
    pairs = _launch_wait(spans["stream.launch"], spans["stream.wait"])
    return ProgramSpans(
        window_s=(hi - lo) * 1e-9,
        n_spans=sum(len(v) for v in host.values()),
        host={n: tracing._length(v) * 1e-9 for n, v in host.items()},
        idle={n: s / k for n, s in idle_by.items()},
        admit_s=[(e - s) * 1e-9 for s, e in spans["serve.admit"]
                 if lo <= s < hi],
        launch_wait_share=_inside_share(sorted(runs), pairs))


def decode_calls(trace) -> float:
    """Runs of the decode program in the window (`tracing.Summary`)."""
    return sum(c for name, (c, _) in trace.modules.items() if DECODE in name)


def idle_ms_per_decode_call(prog: ProgramSpans, trace,
                            names: tuple) -> float | None:
    """Idle milliseconds under the spans ``names``, per decode call; None
    where the program wrote no span (a program older than its spans)."""
    n = decode_calls(trace)
    if not n or not prog.n_spans:
        return None
    return sum(prog.idle[x] for x in names) / n * 1e3


def admit_ms(prog: ProgramSpans) -> float | None:
    """Median duration of a ``serve.admit`` span, in milliseconds."""
    return statistics.median(prog.admit_s) * 1e3 if prog.admit_s else None


def catchup_lane_share(counters: dict | None) -> float | None:
    """Teacher-forced lane-steps over working lane-steps, in %."""
    if not counters:
        return None
    work = counters["lane_steps_generated"] + counters["lane_steps_catchup"]
    return 100.0 * counters["lane_steps_catchup"] / work if work else None


def lane_occupancy(counters: dict | None) -> float | None:
    """Working lane-steps over all lane-steps of the decode ticks, in %."""
    if not counters:
        return None
    work = counters["lane_steps_generated"] + counters["lane_steps_catchup"]
    total = work + counters["lane_steps_empty"]
    return 100.0 * work / total if total else None
