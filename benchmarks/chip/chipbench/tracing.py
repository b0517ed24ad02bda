"""Profiler capture around a run, and the reduction from trace to numbers.

Capture: the JAX profiler runs from before the measured run to after it
(Python tracing off). The benchmark's own host spans go into the same
trace: ``bench.window`` (the measured window), ``bench.tick`` (one
scheduler tick, from the step hook), ``bench.execute_sync`` (around the
stream the benchmark hands in) and ``bench.sample`` (around the sampler).

Reduction (`reduce`), over the ``bench.window`` span only, per TPU plane:
  * busy: the union of the intervals of the device's ops (``XLA Ops``);
  * modules: count and total device time of each program (``XLA Modules``);
  * ops: total time per op, named by its HLO instruction name without
    numeric suffixes (``anemm``, ``convert_bitcast_fusion``), leaving out
    the containers (``while`` of the layer scan) whose ops are counted;
  * gaps: the device's idle time in the window, split by the innermost
    benchmark host span open at each moment (``sample`` inside
    ``execute_sync`` inside ``tick`` inside ``window``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re

HOST_SPANS = ("bench.sample", "bench.execute_sync", "bench.tick",
              "bench.window")


class Tracer:
    """Starts and stops the profiler; writes the benchmark's host spans."""

    def __init__(self, log_dir: str) -> None:
        self.log_dir = log_dir
        self._window = None
        self._tick = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop(self) -> str:
        import jax

        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.log_dir}")
        return found[-1]

    @staticmethod
    def span(name: str):
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)

    def open_window(self) -> None:
        self._window = self.span("bench.window")
        self._window.__enter__()

    def tick(self) -> None:
        if self._tick is not None:
            self._tick.__exit__(None, None, None)
        self._tick = self.span("bench.tick")
        self._tick.__enter__()

    def close_window(self) -> None:
        for s in (self._tick, self._window):
            if s is not None:
                s.__exit__(None, None, None)
        self._tick = self._window = None

    def wrap(self, stream) -> None:
        inner = stream.execute_sync

        def execute_sync():
            with self.span("bench.execute_sync"):
                return inner()

        stream.execute_sync = execute_sync

    @staticmethod
    def unwrap(stream) -> None:
        stream.__dict__.pop("execute_sync", None)


def null_span(_name: str):
    return contextlib.nullcontext()


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over the TPU planes
    devices: int
    modules: dict                 # name -> [calls, seconds]
    ops: dict                     # name -> seconds
    gaps: dict                    # host span -> idle seconds

    def top_ops(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.ops.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.gaps.items()),
                      key=lambda kv: -kv[1])[:n]


def _union(iv: list) -> list:
    out: list = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in iv if e > lo and s < hi]


def _length(iv: list) -> float:
    return sum(e - s for s, e in iv)


def _subtract(a: list, b: list) -> list:
    """Intervals of ``a`` not covered by ``b`` (both unions, sorted)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _intersect(a: list, b: list) -> list:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


_SUFFIX = re.compile(r"[._]\d+$")
# ops whose interval holds other ops of the same line (the layer scan)
CONTAINERS = ("while", "conditional", "call")


def op_name(ev) -> str:
    """An op's HLO instruction name without numeric suffixes: a TPU trace
    names each op by its HLO text, ``%anemm.50 = f32[...] custom-call(...)``
    (``anemm``), stable across programs and runs."""
    name = ev.name.split(" = ", 1)[0].lstrip("%").strip()
    while _SUFFIX.search(name):
        name = _SUFFIX.sub("", name)
    return name


def device_planes(pd) -> list:
    return [p for p in pd.planes
            if p.name.startswith("/device:TPU:") and "NON_CORE" not in p.name]


def reduce(path: str) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: dict = {n: [] for n in HOST_SPANS}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans:
                    spans[ev.name].append([ev.start_ns, ev.end_ns])
    if len(spans["bench.window"]) != 1:
        raise RuntimeError(f"want one bench.window span in the trace, found "
                           f"{len(spans['bench.window'])}")
    lo, hi = spans["bench.window"][0]
    host = {n: _union(_clip(v, lo, hi)) for n, v in spans.items()}

    planes = device_planes(pd)
    if not planes:
        raise RuntimeError("the trace has no TPU plane")
    busy_total, modules, ops = 0.0, {}, {}
    gaps = {n: 0.0 for n in HOST_SPANS}
    gaps["none"] = 0.0
    for plane in planes:
        busy_iv = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    if ev.end_ns <= lo or ev.start_ns >= hi:
                        continue
                    rec = modules.setdefault(ev.name, [0, 0.0])
                    rec[0] += 1
                    rec[1] += (min(ev.end_ns, hi)
                               - max(ev.start_ns, lo)) * 1e-9
            elif line.name == "XLA Ops":
                for ev in line.events:
                    if ev.end_ns <= lo or ev.start_ns >= hi:
                        continue
                    s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
                    busy_iv.append([s, e])
                    name = op_name(ev)
                    if name in CONTAINERS:
                        continue
                    ops[name] = ops.get(name, 0.0) + (e - s) * 1e-9
        busy = _union(busy_iv)
        busy_total += _length(busy) * 1e-9
        idle = _subtract([[lo, hi]], busy)
        for n in HOST_SPANS:
            covered = _intersect(idle, host[n])
            gaps[n] += _length(covered) * 1e-9
            idle = _subtract(idle, host[n])
        gaps["none"] += _length(idle) * 1e-9
    k = len(planes)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_total / k,
                   devices=k,
                   modules={n: [c / k, s / k] for n, (c, s) in modules.items()},
                   ops={n: s / k for n, s in ops.items()},
                   gaps={n: s / k for n, s in gaps.items()})


def inspect(path: str, top: int = 25) -> str:
    """A readable listing of a trace's planes, lines and busiest events,
    with their stats: for checking plane and event names by hand."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            by: dict = {}
            for ev in evs:
                rec = by.setdefault(ev.name, [0, 0, ev])
                rec[0] += 1
                rec[1] += ev.duration_ns
            for name, (c, d, ev) in sorted(by.items(),
                                           key=lambda kv: -kv[1][1])[:top]:
                stats = {k: (str(v)[:160]) for k, v in ev.stats}
                out.append(f"    {name!r}: {c} x, {d * 1e-9:.6f} s; {stats}")
    return "\n".join(out)
