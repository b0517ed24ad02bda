#!/usr/bin/env python3
"""The program's own spans and counters over the traced window of a cell,
on the chip.

    python3 benchmarks/chip/tools/program_spans.py \\
        --workload phi4-chat-steady --seeds 101,102,103 [--seconds 51] \\
        [--out spans.jsonl]

For each seed, in one process: a run of the cell as ``run.py --trace 1``
makes it (the same set-up, fill, window, profiler capture and check),
with the trace reduced twice, by `chipbench.tracing` as the benchmark
does and by `chipbench.spans` for the program's own spans, and the
program's `ServeCounters` read at the window's open and close. Prints one
JSON line per seed (and appends it to ``--out``): the run's ``--trace 1``
result line, the end-to-end metrics read from the traced window (what the
spans cost when they are recorded), and the program's readings. A program
older than its spans and counters runs too; those readings are then null.
"""

import argparse
import dataclasses
import gc
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import (check, cli, device, serving, spans,  # noqa: E402
                       tracing, traffic)


class WindowTracer(tracing.Tracer):
    """The benchmark's tracer, also reading the scheduler's counters when
    the window opens and when it closes."""

    def __init__(self, log_dir: str, server) -> None:
        super().__init__(log_dir)
        self.server = server
        self.snaps = []

    def _snap(self) -> None:
        c = getattr(self.server.sched, "counters", None)
        self.snaps.append(None if c is None else dataclasses.asdict(c))

    def open_window(self) -> None:
        super().open_window()
        self._snap()

    def close_window(self) -> None:
        self._snap()
        super().close_window()

    def counters(self) -> dict | None:
        a, b = self.snaps
        return None if a is None else {k: b[k] - a[k] for k in a}


def measure(cell, seed: int, seconds: float, dev) -> dict:
    import jax

    t_start = time.perf_counter()
    compile_log = serving.CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compile_log)
    specs = traffic.generate(cell.mix, cell.conf["model"]["vocab"], seed,
                             seconds)
    server = serving.Server(cell.conf, seed, cell.chips)
    server.warm_up([len(s.prompt) for s in specs])
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tmp:
        tracer = WindowTracer(tmp, server)
        tracer.start()
        win = server.serve(specs, cell.mix["fill_s"], seconds, tracer,
                           compile_log)
        path = tracer.stop()
        summary = tracing.reduce(path)
        prog = spans.reduce(path)
    if win.compiles_in_window:
        raise RuntimeError(f"{win.compiles_in_window} compile(s) inside the "
                           f"window")
    setup_s = win.t_open - t_start
    mem = device.memory_peak_bytes(jax.devices()[:cell.chips])
    server.free()
    del server
    gc.collect()
    verdict = check.compare(cli.BENCH_DIR, cell.conf, win, seed,
                            cell.mix["check_requests"], cell.limits)
    got = {"win": win, "trace": summary, "setup_s": setup_s,
           "memory_peak_bytes": mem, "verdict": verdict}
    line = cli.result_line(cell, got, True, dev)
    ctx = cli.Context(win, summary, cell.conf["model"], {}, cell.chips,
                      setup_s)
    traced_e2e = {m["name"]: cli.reader(m["name"])(ctx)
                  for m in cell.end_to_end if m["name"] != "setup_s"}
    counters = tracer.counters()

    def per_call(*names):
        return spans.idle_ms_per_decode_call(prog, summary, names)

    calls = spans.decode_calls(summary)
    program = {
        "idle_fetch_ms": per_call("serve.fetch"),
        "idle_launch_ms": per_call("stream.launch"),
        "idle_sched_ms": per_call(*spans.LOOP_SPANS),
        "idle_hook_ms": per_call("serve.hook"),
        "admit_ms": spans.admit_ms(prog),
        "catchup_lane_share": spans.catchup_lane_share(counters),
        "lane_occupancy": spans.lane_occupancy(counters),
        "idle_ms_per_decode_call": {n: per_call(n) for n in prog.idle},
        "host_ms_per_decode_call": (
            {n: v / calls * 1e3 for n, v in prog.host.items()}
            if calls and prog.n_spans else None),
        "idle_s": prog.idle,
        "idle_split_minus_device_idle_of_window": (
            (prog.idle_s - (summary.window_s - summary.busy_s))
            / summary.window_s),
        "launch_wait_share": prog.launch_wait_share,
        "decode_calls": calls,
        "admissions_in_window": len(prog.admit_s),
        "counters": counters,
    }
    return {"workload": cell.name, "seed": seed, "correct": line["correct"],
            "traced_end_to_end": traced_e2e, "program": program,
            "line": line}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    bench = cli.load_json(ROOT, "BENCHMARK.json")
    cell = cli.load_cell(args.workload, bench)
    seconds = args.seconds or bench["run_seconds"]
    cli.configure_jax()
    dev = device.require_tpu(cell.chips)
    bad = 0
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = json.dumps(measure(cell, seed, seconds, dev))
        bad += not json.loads(out)["correct"]
        print(out, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(out + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
