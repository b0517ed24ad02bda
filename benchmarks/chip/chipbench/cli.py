"""One run of one cell: set up, measure, check, print one JSON line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``:
``configs/<config>.json`` (model sizes, serving shape, reference),
``traffic/<traffic>.json`` (the mix), ``metrics/<metric>.py`` (one reader
per metric) and ``checks/<cell>.json`` (the limits of the comparison).
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import tempfile

from chipbench import BENCH_DIR, device, load_module, traffic

ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    conf = load_json(ROOT, conf_entry["file"])
    mix = load_json(BENCH_DIR, "traffic", f"{w['traffic']}.json")
    limits = load_json(BENCH_DIR, "checks", f"{name}.json")

    def applies(metric):
        return name in metric.get("workloads", [name])

    return Cell(name, w["chips"], conf, mix, limits,
                [x for x in bench["end_to_end"] if applies(x)],
                [x for x in bench["per_layer"] if applies(x)])


def reader(metric: str):
    path = os.path.join(BENCH_DIR, "metrics", f"{metric}.py")
    return load_module(path, "metric_" + metric.replace(".", "_")).read


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    win: object
    trace: object
    model: dict
    peak: dict
    chips: int
    setup_s: float


def configure_jax() -> None:
    """Persistent compile cache at a fixed path inside the checkout, for
    every program, before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float, dev=None, log=print, control: bool = False) -> dict:
    """Set up, run the window, read the device, free the program, check.
    ``dev`` None skips the look for a chip (for tests on the CPU);
    ``control`` also reads the fp8 control on the same sample."""
    import jax

    from chipbench import check, serving, tracing

    compile_log = serving.CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compile_log)
    specs = traffic.generate(cell.mix, cell.conf["model"]["vocab"], seed,
                             seconds)
    server = serving.Server(cell.conf, seed, cell.chips)
    warm = server.warm_up([len(s.prompt) for s in specs])
    log(f"warm-up prompt lengths {warm}")
    summary = None
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tmp:
        tracer = tracing.Tracer(tmp) if trace else None
        if tracer is not None:
            tracer.start()
        win = server.serve(specs, cell.mix["fill_s"], seconds, tracer,
                           compile_log)
        if tracer is not None:
            path = tracer.stop()
            summary = tracing.reduce(path)
    if win.compiles_in_window:
        raise RuntimeError(f"{win.compiles_in_window} compile(s) inside the "
                           f"window: warm-up missed a shape")
    setup_s = win.t_open - t_start
    mem = device.memory_peak_bytes(jax.devices()[:cell.chips]) \
        if dev is not None else None
    server.free()
    del server
    gc.collect()
    verdict = check.compare(BENCH_DIR, cell.conf, win, seed,
                            cell.mix["check_requests"], cell.limits,
                            control=control)
    return {"win": win, "trace": summary, "setup_s": setup_s,
            "memory_peak_bytes": mem, "verdict": verdict}


def result_line(cell: Cell, got: dict, trace: bool, dev) -> dict:
    kind = dev.device_kind if dev is not None else "none"
    peak = device.PEAKS.get(kind, {})
    ctx = Context(got["win"], got["trace"], cell.conf["model"], peak,
                  cell.chips, got["setup_s"])
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    win = got["win"]
    dev_out = (device.describe(dev, cell.chips) if dev is not None
               else {"platform": "cpu", "kind": "none", "count": 0})
    dev_out["memory_peak_bytes"] = got["memory_peak_bytes"]
    out = {"correct": got["verdict"]["correct"],
           "attempted": sum(1 for o in win.reqs if o.due < win.t_close),
           "failed": got["verdict"]["numbers"]["bad_requests"]["value"],
           "metrics": metrics, "device": dev_out}
    if trace and got["trace"] is not None:
        t = got["trace"]
        dev_out["busy_s"] = t.busy_s
        dev_out["window_s"] = t.window_s
        out["breakdown"] = {"device_ops": t.top_ops(10),
                            "idle_gaps": t.top_gaps(10)}
    out["checks"] = got["verdict"]["numbers"]
    return out


def check_lines(numbers: dict) -> list:
    return [f"check {k}: {v['value']} {v['rule']} {v['limit']}"
            for k, v in numbers.items()]


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    configure_jax()
    try:
        dev = device.require_tpu(cell.chips)
    except device.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3

    def log(msg):
        print(f"chipbench: {msg}", file=sys.stderr, flush=True)

    got = measure(cell, args.seed, args.seconds, bool(args.trace), t_start,
                  dev=dev, log=log)
    out = result_line(cell, got, bool(args.trace), dev)
    win = got["win"]
    log(f"{len(win.reqs)} requests drawn, {out['attempted']} due, "
        f"{sum(o.finished for o in win.reqs)} finished; compiles in fill "
        f"{win.compiles_in_fill}, in window {win.compiles_in_window}")
    for line in check_lines(out["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
