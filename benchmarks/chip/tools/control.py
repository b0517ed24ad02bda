#!/usr/bin/env python3
"""Readings of the comparison that decides ``correct``: the program's and
the fp8 control's, on the chip, at the cell's own size and load.

    python3 benchmarks/chip/tools/control.py --workload phi4-chat-steady \\
        --seeds 101,102,103 --seconds 30

One process: for each seed, a run of the cell (weights, traffic and sample
from that seed; a window of ``--seconds``), then the reference over the
run's sample twice: in fp32 for the served tokens (the program's reading),
and with every matmul operand in fp8 for the control's first choices (the
control's reading), each judged by `chipbench.check.judge` under the
limit in ``checks/<cell>.json``. Prints one JSON line per seed, and exits
nonzero if a program run is not correct or a control run is. The limit is
set between the largest program reading and the smallest control reading
(see PERF.md).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import cli, device  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    args = ap.parse_args()
    cell = cli.load_cell(args.workload)
    cli.configure_jax()
    dev = device.require_tpu(cell.chips)
    failures = 0
    for seed in [int(s) for s in args.seeds.split(",")]:
        got = cli.measure(cell, seed, args.seconds, False,
                          time.perf_counter(), dev=dev,
                          log=lambda *_: None, control=True)
        verdict = got["verdict"]
        gaps, ctl = verdict["gaps"], verdict["control"]
        failures += (not verdict["correct"]) + ctl["correct"]
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "served_tokens": int(gaps["program"].size),
            "program_widest_gap": float(gaps["program"].max()),
            "correct": verdict["correct"],
            "control_widest_gap": float(gaps["control"].max()),
            "control_median_gap": float(np.median(gaps["control"])),
            "control_correct": ctl["correct"],
            "limit": cell.limits["widest_gap"],
            "memory_peak_bytes": got["memory_peak_bytes"],
        }), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
