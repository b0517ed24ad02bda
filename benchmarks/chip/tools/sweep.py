#!/usr/bin/env python3
"""Find the highest arrival rate a chat cell's configuration sustains.

    python3 benchmarks/chip/tools/sweep.py --workload phi4-chat-steady \\
        --rates 1.5,2,2.5,3 --seconds 30 --seed 7

One process: builds the program once, then serves the cell's mix at each
rate for ``--seconds`` after its fill, and prints per rate one JSON line:
requests released and admitted, the queue (released, not yet admitted) in
the first and last thirds of the window, and TTFT percentiles. A rate is
sustained when the queue does not grow across the window. The cell's
traffic file then carries 0.8 x the highest sustained rate as a number.
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import cli, device, readers, serving, traffic  # noqa: E402


def queue_len(win, t: float) -> int:
    return sum(1 for o in win.reqs if o.released is not None
               and o.released <= t and (o.admitted is None
                                        or o.admitted > t))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    cell = cli.load_cell(args.workload)
    cli.configure_jax()
    dev = device.require_tpu(cell.chips)
    server = serving.Server(cell.conf, args.seed, cell.chips)
    runs = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.mix, arrival=dict(cell.mix["arrival"],
                                          rate_per_s=rate))
        runs.append((rate, mix, traffic.generate(
            mix, cell.conf["model"]["vocab"], args.seed, args.seconds)))
    server.warm_up([len(s.prompt) for _, _, specs in runs for s in specs])
    for rate, mix, specs in runs:
        win = server.serve(specs, mix["fill_s"], args.seconds)
        span = win.seconds / 3
        early = [queue_len(win, t) for t in win.ticks
                 if win.t_open <= t < win.t_open + span]
        late = [queue_len(win, t) for t in win.ticks
                if win.t_close - span <= t < win.t_close]
        ttft = readers.ttfts_s(win)
        steps = readers.decode_dispatches(win)
        print(json.dumps({
            "rate_per_s": rate,
            "released": sum(o.released is not None for o in win.reqs),
            "admitted": sum(o.admitted is not None for o in win.reqs),
            "queue_early": sum(early) / max(len(early), 1),
            "queue_late": sum(late) / max(len(late), 1),
            "ttft_p50_ms": readers.percentile(ttft, 50) * 1e3,
            "ttft_p90_ms": readers.percentile(ttft, 90) * 1e3,
            "tick_ms": 1e3 * win.seconds / max(len(steps), 1),
            "output_tok_s": readers.tokens_in_window(win) / win.seconds,
            "memory_peak_bytes": device.memory_peak_bytes([dev]),
            "bytes_limit": (dev.memory_stats() or {}).get("bytes_limit"),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
