#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of BENCHMARK.json on the chip(s) of
this machine.

    python3 benchmarks/chip/run.py --workload phi4-chat-steady \\
        --seed 123456789012 --seconds 51 --trace 0

Serves the cell's traffic through the program's normal serving path, open
loop, measures ``--seconds`` after the lanes have filled, checks the served
tokens against the configuration's plain reference, and prints one JSON
line last on standard output. With no TPU, or fewer chips than the cell
asks for, it exits nonzero and prints no result. See ``chipbench/cli.py``.
"""

import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import cli  # noqa: E402

if __name__ == "__main__":
    sys.exit(cli.main(sys.argv[1:], T_START))
