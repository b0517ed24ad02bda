"""The chip benchmark's harness (see `cli.py`)."""

import importlib.util
import os

# the benchmark's directory: configs/, references/, traffic/, checks/, metrics/
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_module(path: str, name: str):
    """Import the file at ``path`` as module ``name`` (metric readers and
    references are found by file name, and their names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
