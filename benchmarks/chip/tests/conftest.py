"""Tests of the chip benchmark's own code, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import json  # noqa: E402

import pytest  # noqa: E402


def tiny_cell(limit: float | None = None, vocab: int = 1024,
              arch: str = "phi4-mini-3.8b", tied: bool = True):
    """A phi4-shaped cell small enough for the CPU (kernels interpreted),
    held to the phi4 chat cell's limit unless told otherwise; ``arch``
    names the program's configuration the sizes are laid over."""
    from chipbench import cli

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "checks", "phi4-chat-steady.json")) as f:
        limits = json.load(f)
    if limit is not None:
        limits = {"widest_gap": limit}
    conf = {"name": "tiny", "program_arch": arch,
            "reference": "dense_gqa",
            "model": {"n_layers": 2, "d_model": 256, "n_heads": 4,
                      "n_kv_heads": 2, "d_head": 64, "d_ff": 512,
                      "vocab": vocab, "norm": "rmsnorm", "act": "silu",
                      "tie_embeddings": tied, "rope_theta": 10000.0,
                      "dtype": "bfloat16"},
            "serving": {"n_slots": 4, "max_len": 64,
                        "schedule": "continuous", "sampling": "greedy"}}
    mix = {"arrival": {"kind": "poisson", "rate_per_s": 6.0}, "fill_s": 1.0,
           "window_parts": 2,
           "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                          "min": 4, "max": 40},
           "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                          "min": 2, "max": 24},
           "check_requests": 4}
    return cli.Cell("tiny", 1, conf, mix, limits, bench["end_to_end"],
                    bench["per_layer"])


@pytest.fixture
def tiny():
    return tiny_cell
