import json
import os
import subprocess
import sys

import pytest

from chipbench import device

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))


def test_v5e_peaks_from_the_table():
    p = device.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(device.NoChip, match="TPU v99"):
        device.peak("TPU v99")


def test_cpu_is_refused_by_name():
    with pytest.raises(device.NoChip, match="no TPU: JAX runs on cpu"):
        device.require_tpu(1)


def workloads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("workload", workloads())
def test_harness_exits_nonzero_without_a_chip(workload):
    """No TPU: a nonzero exit naming the device, and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", str(2 ** 40), "--seconds", "51", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr and "cpu" in r.stderr


def test_benchmark_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           f"{w['traffic']}.json"))
        assert os.path.exists(os.path.join(BENCH, "checks",
                                           f"{w['name']}.json"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py"))
