"""The program's parameters come from the configuration's reference module:
`serving.program_params` asks it for every leaf of the program's tree by
path, and names the leaves it cannot fill."""

import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import BENCH_DIR, check, cli, load_module, serving, weights

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(os.path.dirname(BENCH_DIR)), "src")
TINY_PHI4 = {"n_layers": 2, "d_model": 256, "n_heads": 4, "n_kv_heads": 2,
             "d_head": 64, "d_ff": 512, "vocab": 1024, "norm": "rmsnorm",
             "act": "silu", "tie_embeddings": True, "rope_theta": 10000.0,
             "dtype": "bfloat16"}


def program(arch: str, **sizes):
    from repro import configs
    from repro.models.model import build_model

    cfg = dataclasses.replace(configs.get_config(arch), **sizes)
    return build_model(cfg), cfg


def digest(params) -> str:
    from repro.kernels import compat

    h = hashlib.sha256()
    for p, leaf in compat.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(f"{compat.tree_path_str(p)}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def stub():
    return load_module(os.path.join(HERE, "data", "deepseek_v3_stub.py"),
                       "deepseek_v3_stub")


@pytest.mark.parametrize("seed, expected", [
    (7, "f8fd6bf53a63871e3988e49a9b58816fc9b66d75cbfb4bf5613873121d9e7de9"),
    (2 ** 40 + 9,
     "e5dc4e8c68d9453d47a56b61ed1507978e3ac4860714446359b7fbc856b3900b"),
])
def test_dense_parameters_equal_the_recorded_digest(seed, expected):
    """A phi4-shaped program gets the same parameters, bit for bit, as the
    harness gave it before the layout moved into the reference module
    (digests recorded from that harness at these two seeds)."""
    model, _ = program("phi4-mini-3.8b", **TINY_PHI4)
    dense = check.load_reference(BENCH_DIR, "dense_gqa")
    assert digest(serving.program_params(model, dense, TINY_PHI4,
                                         seed)) == expected


def test_untied_cell_is_correct_end_to_end(tiny):
    """tinyllama's program has an untied (d, V) head: the dense module
    fills ``embed/unembed`` and its reference reads the same leaf."""
    cell = tiny(arch="tinyllama-1.1b", tied=False)
    got = cli.measure(cell, 2 ** 37 + 3, 2.0, False, time.perf_counter(),
                      dev=None, log=lambda *_: None)
    out = cli.result_line(cell, got, False, None)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["served_tokens"]["value"] >= 10


def deepseek_smoke():
    from repro import configs
    from repro.models.model import build_model

    cfg = configs.get_smoke("deepseek-v3-671b")
    return build_model(cfg), cfg


def test_foreign_layout_is_filled_by_its_module():
    """A module of another architecture fills DeepSeek-V3's tree (dense
    and MoE layer groups, expert banks, MTP, untied head) with no edit to
    the harness."""
    model, cfg = deepseek_smoke()
    params = serving.program_params(model, stub(), dataclasses.asdict(cfg),
                                    11)
    from repro.kernels import compat

    paths = {compat.tree_path_str(p): leaf for p, leaf in
             compat.tree_flatten_with_path(params)[0]}
    assert {"layers/0/sub0/mix/wq_a", "layers/1/sub0/moe/wg",
            "layers/1/sub0/moe/shared/wd", "mtp/proj",
            "embed/unembed"} <= set(paths)
    bank = np.asarray(paths["layers/1/sub0/moe/wg"])
    assert bank.shape == (cfg.n_layers - cfg.n_dense_layers, cfg.n_experts,
                          cfg.d_model, cfg.d_ff_expert)
    # every expert and every layer drew numbers of its own
    assert not np.array_equal(bank[0, 0], bank[0, 1])
    assert not np.array_equal(bank[0, 0], bank[1, 0])


@pytest.mark.parametrize("table, name, path", [
    ("LAYER_LEAVES", "moe/router", "layers/1/sub0/moe/router"),
    ("LAYER_LEAVES", "mix/wkv_b", "layers/0/sub0/mix/wkv_b"),
    ("OUTER_LEAVES", "mtp/proj", "mtp/proj"),
    ("OUTER_LEAVES", "embed/unembed", "embed/unembed"),
])
def test_a_leaf_the_module_lacks_is_named(table, name, path):
    model, cfg = deepseek_smoke()
    mod = stub()
    del getattr(mod, table)[name]
    with pytest.raises(ValueError, match=f"{path} "):
        serving.program_params(model, mod, dataclasses.asdict(cfg), 11)


def test_a_leaf_of_the_wrong_dtype_is_refused():
    model, _ = program("phi4-mini-3.8b", **TINY_PHI4)
    dense = check.load_reference(BENCH_DIR, "dense_gqa")
    wide = types.SimpleNamespace(
        __name__="wide", program_leaf=lambda *a: dense.program_leaf(
            *a).astype(jnp.float32))
    with pytest.raises(ValueError, match="embed/table as float32"):
        serving.program_params(model, wide, TINY_PHI4, 1)


def test_a_share_of_the_experts_is_a_slice_of_the_uncut_bank():
    """By the key rule, the experts held on one chip draw the numbers of
    the same experts of the uncut model."""
    mod = stub()
    m = {"n_layers": 4, "n_dense_layers": 1, "dtype": "float32"}
    key = weights.root_key(2 ** 41 + 1)
    path = "layers/1/sub0/moe/wd"
    whole = mod.program_leaf(key, m, path, (3, 8, 16, 24))
    share = mod.program_leaf(key, dict(m, expert_first=4), path,
                             (3, 2, 16, 24))
    assert np.array_equal(np.asarray(share), np.asarray(whole[:, 4:6]))


def test_server_refuses_more_than_one_chip(tiny):
    with pytest.raises(ValueError, match="asks for 4 chips"):
        serving.Server(tiny().conf, 1, 4)



RUN_CELL = """
import json, sys, time
from chipbench import cli
cell = cli.Cell(**json.loads(sys.argv[1]))
got = cli.measure(cell, 2 ** 37 + 5, 2.0, False, time.perf_counter(),
                  dev=None, log=lambda *_: None)
out = cli.result_line(cell, got, False, None)
print(json.dumps({"correct": out["correct"], "checks": out["checks"]}))
"""


def test_a_new_reference_file_is_found_by_name(tmp_path, tiny):
    """A configuration that names a reference module which is a new file
    under ``references/`` gets its parameters built and its output checked
    by that file, in a copy of the benchmark where no harness file is
    edited. The module draws other weights than ``dense_gqa``, so the run
    is correct only where both sides took them from it."""
    bench = tmp_path / "chip"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(os.path.join(HERE, "data", "dense_by_rule.py"),
                bench / "references" / "dense_by_rule.py")
    by_rule = load_module(str(bench / "references" / "dense_by_rule.py"),
                          "dense_by_rule")
    dense = check.load_reference(BENCH_DIR, "dense_gqa")
    key = weights.root_key(2 ** 37 + 5)
    assert not np.array_equal(
        np.asarray(by_rule.outer(key, TINY_PHI4)["embed"], np.float32),
        np.asarray(dense.outer(key, TINY_PHI4)["embed"], np.float32))
    cell = tiny()
    cell.conf["reference"] = "dense_by_rule"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(bench), SRC]))
    r = subprocess.run([sys.executable, "-c", RUN_CELL,
                        json.dumps(dataclasses.asdict(cell))],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["served_tokens"]["value"] >= 10
