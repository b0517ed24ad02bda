"""The control: the reference in fp8 put in the program's place. The
harness judges it by the same rule and limit as the program, and it has
to come out as not correct."""

import json
import os
import time

import numpy as np

from chipbench import check, cli

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_fp8_control_reads_far_above_the_program(tiny):
    """Through a whole run at a size the CPU holds: the control's first
    choices lie much further below the fp32 reference's best than the
    served tokens do, and the harness judges the control under the
    program's own rule and limit."""
    cell = tiny()
    ratios = []
    for seed in (3, 2 ** 36 + 5):
        got = cli.measure(cell, seed, 2.0, False, time.perf_counter(),
                          dev=None, log=lambda *_: None, control=True)
        verdict = got["verdict"]
        gaps, ctl = verdict["gaps"], verdict["control"]
        prog, widest = float(gaps["program"].max()), float(
            gaps["control"].max())
        assert gaps["control"].size == gaps["program"].size >= 10
        assert verdict["correct"] is True
        assert ctl["numbers"]["widest_gap"] == {
            "value": widest, "limit": cell.limits["widest_gap"],
            "rule": "<="}
        assert ctl["correct"] is (widest <= cell.limits["widest_gap"])
        ratios.append(widest / max(prog, 1e-6))
    assert min(ratios) >= 3.0, ratios


def control_fails(limits: dict, m: dict, batch: int, seq: int,
                  prompt: int) -> None:
    ref = check.load_reference(BENCH, "dense_gqa").Reference(m, batch, seq)
    for seed in (1, 2, 2 ** 40 + 3):
        rng = np.random.default_rng(seed)
        seqs = [rng.integers(0, m["vocab"], seq).astype(np.int32)
                for _ in range(batch)]
        gaps = ref.gaps(seed, seqs, [prompt] * batch, control=True)
        got = check.judge(gaps["control"], limits)
        assert got["numbers"]["served_tokens"]["value"] == \
            batch * (seq - prompt)
        assert got["correct"] is False, got["numbers"]


def test_fp8_control_fails_the_cell_limit():
    """At 16 layers of 1024 wide, 8192 tokens and 448 compared positions
    (the chip cell compares some thousands at 32 layers of 3072), the
    control is not correct under the limit of ``checks/phi4-chat-steady``
    on every seed."""
    with open(os.path.join(BENCH, "checks", "phi4-chat-steady.json")) as f:
        limits = json.load(f)
    m = {"n_layers": 16, "d_model": 1024, "n_heads": 8, "n_kv_heads": 2,
         "d_head": 128, "d_ff": 2730, "vocab": 8192,
         "rope_theta": 10000.0, "tie_embeddings": True}
    control_fails(limits, m, batch=4, seq=128, prompt=16)


def test_fp8_control_fails_the_granite_cell_limit():
    """At 9 layers of 1024 wide with granite's ratios (4 query heads to a
    key/value head, FFN 3.5 x wide), 4096 tokens and 448 compared
    positions (the chip cell compares some thousands at 18 layers of
    4096), the control is not correct under the limit of
    ``checks/granite-chat-steady`` on every seed."""
    with open(os.path.join(BENCH, "checks", "granite-chat-steady.json")) as f:
        limits = json.load(f)
    m = {"n_layers": 9, "d_model": 1024, "n_heads": 8, "n_kv_heads": 2,
         "d_head": 128, "d_ff": 3584, "vocab": 4096,
         "rope_theta": 10000.0, "tie_embeddings": True}
    control_fails(limits, m, batch=4, seq=128, prompt=16)
