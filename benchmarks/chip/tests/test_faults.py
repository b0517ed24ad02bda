"""A whole run on the CPU, the look for a chip skipped, with the timed path
sound and then broken underneath: ``correct`` must follow."""

import json
import os
import time

import pytest

from chipbench import BENCH_DIR, cli


def run(cell, seed=2 ** 35 + 1, seconds=2.0):
    got = cli.measure(cell, seed, seconds, False, time.perf_counter(),
                      dev=None, log=lambda *_: None)
    return got, cli.result_line(cell, got, False, None)


def test_sound_run_is_correct(tiny):
    got, out = run(tiny())
    assert out["correct"] is True
    assert out["checks"]["served_tokens"]["value"] >= 10
    assert set(out["metrics"]) >= {"ttft_p50_ms", "itl_p50_ms", "itl_p99_ms",
                                 "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("every", [1, 7])
def test_token_altered_where_produced_is_not_correct(tiny, monkeypatch,
                                                     every):
    """The sampler is where a served token is produced: shift some of its
    choices to another token."""
    from repro.launch import scheduler

    real = scheduler.TokenSampler.__call__
    vocab = tiny().conf["model"]["vocab"]

    def altered(self, row, rid, position):
        tok = real(self, row, rid, position)
        if position % every == 0:
            tok = (tok + vocab // 2) % vocab
        return tok

    monkeypatch.setattr(scheduler.TokenSampler, "__call__", altered)
    got, out = run(tiny())
    assert out["correct"] is False
    assert out["checks"]["widest_gap"]["value"] > \
        out["checks"]["widest_gap"]["limit"]


def test_run_without_finished_requests_is_not_correct(tiny, monkeypatch):
    from chipbench import check

    monkeypatch.setattr(check, "sample", lambda *a: [])
    got, out = run(tiny())
    assert out["correct"] is False


def cell_limits():
    with open(os.path.join(os.path.dirname(os.path.dirname(BENCH_DIR)),
                           "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    out = []
    for name in names:
        with open(os.path.join(BENCH_DIR, "checks", f"{name}.json")) as f:
            out.append(pytest.param(json.load(f)["widest_gap"], id=name))
    return out


@pytest.mark.parametrize("limit", cell_limits())
def test_token_altered_fails_each_cell_limit(tiny, monkeypatch, limit):
    """Under every cell's own limit, one served token in seven shifted
    where the sampler produces it is not correct."""
    from repro.launch import scheduler

    real = scheduler.TokenSampler.__call__
    vocab = tiny().conf["model"]["vocab"]

    def altered(self, row, rid, position):
        tok = real(self, row, rid, position)
        return (tok + vocab // 2) % vocab if position % 7 == 0 else tok

    monkeypatch.setattr(scheduler.TokenSampler, "__call__", altered)
    got, out = run(tiny(limit=limit))
    assert out["correct"] is False
    assert out["checks"]["widest_gap"]["limit"] == limit
