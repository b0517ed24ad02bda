"""The serve loop's profiler spans and work counters.

Inside a profiler session the schedulers write host spans on the
profiler's clock (`serve.*` in `launch/scheduler.py`, `stream.*` in
`core/dispatch.py`); `ServeCounters` counts decode ticks, lane-steps,
admissions and prefill tokens. One session here covers a continuous and
an SLO run of the smoke model: every span appears, the spans pair up with
the loop iterations and dispatches they wrap, the counters equal the
lane-steps worked out from the requests' lengths and buckets, and the
token streams equal those of the same runs with no session open.
"""

import glob

import jax
import numpy as np
import pytest

from repro.core.dispatch import (AsyncExecutionStream, ExecutionStream,
                                 ProgramCache)
from repro.launch.scheduler import ServeConfig, build_scheduler

from test_serve_scheduler import PARITY_LENS, V5E, _requests, _served_model

GEN = 6
N_SLOTS = 3
SPANS = ("serve.tick", "serve.hook", "serve.admit", "serve.frames",
         "serve.fetch", "serve.advance", "stream.launch", "stream.wait")
SCHEDULES = ("continuous", "slo")


def _run(schedule, cache):
    cfg, model, params = _served_model("tinyllama-1.1b", "fp16")
    stream = (AsyncExecutionStream(cache, target=V5E) if schedule == "slo"
              else ExecutionStream(cache, target=V5E))
    sched = build_scheduler(
        ServeConfig(schedule=schedule, max_len=max(PARITY_LENS) + GEN,
                    n_slots=N_SLOTS, stream=stream), model, params, cfg)
    hooks = []
    sched.step_hook = lambda s, step: hooks.append(step)
    results = sched.run(_requests(cfg, PARITY_LENS, GEN))
    return {"results": {r.rid: r for r in results}, "sched": sched,
            "iterations": len(hooks)}


def _events(log_dir):
    """Host events of the session by name: [(start_ns, end_ns, stats)],
    with the stats of `serve.admit` alone."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats) if ev.name == "serve.admit" else {}
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.end_ns, stats))
    return out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Each schedule run once with no session and once inside one session
    (sharing its programs); each traced run inside a `test.<schedule>`
    span, so its events can be told apart."""
    caches = {s: ProgramCache() for s in SCHEDULES}
    plain = {s: _run(s, caches[s]) for s in SCHEDULES}
    log_dir = tmp_path_factory.mktemp("profile")
    traced = {}
    jax.profiler.start_trace(str(log_dir))
    try:
        for s in SCHEDULES:
            with jax.profiler.TraceAnnotation(f"test.{s}"):
                traced[s] = _run(s, caches[s])
    finally:
        jax.profiler.stop_trace()
    events = _events(log_dir)
    for s in SCHEDULES:
        (lo, hi, _), = events[f"test.{s}"]
        traced[s]["spans"] = {
            name: [e for e in events.get(name, []) if lo <= e[0] < hi]
            for name in SPANS}
    return plain, traced


def _decode_records(sched):
    keys = ({k for _, k in sched._decode_memo.values()}
            if sched.name == "continuous" else sched._decode_keys)
    return [r for r in sched.stream.records if r.key in keys]


def test_every_span_appears(runs):
    _, traced = runs
    for s in SCHEDULES:
        missing = [n for n in SPANS if not traced[s]["spans"][n]]
        assert not missing, f"{s}: no {missing} span in the session"


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_one_tick_and_hook_per_loop_iteration(runs, schedule):
    run = runs[1][schedule]
    spans = run["spans"]
    assert len(spans["serve.tick"]) == run["iterations"] > 0
    assert len(spans["serve.hook"]) == run["iterations"]


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_one_launch_per_dispatch_record(runs, schedule):
    run = runs[1][schedule]
    assert len(run["spans"]["stream.launch"]) \
        == len(run["sched"].stream.records)


def test_sync_stream_pairs_launch_and_wait(runs):
    run = runs[1]["continuous"]
    launch, wait = run["spans"]["stream.launch"], run["spans"]["stream.wait"]
    assert len(launch) == len(wait) == len(run["sched"].stream.records)
    for (ls, le, _), (ws, we, _) in zip(sorted(launch), sorted(wait)):
        assert ls <= le <= ws <= we


def test_one_fetch_per_decode_tick_inside_a_tick(runs):
    run = runs[1]["continuous"]
    fetch, ticks = run["spans"]["serve.fetch"], run["spans"]["serve.tick"]
    n = run["sched"].counters.decode_ticks
    assert len(fetch) == n == len(_decode_records(run["sched"])) > 0
    for s, e, _ in fetch:
        assert any(ts <= s and e <= te for ts, te, _ in ticks)


def test_admit_spans_carry_rid_and_bucket(runs):
    run = runs[1]["continuous"]
    admits = run["spans"]["serve.admit"]
    assert len(admits) == len(PARITY_LENS)
    for _, _, stats in admits:
        assert stats["bucket"] == run["results"][stats["rid"]].bucket


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("schedule", SCHEDULES)
def test_counters_equal_lane_steps_from_lengths(runs, schedule, traced):
    run = runs[traced][schedule]
    c = run["sched"].counters
    buckets = [run["results"][i].bucket for i in range(len(PARITY_LENS))]
    assert buckets == [16, 0, 16, 16]  # 6 tokens: below the least bucket
    catchup = sum(max(L - 1 - b, 0) for L, b in zip(PARITY_LENS, buckets))
    # a prompt prefilled whole samples its first token from the prefill
    generated = sum(GEN - (b == L) for L, b in zip(PARITY_LENS, buckets))
    assert c.admissions == len(PARITY_LENS)
    assert c.prefill_tokens == sum(buckets)
    assert c.lane_steps_catchup == catchup
    assert c.lane_steps_generated == generated
    assert c.decode_ticks == len(_decode_records(run["sched"]))
    assert c.lane_steps_empty == (c.decode_ticks * N_SLOTS
                                  - catchup - generated)
    assert run["sched"].stats(len(PARITY_LENS))["counters"]["decode_ticks"] \
        == c.decode_ticks


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_profiler_session_leaves_tokens_unchanged(runs, schedule):
    plain, traced = runs[0][schedule], runs[1][schedule]
    assert set(plain["results"]) == set(traced["results"])
    for rid, r in plain["results"].items():
        np.testing.assert_array_equal(r.tokens, traced["results"][rid].tokens)
