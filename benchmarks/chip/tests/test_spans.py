"""The reduction of the program's own spans, on a hand-made trace whose
every number can be counted by hand (see the header of the data file)."""

import os

import pytest

from chipbench import spans, tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NS = 1e-9


def _serialized(name):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, name)) as f:
        txt = "".join(line for line in f if not line.startswith("#"))
    return ProfileData.text_proto_to_serialized_xspace(txt)


@pytest.fixture(scope="module")
def program(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "program.xplane.pb"
    path.write_bytes(_serialized("synthetic_program_trace.pbtxt"))
    return spans.reduce(str(path)), tracing.reduce(str(path))


def test_idle_split_by_innermost_program_span(program):
    prog, _ = program
    want = {"stream.launch": 7, "stream.wait": 6, "serve.fetch": 13,
            "serve.frames": 4, "serve.advance": 12, "serve.admit": 3,
            "serve.hook": 7, "serve.tick": 6, "none": 10}
    assert prog.idle == {k: pytest.approx(v * NS) for k, v in want.items()}


def test_buckets_sum_to_device_idle(program):
    prog, trace = program
    assert prog.window_s == pytest.approx(110 * NS)
    assert trace.busy_s == pytest.approx(42 * NS)
    assert prog.idle_s == pytest.approx(trace.window_s - trace.busy_s)


def test_per_decode_call_division(program):
    prog, trace = program
    assert spans.decode_calls(trace) == 3
    assert spans.idle_ms_per_decode_call(
        prog, trace, ("serve.fetch",)) == pytest.approx(13 * NS / 3 * 1e3)
    assert spans.idle_ms_per_decode_call(
        prog, trace, spans.LOOP_SPANS) == pytest.approx(22 * NS / 3 * 1e3)


def test_admissions_and_clock_check(program):
    prog, _ = program
    assert prog.admit_s == [pytest.approx(15 * NS)]
    assert spans.admit_ms(prog) == pytest.approx(15 * NS * 1e3)
    # two of the three decode runs lie inside a launch..wait pair
    assert prog.launch_wait_share == pytest.approx(2 / 3)


def test_trace_without_program_spans_reads_nothing(tmp_path):
    """A program older than its spans: the reduction runs, and the
    per-decode-call readings are None, not zero."""
    path = tmp_path / "bench_only.xplane.pb"
    path.write_bytes(_serialized("synthetic_trace.pbtxt"))
    prog, trace = spans.reduce(str(path)), tracing.reduce(str(path))
    assert prog.n_spans == 0 and prog.launch_wait_share is None
    assert prog.idle["none"] == pytest.approx(trace.window_s - trace.busy_s)
    assert spans.idle_ms_per_decode_call(prog, trace, ("serve.fetch",)) \
        is None
    assert spans.admit_ms(prog) is None


def test_lane_shares_from_counters():
    c = {"lane_steps_generated": 60, "lane_steps_catchup": 20,
         "lane_steps_empty": 20}
    assert spans.catchup_lane_share(c) == pytest.approx(25.0)
    assert spans.lane_occupancy(c) == pytest.approx(80.0)
    assert spans.catchup_lane_share(None) is None
    assert spans.lane_occupancy(None) is None


@pytest.mark.parametrize("runs,pairs,want", [
    ([[1, 3], [4, 9]], [[0, 5]], 0.5),        # the second outlasts its pair
    ([[1, 3], [6, 8]], [[0, 4], [5, 9]], 1.0),
    ([[1, 3]], [[2, 9]], 0.0),                # starts before the launch
    ([[1, 3]], [], None),
])
def test_inside_share(runs, pairs, want):
    assert spans._inside_share(runs, pairs) == want


def test_launch_pairs_with_the_next_wait():
    assert spans._launch_wait([[5, 6], [0, 1]], [[6, 9], [1, 4]]) \
        == [[0, 4], [5, 9]]
