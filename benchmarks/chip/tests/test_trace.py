"""The reduction from trace to numbers, on a hand-made trace whose every
number can be counted by hand (see the header of the data file)."""

import os

import pytest

from chipbench import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NS = 1e-9


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "synthetic_trace.pbtxt")) as f:
        txt = "".join(line for line in f if not line.startswith("#"))
    path = tmp_path_factory.mktemp("trace") / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(txt))
    return tracing.reduce(str(path))


def test_window_and_busy(synthetic):
    assert synthetic.window_s == pytest.approx(100 * NS)
    # [0,2] + [10,25] + [60,75] + [95,100], ops clipped to the window
    assert synthetic.busy_s == pytest.approx(37 * NS)
    assert synthetic.devices == 1


def test_idle_split_by_innermost_host_span(synthetic):
    g = synthetic.gaps
    assert g["bench.sample"] == pytest.approx(16 * NS)
    assert g["bench.execute_sync"] == pytest.approx(20 * NS)
    assert g["bench.tick"] == pytest.approx(27 * NS)
    assert g["bench.window"] == pytest.approx(0.0)
    assert g["none"] == pytest.approx(0.0)
    assert sum(g.values()) == pytest.approx(synthetic.window_s
                                            - synthetic.busy_s)


def test_modules_and_ops(synthetic):
    calls, secs = synthetic.modules["jit_decode_step(17)"]
    assert (calls, secs) == (2, pytest.approx(30 * NS))
    assert synthetic.modules["jit_prefill(3)"][1] == pytest.approx(5 * NS)
    ops = synthetic.ops
    assert set(ops) == {"fusion", "anemm", "dot", "copy"}   # no "while"
    assert ops["fusion"] == pytest.approx(25 * NS)
    assert ops["anemm"] == pytest.approx(10 * NS)
    assert ops["dot"] == pytest.approx(5 * NS)
    assert ops["copy"] == pytest.approx(2 * NS)
    assert synthetic.top_ops(1) == [["fusion", pytest.approx(25 * NS)]]


@pytest.mark.parametrize("a,b,want", [
    ([[0, 10]], [[2, 3], [5, 6]], [[0, 2], [3, 5], [6, 10]]),
    ([[0, 10], [20, 30]], [[5, 25]], [[0, 5], [25, 30]]),
    ([[0, 10]], [], [[0, 10]]),
    ([[0, 10]], [[-5, 15]], []),
])
def test_subtract(a, b, want):
    assert tracing._subtract(a, b) == want


def test_union_and_intersect():
    assert tracing._union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3],
                                                                [5, 8]]
    assert tracing._intersect([[0, 5], [8, 12]], [[3, 9]]) == [[3, 5],
                                                               [8, 9]]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace recorded on a TPU v5e (`v5e_tiny_serve.xplane.pb.gz`): a
    2-layer, 256-wide model of the phi4 shape served through the benchmark
    for a 0.3 s window, with the benchmark's host spans."""
    import gzip

    with gzip.open(os.path.join(DATA, "v5e_tiny_serve.xplane.pb.gz")) as f:
        raw = f.read()
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    path.write_bytes(raw)
    return tracing.reduce(str(path))


def test_recorded_tpu_trace(recorded):
    r = recorded
    assert r.devices == 1
    assert r.window_s == pytest.approx(0.300546772)
    assert r.busy_s == pytest.approx(0.00158763)
    (calls, secs), = [v for k, v in r.modules.items()
                      if k.startswith("jit_decode_step")]
    assert calls == 49 and secs == pytest.approx(0.0013516)
    assert {"anemm", "decode_attention", "flash_attention"} <= set(r.ops)
    assert "while" not in r.ops
    assert sum(r.gaps.values()) == pytest.approx(r.window_s - r.busy_s)
    assert r.gaps["bench.tick"] > r.gaps["bench.sample"] > 0
