"""Metric readers on a hand-made window."""

import pytest

from chipbench import cli, readers, serving
from chipbench.tracing import Summary


def window():
    r = serving.ReqObs
    reqs = [
        # due before the window: not in TTFT, its in-window gaps count
        r(0, 10, 3, due=0.0, released=0.0, admitted=0.5, bucket=8,
          times=[10.5, 11.0, 11.5], tokens=[1, 2, 3]),
        # due in the window, first token 2 s later
        r(1, 20, 2, due=12.0, released=12.0, admitted=13.0, bucket=16,
          times=[14.0, 14.25], tokens=[4, 5]),
        # due in the window, never admitted: waits until the close
        r(2, 30, 5, due=15.0, released=15.0),
        # due after the window
        r(3, 30, 5, due=25.0),
    ]
    d = serving.Dispatch
    disp = [d("decode", 3, 10.1), d("prefill", 1, 10.3),
            d("decode", 1, 10.5), d("decode", 2, 14.1)]
    return serving.Window(t0=0.0, t_open=10.0, t_close=20.0, n_slots=4,
                          reqs=reqs, ticks=[10.0, 10.5, 14.0],
                          dispatches=disp, compiles_in_window=0,
                          compiles_in_fill=0)


def ctx(trace=None):
    m = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
         "d_head": 4, "d_ff": 16, "vocab": 32}
    return cli.Context(window(), trace, m, {}, 1, 42.0)


def test_ttft_counts_waits_past_the_close():
    assert sorted(readers.ttfts_s(window())) == [2.0, 5.0]
    assert cli.reader("ttft_p50_ms")(ctx()) == pytest.approx(3500.0)
    assert readers.percentile(readers.ttfts_s(window()), 90) == \
        pytest.approx(4.7)


def test_itl_pools_in_window_gaps():
    assert sorted(readers.itls_s(window())) == [0.25, 0.5, 0.5]
    assert cli.reader("itl_p50_ms")(ctx()) == pytest.approx(500.0)
    assert cli.reader("itl_p99_ms")(ctx()) == pytest.approx(500.0)


def test_setup():
    assert cli.reader("setup_s")(ctx()) == 42.0


def test_queue_wait_and_catchup():
    assert sorted(readers.queue_waits_s(window())) == [1.0, 5.0]
    # finished in the window: rid 0 (10 - 8 catch-up, 3 out) and rid 1
    want = 100.0 * (2 + 4) / (2 + 3 + 4 + 2)
    assert cli.reader("catchup_share.chat")(ctx()) == pytest.approx(want)


def test_trace_readers_read_nothing_without_a_trace():
    for name in ("decode_step_ms.chat", "device_idle.chat"):
        assert cli.reader(name)(ctx()) is None


def test_trace_readers():
    t = Summary(window_s=10.0, busy_s=7.5, devices=1,
                modules={"jit_decode_step": [100, 4.0],
                         "jit_prefill": [5, 1.0]},
                ops={}, gaps={})
    assert cli.reader("decode_step_ms.chat")(ctx(t)) == pytest.approx(40.0)
    assert cli.reader("device_idle.chat")(ctx(t)) == pytest.approx(25.0)


def test_decode_dispatches_are_the_most_frequent_program_in_window():
    got = readers.decode_dispatches(window())
    assert [(d.key, d.submit) for d in got] == [
        ("decode", 10.1), ("decode", 10.5), ("decode", 14.1)]
