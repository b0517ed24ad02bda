import json
import os

import numpy as np
import pytest

from chipbench import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))


def load(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    m = load(mix)
    a = traffic.generate(m, 1000, 2 ** 40 + 3, 51)
    b = traffic.generate(m, 1000, 2 ** 40 + 3, 51)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due_s) == (y.max_new, y.due_s)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_the_work(mix):
    """Every seed draws the same multiset of sizes and gaps, in its own
    order, with its own token ids."""
    m = load(mix)
    a = traffic.generate(m, 1000, 1, 51)
    b = traffic.generate(m, 1000, 2, 51)
    for part in (lambda x: x.due_s < m["fill_s"],
                 lambda x: x.due_s >= m["fill_s"]):
        pa, pb = [x for x in a if part(x)], [x for x in b if part(x)]
        assert sorted(len(x.prompt) for x in pa) == sorted(
            len(x.prompt) for x in pb)
        assert sorted(x.max_new for x in pa) == sorted(x.max_new for x in pb)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert not np.array_equal(a[0].prompt[:4], b[0].prompt[:4])


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_respect_clips_and_medians(mix):
    m = load(mix)
    reqs = traffic.generate(m, 1000, 9, 51)
    for key, vals in (("prompt_len", [len(x.prompt) for x in reqs]),
                      ("output_len", [x.max_new for x in reqs])):
        d = m[key]
        assert min(vals) >= d["min"] and max(vals) <= d["max"]
        assert abs(np.median(vals) - d["median"]) <= 0.02 * d["median"] + 1


@pytest.mark.parametrize("mix", MIXES)
def test_arrivals_cover_the_window(mix):
    m = load(mix)
    reqs = traffic.generate(m, 1000, 4, 51)
    due = [x.due_s for x in reqs]
    assert due == sorted(due)
    rate, fill = m["arrival"]["rate_per_s"], m["fill_s"]
    k = m["window_parts"]
    in_window = [d for d in due if fill <= d < fill + 51]
    assert len(in_window) == k * round(rate * 51 / k)
    assert len(due) - len(in_window) == round(rate * fill)
    assert 0 < due[0] and due[-1] < fill + 51
    assert fill + 51 - due[-1] < 5 / rate


@pytest.mark.parametrize("mix", MIXES)
def test_every_span_of_the_window_gets_the_same_multiset(mix):
    """The window's spans each hold the same multiset of lengths, so which
    requests come due late in the window does not change with the seed."""
    m = load(mix)
    fill, k = m["fill_s"], m["window_parts"]
    for seed in (5, 2 ** 41 + 7):
        reqs = traffic.generate(m, 1000, seed, 51)
        spans = [[x for x in reqs
                  if fill + i * 51 / k <= x.due_s < fill + (i + 1) * 51 / k]
                 for i in range(k)]
        assert len(spans[0]) == round(m["arrival"]["rate_per_s"] * 51 / k)
        for part in spans[1:]:
            for size in (lambda x: len(x.prompt), lambda x: x.max_new):
                assert sorted(map(size, part)) == sorted(map(size, spans[0]))


def test_backlog_blocks_repeat_the_multiset():
    m = {"arrival": {"kind": "backlog", "requests": 130, "block": 50},
         "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.75,
                        "min": 32, "max": 768},
         "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.75,
                        "min": 16, "max": 256}}
    reqs = traffic.generate(m, 1000, 6, 51)
    assert len(reqs) == 130 and {x.due_s for x in reqs} == {0.0}
    block = m["arrival"]["block"]
    first = sorted(len(x.prompt) for x in reqs[:block])
    for i in range(block, len(reqs), block):
        part = reqs[i:i + block]
        if len(part) == block:
            assert sorted(len(x.prompt) for x in part) == first


def test_token_ids_in_vocab_and_uniform():
    m = load(MIXES[0])
    reqs = traffic.generate(m, 50, 8, 51)
    ids = np.concatenate([x.prompt for x in reqs])
    assert ids.min() >= 0 and ids.max() < 50
    counts = np.bincount(ids, minlength=50)
    assert counts.min() > 0.5 * counts.mean()


def test_unknown_kinds_fail():
    m = dict(load(MIXES[0]), arrival={"kind": "closed"})
    with pytest.raises(ValueError):
        traffic.generate(m, 10, 1, 5)
    with pytest.raises(ValueError):
        traffic.lengths({"dist": "zipf"}, 4)
