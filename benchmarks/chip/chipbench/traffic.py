"""One seeded generator for every traffic mix (`traffic/<name>.json`).

A mix file gives the arrival process and the length distributions; the
generator turns it and a seed into requests. The requests come in parts,
and every seed draws the same multiset of prompt lengths, output lengths
and inter-arrival gaps for each part (the distribution's quantiles at
(i + 0.5) / n), in an order of its own, with token ids of its own: the
work of a run is fixed, and a seed changes only which request meets which.

Arrival kinds:
  * ``poisson``: open loop at ``rate_per_s``, exponential gaps; one part
    of rate x ``fill_s`` requests due in the fill, and the window cut into
    ``window_parts`` equal spans of rate x span requests each, so the
    requests whose time to first token is measured are the same multiset
    in every run, and in every span of the window alike.
  * ``backlog``: ``requests`` requests, all due at time 0 (offline batch),
    in parts of ``block``, each block the same multiset.

Length distributions (``prompt_len``, ``output_len``):
  * ``lognormal``: ``median``, ``sigma``, clipped to [``min``, ``max``].
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Spec:
    """One generated request: token ids, generation budget, due offset."""

    prompt: np.ndarray
    max_new: int
    due_s: float


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    raw = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(np.int64)


def _parts(mix: dict, seconds: float) -> list:
    """(count, start, span) of each part of the arrivals."""
    arr = mix["arrival"]
    if arr["kind"] == "backlog":
        n, block = int(arr["requests"]), int(arr["block"])
        return [(min(block, n - i), 0.0, 0.0) for i in range(0, n, block)]
    if arr["kind"] == "poisson":
        r, fill, k = arr["rate_per_s"], mix["fill_s"], mix["window_parts"]
        span = seconds / k
        return [(max(1, round(r * fill)), 0.0, fill)] + [
            (max(1, round(r * span)), fill + i * span, span)
            for i in range(k)]
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def _dues(n: int, start: float, span: float,
          rng: np.random.Generator) -> np.ndarray:
    """``n`` Poisson-like arrivals inside (start, start + span): the
    exponential quantiles as gaps, in the seed's order, scaled to the
    span."""
    if span == 0:
        return np.full(n, start)
    gaps = rng.permutation(-np.log1p(-_quantiles(n)))
    c = np.cumsum(gaps)
    return start + span * (c - gaps / 2) / c[-1]


def generate(mix: dict, vocab: int, seed: int, seconds: float) -> list[Spec]:
    """The requests of one run, in due order."""
    rng = np.random.default_rng(seed)
    out = []
    for n, start, span in _parts(mix, seconds):
        due = _dues(n, start, span, rng)
        plen = rng.permutation(lengths(mix["prompt_len"], n))
        glen = rng.permutation(lengths(mix["output_len"], n))
        for i in range(n):
            toks = rng.integers(0, vocab, size=int(plen[i]), dtype=np.int64)
            out.append(Spec(toks.astype(np.int32), int(glen[i]),
                            float(due[i])))
    return out
