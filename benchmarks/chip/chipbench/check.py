"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed: a sample of
the requests the timed path finished, drawn from the seed and always
holding the longest, is run once through the configuration's plain
reference (prompt followed by the served tokens, one full causal forward
in fp32). For every served token the reference gives the gap by which
that token's logit lies below its best logit; greedy serving at the
configuration's precision keeps the widest gap small, and a token altered
where it is produced, or a lower precision, opens it. Each compared number
has its limit in ``checks/<cell>.json``.
"""

from __future__ import annotations

import os

import numpy as np

from chipbench import load_module

SAMPLE_SALT = 0x5EED


def sample(win, n: int, seed: int) -> list:
    """Up to ``n`` finished requests: the longest, and the rest drawn from
    the seed."""
    done = [o for o in win.reqs if o.finished]
    if not done:
        return []
    longest = max(done, key=lambda o: (o.prompt_len + o.max_new, -o.rid))
    rest = [o for o in done if o is not longest]
    rng = np.random.default_rng([seed, SAMPLE_SALT])
    k = min(n - 1, len(rest))
    pick = rng.choice(len(rest), size=k, replace=False) if k else []
    return [longest] + [rest[i] for i in sorted(pick)]


def load_reference(root: str, name: str):
    return load_module(os.path.join(root, "references", f"{name}.py"),
                       f"ref_{name}")


def judge(gaps: np.ndarray, limits: dict, bad: int = 0) -> dict:
    """The numbers of one side (the program, or the control in its place),
    each with its limit, and the verdict: every served token within the
    widest gap allowed."""
    widest = float(gaps.max()) if gaps.size else float("inf")
    numbers = {"widest_gap": {"value": widest, "limit": limits["widest_gap"],
                              "rule": "<="},
               "served_tokens": {"value": int(gaps.size), "limit": 1,
                                 "rule": ">="}}
    correct = bad == 0 and gaps.size >= 1 and widest <= limits["widest_gap"]
    return {"correct": bool(correct), "numbers": numbers}


def compare(root: str, conf: dict, win, seed: int, n_check: int,
            limits: dict, control: bool = False) -> dict:
    """The compared numbers, each with its limit, and the verdict; with
    ``control``, also the control's numbers and verdict under the same
    rule and limits."""
    m = conf["model"]
    picked = sample(win, n_check, seed)
    bad = 0
    for o in win.reqs:
        if o.finished and (len(o.tokens) != o.max_new or any(
                not 0 <= t < m["vocab"] for t in o.tokens)):
            bad += 1
    out = {"bad_requests": {"value": bad, "limit": 0, "rule": "<="}}
    if not picked:
        out["served_tokens"] = {"value": 0, "limit": 1, "rule": ">="}
        return {"correct": False, "numbers": out, "gaps": None}
    ref = load_reference(root, conf["reference"]).Reference(
        m, n_check, conf["serving"]["max_len"])
    seqs = [win.seqs[o.rid][:o.prompt_len + o.max_new] for o in picked]
    gaps = ref.gaps(seed, seqs, [o.prompt_len for o in picked],
                    control=control)
    got = judge(gaps["program"], limits, bad)
    out.update(got["numbers"])
    verdict = {"correct": got["correct"], "numbers": out, "gaps": gaps}
    if control:
        verdict["control"] = judge(gaps["control"], limits)
    return verdict
