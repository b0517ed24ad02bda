"""A reference module in a file of its own, for the tests: dense GQA with
``dense_gqa``'s forward, comparison and layer draws, but the embedding and
the final norm drawn by the key rule of `chipbench.weights`. Its weights
differ from ``dense_gqa``'s, so a run is correct only where the program's
parameters and the reference both come from this file."""

import os

from chipbench import BENCH_DIR, load_module, weights

_base = load_module(os.path.join(BENCH_DIR, "references", "dense_gqa.py"),
                    "dense_by_rule_base")


def outer(key, m: dict) -> dict:
    d, v = m["d_model"], m["vocab"]
    out = {"embed": weights.matrix(weights.named_key(key, "embed"), (v, d),
                                   2500),            # std 0.02
           "final_norm": weights.norm(weights.named_key(key, "final_norm"),
                                      d)}
    if not m["tie_embeddings"]:
        out["unembed"] = weights.matrix(weights.named_key(key, "unembed"),
                                        (d, v), d)
    return out


# the base module's program_leaf and Reference read `outer` from its globals
_base.outer = outer
layer, program_leaf, Reference = _base.layer, _base.program_leaf, \
    _base.Reference
