"""Public specdec surface: seeded scores + routed verify/accept.

`seeded_scores` turns target logits into the score rows the verify/accept
kernel reduces: raw logits for greedy streams, gumbel-perturbed logits for
seeded categorical streams. The perturbation reproduces
`jax.random.categorical` exactly — `categorical(key, row)` is defined as
`argmax(gumbel(key, row.shape, row.dtype) + row)` — with the same
per-(rid, position) `fold_in` key chain the host `TokenSampler` uses, so a
first-index argmax over the perturbed rows is bit-identical to the host
sampler's draw at that (request, position).

`verify_accept` is the dispatcher-aware entry: the Pallas kernel when the
target's capability surface reaches `argmax`, the jnp oracle otherwise —
one more live cell of the op-by-device matrix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.specdec.ref import verify_accept_ref, verify_accept_tree_ref
from repro.kernels.specdec.specdec import (verify_accept_kernel,
                                           verify_accept_tree_kernel)


def seeded_scores(logits: jnp.ndarray, root, rids: jnp.ndarray,
                  positions: jnp.ndarray, mode: str) -> jnp.ndarray:
    """logits (B, T, V) -> score rows for `verify_accept`.

    greedy: the raw fp32 logits (first-index argmax == host greedy).
    categorical: logits + gumbel(fold_in(fold_in(root, rid), position)) per
    row, so argmax(scores[b, t]) == jax.random.categorical(key, logits[b, t])
    bit for bit — the host `TokenSampler`'s math, moved on device.
    """
    lg = logits.astype(jnp.float32)
    if mode == "greedy":
        return lg
    if mode != "categorical":
        raise ValueError(f"unknown sampling mode {mode!r}")

    def row(rid, p, r):
        key = jax.random.fold_in(jax.random.fold_in(root, rid), p)
        return r + jax.random.gumbel(key, r.shape, r.dtype)

    return jax.vmap(jax.vmap(row, in_axes=(None, 0, 0)))(rids, positions, lg)


def verify_accept(scores: jnp.ndarray, draft: jnp.ndarray, *,
                  dispatcher=None):
    """Routed verify/accept: (samples (B, T) i32, accept_len (B,) i32).

    With a dispatcher the call resolves through the `specdec` registry row
    (capability-gated on `argmax`, oracle fallback recorded in the route
    census); without one it runs the Pallas kernel directly.
    """
    if dispatcher is None:
        return verify_accept_kernel(scores, draft)
    from repro.models.dispatched import route_and_run

    return route_and_run(
        dispatcher, "specdec", scores.dtype,
        verify_accept_kernel, verify_accept_ref, scores, draft)


def verify_accept_tree(scores: jnp.ndarray, draft: jnp.ndarray, *,
                       dispatcher=None):
    """Routed tree verify/accept over sibling draft branches per lane:
    (samples (B, T) i32, accept_len (B,) i32, branch (B,) i32).

    Same accept-prefix + bonus-resample math as `verify_accept`, reduced
    over the NBR branch axis on device (max accept, first-index tie-break);
    a single-branch tree is bit-for-bit the chain kernel. Resolves through
    the `specdec_tree` registry row when a dispatcher is given.
    """
    if dispatcher is None:
        return verify_accept_tree_kernel(scores, draft)
    from repro.models.dispatched import route_and_run

    return route_and_run(
        dispatcher, "specdec_tree", scores.dtype,
        verify_accept_tree_kernel, verify_accept_tree_ref, scores, draft)
