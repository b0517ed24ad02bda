"""specdec: fused speculative-decoding verify/accept (paper §9 economics).

The engine's fixed per-dispatch floor dominates decode (§9.3/§9.4), so the
only way to cut per-token cost is more tokens per dispatch. Speculative
decoding buys exactly that: a cheap drafter proposes K tokens, the target
scores all K+1 positions in one dispatch, and this kernel performs the
accept/reject math *on device* so the token chain never round-trips the
host inside a window:

  * **per-position resample** — the target's pick at every drafted position:
    a first-index argmax over the (possibly gumbel-perturbed) score rows.
    With raw logits this is greedy; with per-(rid, pos) gumbel noise added
    by `ops.seeded_scores` it is bit-identical to
    `jax.random.categorical(fold_in(fold_in(root, rid), pos), logits)` —
    the host `TokenSampler`'s draw.
  * **accept-prefix selection** — the longest prefix of draft tokens that
    matches the target's picks position by position. Accepted tokens ARE
    the target's picks, so the emitted stream is always the target
    sampler's stream regardless of what the drafter proposed.
  * **bonus token** — the target's pick at the first mismatch (or at the
    position past the last draft token when everything matched): every
    window emits `accept_len + 1` tokens.

The argmax is gather-free, as the VPU wants it: row max, then a min-reduce
over an iota masked to the argmax positions — first-index tie-breaking,
exactly `jnp.argmax`'s contract (and the ANE's argmax feature byte
0x4f2_argmax_hw gates the capability row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat
from repro.kernels.common import cdiv

NEG_INF = float("-inf")


def _kernel(scores_ref, draft_ref, samp_ref, acc_ref, *, t: int, v: int):
    """One lane's window: scores (1, T, Vp) f32, draft (1, max(T-1, 1)) i32
    -> samples (1, T) i32, accept_len (1, 1) i32."""
    s = scores_ref[0].astype(jnp.float32)            # (T, Vp)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < v, s, NEG_INF)               # padding never wins
    m = jnp.max(s, axis=-1, keepdims=True)
    # first-index argmax: smallest column index attaining the row max
    idx = jnp.min(jnp.where(s == m, col, v), axis=-1).astype(jnp.int32)  # (T,)
    samp_ref[0, :] = idx
    # accept-prefix: position i accepts iff every draft token up to and
    # including i equals the target's pick there (T is static; unrolled)
    alive = jnp.int32(1)
    acc = jnp.int32(0)
    for i in range(t - 1):
        alive = alive * (draft_ref[0, i] == idx[i]).astype(jnp.int32)
        acc = acc + alive
    acc_ref[0, 0] = acc


def _tree_kernel(scores_ref, draft_ref, samp_ref, acc_ref, br_ref, *,
                 nbr: int, t: int, v: int):
    """One lane's draft tree: scores (1, NBR, T, Vp) f32, draft
    (1, NBR, max(T-1, 1)) i32 -> samples (1, T) i32, accept_len (1, 1) i32,
    branch (1, 1) i32.

    Each branch is an independent chain sharing the window's first position;
    the per-branch math is exactly `_kernel`'s accept-prefix scan, then the
    winning branch is the one with the longest accepted prefix (first-index
    tie-break, so NBR=1 degenerates to the chain kernel bit for bit — ties
    between sibling branches only happen when their accepted prefixes are
    identical token strings anyway, because accepted tokens ARE the target's
    picks)."""
    s = scores_ref[0].astype(jnp.float32)            # (NBR, T, Vp)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(col < v, s, NEG_INF)               # padding never wins
    m = jnp.max(s, axis=-1, keepdims=True)
    # first-index argmax per (branch, position)
    idx = jnp.min(jnp.where(s == m, col, v), axis=-1).astype(jnp.int32)
    alive = jnp.ones((nbr,), jnp.int32)              # idx: (NBR, T)
    acc = jnp.zeros((nbr,), jnp.int32)
    for i in range(t - 1):
        alive = alive * (draft_ref[0, :, i] == idx[:, i]).astype(jnp.int32)
        acc = acc + alive
    best = jnp.max(acc)
    bidx = jax.lax.broadcasted_iota(jnp.int32, (nbr,), 0)
    win = jnp.min(jnp.where(acc == best, bidx, nbr)).astype(jnp.int32)
    acc_ref[0, 0] = best
    br_ref[0, 0] = win
    # the winning branch's picks, gather-free: one-hot select over NBR
    onehot = (bidx[:, None] == win).astype(jnp.int32)           # (NBR, 1)
    samp_ref[0, :] = jnp.sum(idx * onehot, axis=0)


@functools.partial(jax.jit, static_argnames=("vocab",))
def verify_accept_tree_kernel(scores: jnp.ndarray, draft: jnp.ndarray, *,
                              vocab: int | None = None):
    """Fused verify/accept over a *tree* of speculative branches.

    scores: (B, NBR, T, V) fp32 — target scores per (branch, position); the
        NBR branches of a lane share position 0's context and diverge on
        their first proposed token.
    draft:  (B, NBR, T-1) int32 — each branch's proposal chain.
    Returns (samples (B, T) int32, accept_len (B,) int32, branch (B,) int32):
    the winning branch's target picks, its matched-prefix length (the max
    over branches, first index on ties), and which branch won; the window
    emits `samples[:, :accept_len + 1]`.
    """
    b, nbr, t, v = scores.shape
    vocab = v if vocab is None else vocab
    if nbr < 1:
        raise ValueError(f"tree needs >= 1 branch, got {nbr}")
    if draft.shape != (b, nbr, t - 1):
        raise ValueError(f"draft {draft.shape} does not pair with scores "
                         f"{scores.shape}; want ({b}, {nbr}, {t - 1})")
    vp = 128 * cdiv(max(v, 1), 128)
    sp = jnp.pad(scores.astype(jnp.float32),
                 ((0, 0), (0, 0), (0, 0), (0, vp - v)),
                 constant_values=NEG_INF)
    dp = draft.astype(jnp.int32) if t > 1 else \
        jnp.full((b, nbr, 1), -1, jnp.int32)
    samples, accept, branch = pl.pallas_call(
        functools.partial(_tree_kernel, nbr=nbr, t=t, v=min(v, vocab)),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, nbr, t, vp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, nbr, dp.shape[2]), lambda i: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, t), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, t), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ),
        interpret=compat.interpret_mode(),
    )(sp, dp)
    return samples, accept[:, 0], branch[:, 0]


@functools.partial(jax.jit, static_argnames=("vocab",))
def verify_accept_kernel(scores: jnp.ndarray, draft: jnp.ndarray, *,
                         vocab: int | None = None):
    """Fused verify/accept over a speculative window.

    scores: (B, T, V) fp32 — target scores per position (logits, or
        gumbel-perturbed logits for seeded categorical streams).
    draft:  (B, T-1) int32 — the drafter's proposals for positions 1..T-1
        of the window (position 0 has no proposal: its pick seeds the
        window's first emitted token).
    Returns (samples (B, T) int32, accept_len (B,) int32): the target's
    per-position picks and the matched-prefix length; the window emits
    `samples[:, :accept_len + 1]`.
    """
    b, t, v = scores.shape
    vocab = v if vocab is None else vocab
    if draft.shape != (b, t - 1):
        raise ValueError(f"draft {draft.shape} does not pair with scores "
                         f"{scores.shape}; want ({b}, {t - 1})")
    vp = 128 * cdiv(max(v, 1), 128)
    sp = jnp.pad(scores.astype(jnp.float32), ((0, 0), (0, 0), (0, vp - v)),
                 constant_values=NEG_INF)
    # a zero-width draft (bonus-only window) still needs a real operand
    dp = draft.astype(jnp.int32) if t > 1 else \
        jnp.full((b, 1), -1, jnp.int32)
    samples, accept = pl.pallas_call(
        functools.partial(_kernel, t=t, v=min(v, vocab)),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, t, vp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, dp.shape[1]), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, t), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, t), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ),
        interpret=compat.interpret_mode(),
    )(sp, dp)
    return samples, accept[:, 0]
