"""Plain reference: a dense decoder with grouped-query attention, RoPE,
SwiGLU, RMSNorm and a tied logits head, in fp32.

It follows the equations of the configurations that name it (see their
``departures``): RMSNorm ``x / sqrt(mean(x^2) + 1e-6) * scale``; RoPE over
the whole head, rotating the first half against the second with
frequencies ``theta ** (-2j / d_head)``; query head ``h`` reads key/value
head ``h // (H / KV)``; scores scaled by ``d_head ** -0.5`` under a causal
mask; ``wd(silu(wg x) * wu x)``; logits ``h @ embed.T``.

No cache, no batching of requests into lanes, no kernels: each sequence
(prompt followed by its served tokens) is one full causal forward, layer by
layer, with every weight regenerated from the seed (`chipbench.weights`)
and every matmul at ``Precision.HIGHEST``. It imports nothing of the
program under test.

``control=True`` also runs the same forward with every matmul operand
rounded to fp8 (e4m3, scaled per row and per column): the step below the
configuration's bf16 that a later change might be tempted by.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
ROW_BLOCK = 256
NEG = -1e30


def _q8(x, axis):
    """Round to fp8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, fp8: bool):
    """(..., k) @ (k, n)."""
    if fp8:
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.dot(a, b, precision=HI)


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[..., None].astype(jnp.float32) * freqs          # (B, S, dh/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(m, x, w, fp8: bool):
    b, s, _ = x.shape
    h, kv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    g = h // kv
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    y = _rms(x, w["attn_norm"])
    q = _mm(y, w["wq"], fp8).reshape(b, s, h, dh)
    k = _mm(y, w["wk"], fp8).reshape(b, s, kv, dh)
    v = _mm(y, w["wv"], fp8).reshape(b, s, kv, dh)
    q = _rope(q, pos, m["rope_theta"]).reshape(b, s, kv, g, dh)
    k = _rope(k, pos, m["rope_theta"])
    if fp8:
        q, k = _q8(q, -1), _q8(k, -1)
    sc = jnp.einsum("bqkgd,bckd->bkgqc", q, k, precision=HI) * dh ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal, sc, NEG)
    p = jax.nn.softmax(sc, axis=-1)
    if fp8:
        p, v = _q8(p, -1), _q8(v, 1)
    o = jnp.einsum("bkgqc,bckd->bqkgd", p, v, precision=HI)
    x = x + _mm(o.reshape(b, s, h * dh), w["wo"], fp8)
    y = _rms(x, w["mlp_norm"])
    act = jax.nn.silu(_mm(y, w["wg"], fp8)) * _mm(y, w["wu"], fp8)
    return x + _mm(act, w["wd"], fp8)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


class Reference:
    """Compiled pieces for one configuration and one padded batch shape."""

    def __init__(self, m: dict, batch: int, seq_len: int) -> None:
        self.m, self.batch, self.seq_len = m, batch, seq_len
        self._layer_w = jax.jit(
            lambda key, i: _f32(weights.layer(key, i, m)))
        self._outer_w = jax.jit(lambda key: _f32(weights.outer(key, m)))
        self._layer = jax.jit(functools.partial(_layer, m), static_argnums=2)
        self._embed = jax.jit(lambda table, tok: table[tok])
        self._final = jax.jit(_rms)
        self._gaps = jax.jit(self._block_gaps, static_argnums=4)

    @staticmethod
    def _block_gaps(rows, table, target, ref_rows, fp8):
        """Per row: the reference's best logit minus the reference's logit
        of ``target`` (or, with ``fp8``, of the fp8 head's first choice over
        ``rows``)."""
        ref = jnp.dot(ref_rows, table.T, precision=HI)
        best = ref.max(-1)
        if fp8:
            target = jnp.argmax(_mm(rows, table.T, True), -1)
        picked = jnp.take_along_axis(ref, target[:, None], -1)[:, 0]
        return best - picked

    def gaps(self, seed: int, seqs: list[np.ndarray], prompt_lens: list[int],
             control: bool = False) -> dict:
        """Gap of each served token (the positions after each prompt) under
        the reference; with ``control`` also the gap of the fp8 forward's
        first choice at the same positions."""
        m, B, S = self.m, self.batch, self.seq_len
        if len(seqs) > B or any(len(s) > S for s in seqs):
            raise ValueError(f"reference holds {B} sequences of <= {S}")
        tok = np.zeros((B, S), np.int32)
        rows, target = [], []
        for b, (seq, L) in enumerate(zip(seqs, prompt_lens)):
            tok[b, :len(seq)] = seq
            for p in range(L - 1, len(seq) - 1):
                rows.append(b * S + p)
                target.append(int(seq[p + 1]))
        key = weights.root_key(seed)
        outer = self._outer_w(key)
        x = self._embed(outer["embed"], jnp.asarray(tok))
        xs = {"program": x}
        if control:
            xs["control"] = x
        for i in range(m["n_layers"]):
            w = self._layer_w(key, i)
            xs = {k: self._layer(v, w, k == "control") for k, v in xs.items()}
            del w
        hs = {k: self._final(v, outer["final_norm"]).reshape(B * S, -1)
              for k, v in xs.items()}
        del xs
        n = len(rows)
        pad = -n % ROW_BLOCK
        rows_a = np.array(rows + [0] * pad, np.int32)
        tgt_a = np.array(target + [0] * pad, np.int32)
        out = {k: [] for k in hs}
        for lo in range(0, n + pad, ROW_BLOCK):
            r = jnp.asarray(rows_a[lo:lo + ROW_BLOCK])
            t = jnp.asarray(tgt_a[lo:lo + ROW_BLOCK])
            ref_rows = hs["program"][r]
            for k, h in hs.items():
                out[k].append(np.asarray(self._gaps(
                    h[r], outer["embed"], t, ref_rows, k == "control")))
        return {k: np.concatenate(v)[:n] for k, v in out.items()}
