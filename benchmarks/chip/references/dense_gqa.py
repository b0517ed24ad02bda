"""Plain reference: a dense decoder with grouped-query attention, RoPE,
SwiGLU, RMSNorm and a logits head tied to the embedding or apart from it,
in fp32; and the weights of that architecture, with the program's layout.

It follows the equations of the configurations that name it (see their
``departures``): RMSNorm ``x / sqrt(mean(x^2) + 1e-6) * scale``; RoPE over
the whole head, rotating the first half against the second with
frequencies ``theta ** (-2j / d_head)``; query head ``h`` reads key/value
head ``h // (H / KV)``; scores scaled by ``d_head ** -0.5`` under a causal
mask; ``wd(silu(wg x) * wu x)``; logits ``h @ embed.T`` when
``tie_embeddings``, else ``h @ unembed``.

No cache, no batching of requests into lanes, no kernels: each sequence
(prompt followed by its served tokens) is one full causal forward, layer by
layer, with every weight regenerated from the seed (`layer`, `outer`) and
every matmul at ``Precision.HIGHEST``. It imports nothing of the program
under test.

``control=True`` also runs the same forward with every matmul operand
rounded to fp8 (e4m3, scaled per row and per column): the step below the
configuration's bf16 that a later change might be tempted by.

What a reference module provides (the harness finds it by the
configuration's ``"reference"``):

  * ``outer(key, m)`` and ``layer(key, i, m)``: the canonical weights,
    drawn from the seed's key, one layer at a time;
  * ``program_leaf(key, m, path, shape)``: the weight of one leaf of the
    program's parameter tree, given its path (``layers/0/sub0/mix/wq``)
    and shape, in the program's dtype; ``KeyError`` for a path the module
    does not know;
  * ``Reference(m, batch, seq_len).gaps(seed, seqs, prompt_lens,
    control)``: the comparison's readings (`chipbench.check`).

Keys: a new module draws each leaf from `weights.named_key` (the leaf's
name, its layer, an expert's global index), so that a chip's share of
the experts gets the numbers of the uncut model. This module's layer
leaves and its embedding keep the draws they had before that rule (one
split per layer, in ``LAYER_LEAVES`` order), so the weights that
`phi4-chat-steady` was measured with stay the same bit for bit; the
untied head, added later, follows the rule.

Canonical shapes (d = d_model, H/KV heads, dh head size, f = d_ff, V vocab):
  embed (V, d); unembed (d, V), untied only; final_norm (d,)
  per layer: attn_norm (d,), wq (d, H*dh), wk (d, KV*dh), wv (d, KV*dh),
  wo (H*dh, d), mlp_norm (d,), wg (d, f), wu (d, f), wd (f, d)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
ROW_BLOCK = 256
NEG = -1e30
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                "wg", "wu", "wd")
# the program's leaf -> (group, canonical name); the layer group is stacked
PROGRAM_LEAVES = {
    "embed/table": ("outer", "embed"),
    "embed/unembed": ("outer", "unembed"),
    "final_ln/scale": ("outer", "final_norm"),
    "layers/0/sub0/ln1/scale": ("layer", "attn_norm"),
    "layers/0/sub0/ln2/scale": ("layer", "mlp_norm"),
    "layers/0/sub0/mix/wq": ("layer", "wq"),
    "layers/0/sub0/mix/wk": ("layer", "wk"),
    "layers/0/sub0/mix/wv": ("layer", "wv"),
    "layers/0/sub0/mix/wo": ("layer", "wo"),
    "layers/0/sub0/mlp/wg": ("layer", "wg"),
    "layers/0/sub0/mlp/wu": ("layer", "wu"),
    "layers/0/sub0/mlp/wd": ("layer", "wd"),
}


def layer_leaf(key, i, m: dict, name: str):
    """One leaf of layer ``i`` (``i`` may be traced, for a vmap over
    layers)."""
    d, h, kv, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["d_head"], m["d_ff"])
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1), i),
                          len(LAYER_LEAVES))
    k = ks[LAYER_LEAVES.index(name)]
    if name in ("attn_norm", "mlp_norm"):
        return weights.norm(k, d)
    shape, fan_in = {"wq": ((d, h * dh), d), "wk": ((d, kv * dh), d),
                     "wv": ((d, kv * dh), d), "wo": ((h * dh, d), h * dh),
                     "wg": ((d, f), d), "wu": ((d, f), d),
                     "wd": ((f, d), f)}[name]
    return weights.matrix(k, shape, fan_in)


def layer(key, i, m: dict) -> dict:
    """Layer ``i``'s weights."""
    return {n: layer_leaf(key, i, m, n) for n in LAYER_LEAVES}


def outer(key, m: dict) -> dict:
    """The embedding, the final norm and, untied, the logits head."""
    k_e, k_n = jax.random.split(jax.random.fold_in(key, 0))
    a = 0.02 * math.sqrt(3.0)                 # std 0.02
    table = jax.random.uniform(k_e, (m["vocab"], m["d_model"]), jnp.float32,
                               -a, a).astype(weights.SERVED)
    out = {"embed": table, "final_norm": weights.norm(k_n, m["d_model"])}
    if not m["tie_embeddings"]:
        out["unembed"] = weights.matrix(weights.named_key(key, "unembed"),
                                        (m["d_model"], m["vocab"]),
                                        m["d_model"])
    return out


def program_leaf(key, m: dict, path: str, shape):
    """The weight of the program's leaf ``path``: a canonical weight (the
    layer leaves stacked over layers) in the program's ``shape``."""
    group, name = PROGRAM_LEAVES[path]
    if group == "outer":
        w = outer(key, m)[name]
    else:
        w = weights.stacked(lambda i: layer_leaf(key, i, m, name),
                            m["n_layers"])
    if w.size != math.prod(shape):
        raise ValueError(f"{path}: {name} {w.shape} does not fill {shape}")
    return w.reshape(shape)


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
        self.tied = m["tie_embeddings"]
        self._layer_w = jax.jit(lambda key, i: _f32(layer(key, i, m)))
        self._outer_w = jax.jit(lambda key: _f32(outer(key, m)))
        self._layer = jax.jit(functools.partial(_layer, m), static_argnums=2)
        self._embed = jax.jit(lambda table, tok: table[tok])
        self._final = jax.jit(_rms)
        self._gaps = jax.jit(self._block_gaps, static_argnums=(4, 5))

    @staticmethod
    def _block_gaps(rows, head, target, ref_rows, fp8, tied):
        """Per row: the reference's best logit minus the reference's logit
        of ``target`` (or, with ``fp8``, of the fp8 head's first choice over
        ``rows``). ``head`` is the (V, d) embedding when ``tied``, else the
        (d, V) unembedding."""
        w = head.T if tied else head
        ref = jnp.dot(ref_rows, w, precision=HI)
        best = ref.max(-1)
        if fp8:
            target = jnp.argmax(_mm(rows, w, True), -1)
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
        ow = self._outer_w(key)
        x = self._embed(ow["embed"], jnp.asarray(tok))
        xs = {"program": x}
        if control:
            xs["control"] = x
        for i in range(m["n_layers"]):
            w = self._layer_w(key, i)
            xs = {k: self._layer(v, w, k == "control") for k, v in xs.items()}
            del w
        hs = {k: self._final(v, ow["final_norm"]).reshape(B * S, -1)
              for k, v in xs.items()}
        del xs
        n = len(rows)
        pad = -n % ROW_BLOCK
        rows_a = np.array(rows + [0] * pad, np.int32)
        tgt_a = np.array(target + [0] * pad, np.int32)
        head = ow["embed"] if self.tied else ow["unembed"]
        out = {k: [] for k in hs}
        for lo in range(0, n + pad, ROW_BLOCK):
            r = jnp.asarray(rows_a[lo:lo + ROW_BLOCK])
            t = jnp.asarray(tgt_a[lo:lo + ROW_BLOCK])
            ref_rows = hs["program"][r]
            for k, h in hs.items():
                out[k].append(np.asarray(self._gaps(
                    h[r], head, t, ref_rows, k == "control", self.tied)))
        return {k: np.concatenate(v)[:n] for k, v in out.items()}
