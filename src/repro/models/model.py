"""Model facade: init / train-loss / prefill / decode for every arch family.

`build_model(cfg, ctx)` returns a `Model` whose five entry points are what
the launcher jits:

    init(key)                          -> params
    loss(params, batch)                -> (scalar, metrics)       [train_step]
    prefill(params, batch)             -> (caches, last_logits)   [prefill]
    decode_step(params, caches, token, pos) -> (caches, logits)   [serve_step]
    init_cache(batch, max_len)         -> caches

plus `input_specs(shape)` producing the ShapeDtypeStruct stand-ins the
multi-pod dry-run lowers against (no allocation).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.dispatch import KernelDispatcher
from repro.kernels import compat
from repro.models import dispatched as dsp
from repro.models import encdec as encdec_lib
from repro.models import transformer as tf_lib
from repro.models.layers import (Params, apply_norm, embed_tokens, init_embed,
                                 init_norm, logits as logits_fn)
from repro.parallel.ctx import CPU_CTX, ParallelContext

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    ctx: ParallelContext
    # Op-by-device routing: when set, every matmul traced by loss/prefill/
    # decode_step resolves through this dispatcher against the kernel
    # registry (packed weights stream; gated kernels fall back to oracles).
    # None = the seed's plain dense path.
    dispatcher: KernelDispatcher | None = None

    # ------------------------------------------------------------------
    @property
    def dtype(self):
        return _DTYPES[self.cfg.dtype]

    def _dispatch_scope(self):
        """The dispatcher, and on a multi-device mesh the manual region
        every Pallas kernel needs (`dsp.use_mesh`)."""
        scope = contextlib.ExitStack()
        scope.enter_context(dsp.use_dispatcher(self.dispatcher))
        if self.ctx.active:
            scope.enter_context(dsp.use_mesh(self.ctx.mesh))
        return scope

    def init(self, key) -> Params:
        cfg = self.cfg
        k_embed, k_stack, k_final, k_mtp = jax.random.split(key, 4)
        params: Params = {
            "embed": init_embed(k_embed, cfg, self.dtype),
            "final_ln": init_norm(cfg, cfg.d_model),
        }
        if cfg.family == "encdec":
            params["encdec"] = encdec_lib.init_encdec_stacks(k_stack, cfg,
                                                             self.dtype)
        else:
            params["layers"] = tf_lib.init_stack(k_stack, cfg, self.dtype)
        if cfg.mtp_depth:
            sig = tf_lib.layer_signature(cfg, cfg.n_layers - 1)
            params["mtp"] = {
                "proj": jax.random.normal(
                    k_mtp, (2 * cfg.d_model, cfg.d_model), self.dtype)
                * (2 * cfg.d_model) ** -0.5,
                "ln_h": init_norm(cfg, cfg.d_model),
                "ln_e": init_norm(cfg, cfg.d_model),
                "layer": tf_lib.init_layer(jax.random.fold_in(k_mtp, 1), cfg,
                                           sig, self.dtype),
            }
        return params

    # ------------------------------------------------------------------
    @staticmethod
    def named_leaves(tree: Params) -> list[tuple[str, Any]]:
        """(path, leaf) pairs over a param/cache tree, rendered "a/b/0/c" —
        the naming the sharding rules and checkpoint layout key on. Goes
        through the version-adaptive pytree surface (`kernels.compat`): the
        path-aware flatten moved modules between jax 0.4.x and 0.5+."""
        leaves, _ = compat.tree_flatten_with_path(tree)
        return [(compat.tree_path_str(p), leaf) for p, leaf in leaves]

    # ------------------------------------------------------------------
    def _backbone(self, params, x, positions, *, mode, caches=None,
                  frames=None):
        cfg = self.cfg
        if cfg.family == "encdec":
            if mode == "decode":
                cross = caches["cross"]   # built at prefill; resident
            else:
                enc_out = encdec_lib.encode(cfg, params["encdec"], frames,
                                            self.ctx)
                cross = encdec_lib.build_cross_cache(cfg, params["encdec"],
                                                     enc_out)
            x, self_caches = encdec_lib.decoder_stack(
                cfg, params["encdec"], x, positions, self.ctx, mode=mode,
                cross=cross,
                caches=caches["self"] if mode == "decode" else None)
            new_caches = None
            if mode in ("prefill", "decode"):
                new_caches = {"self": self_caches, "cross": cross}
            return x, new_caches, jnp.zeros((), jnp.float32)
        return tf_lib.apply_stack(cfg, params["layers"], x, positions,
                                  self.ctx, mode=mode, caches=caches)

    def forward(self, params, tokens, positions, *, mode, caches=None,
                frames=None):
        cfg = self.cfg
        with self._dispatch_scope():
            x = embed_tokens(params["embed"], tokens).astype(self.dtype)
            if cfg.family == "encdec" and mode == "decode":
                # cross cache already built at prefill; frames unused in decode
                frames = None
            x, new_caches, aux = self._backbone(params, x, positions,
                                                mode=mode, caches=caches,
                                                frames=frames)
            h = apply_norm(cfg, params["final_ln"], x)
        return h, new_caches, aux

    # ------------------------------------------------------------------
    # Training
    # ------------------------------------------------------------------
    def loss(self, params, batch) -> tuple[jnp.ndarray, dict[str, Any]]:
        """Next-token cross entropy, logits in fp32 (the wide anchor), with
        z-loss and the MoE balance loss; optional MTP auxiliary loss."""
        cfg = self.cfg
        tokens = batch["tokens"]                       # (B, S)
        targets = batch["targets"]                     # (B, S)
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h, _, aux = self.forward(params, tokens, positions, mode="train",
                                 frames=batch.get("frames"))
        with self._dispatch_scope():
            lg = logits_fn(cfg, params["embed"], h)    # fp32 (B,S,V)
            ce, z = _xent(lg, targets, cfg.vocab)
            loss = ce + 1e-4 * z + 1e-2 * aux
            metrics = {"ce": ce, "zloss": z, "moe_aux": aux,
                       "tokens": jnp.asarray(b * s, jnp.float32)}
            if cfg.mtp_depth and "mtp" in params:
                mtp_loss = self._mtp_loss(params, tokens, targets, h,
                                          positions)
                loss = loss + 0.3 * mtp_loss
                metrics["mtp"] = mtp_loss
        metrics["loss"] = loss
        return loss, metrics

    def _mtp_loss(self, params, tokens, targets, h, positions):
        """DeepSeek-V3 multi-token prediction (depth 1): predict t+2 from the
        main stream at t combined with the embedding of t+1.

        Runs at FULL sequence length (shift via roll + loss mask) so the MoE
        layer keeps its EP-divisible token count — slicing to S-1 tokens
        would push the routed experts onto the dense fallback path."""
        cfg = self.cfg
        p = params["mtp"]
        h_in = apply_norm(cfg, p["ln_h"], h)
        next_tok = jnp.roll(tokens, -1, axis=1)       # t+1 (last col is junk)
        e_next = apply_norm(
            cfg, p["ln_e"],
            embed_tokens(params["embed"], next_tok).astype(h.dtype))
        merged = jnp.concatenate([h_in, e_next], axis=-1)
        x = dsp.linear(merged, p["proj"])
        sig = tf_lib.layer_signature(cfg, cfg.n_layers - 1)
        x, _, _ = tf_lib.apply_layer(cfg, sig, p["layer"], x, positions,
                                     self.ctx, mode="train", cache=None)
        lg = logits_fn(cfg, params["embed"], apply_norm(cfg, params["final_ln"], x))
        # position t predicts target t+1 of the shifted stream = token t+2;
        # the last two positions see rolled-around junk -> masked out
        mtp_targets = jnp.roll(targets, -1, axis=1)
        s = tokens.shape[1]
        mask = (jnp.arange(s) < s - 2).astype(jnp.float32)[None, :]
        ce, _ = _xent(lg, mtp_targets, cfg.vocab, mask=mask)
        return ce

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> Params:
        cfg = self.cfg
        if cfg.family == "encdec":
            return {
                "self": encdec_lib.init_decoder_cache(cfg, batch, max_len,
                                                      self.dtype),
                "cross": {
                    "k": jnp.zeros((cfg.n_layers, batch, cfg.encoder_len,
                                    cfg.n_kv_heads, cfg.d_head), self.dtype),
                    "v": jnp.zeros((cfg.n_layers, batch, cfg.encoder_len,
                                    cfg.n_kv_heads, cfg.d_head), self.dtype),
                },
            }
        return tf_lib.init_stack_cache(cfg, batch, max_len, self.dtype)

    def prefill(self, params, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h, caches, _ = self.forward(params, tokens, positions, mode="prefill",
                                    frames=batch.get("frames"))
        with self._dispatch_scope():
            lg = logits_fn(self.cfg, params["embed"], h[:, -1:])
        return caches, lg

    def decode_step(self, params, caches, token, pos):
        """token: (B, 1) int32; pos: (B,) int32 absolute positions."""
        positions = pos[:, None]
        h, caches, _ = self.forward(params, token, positions, mode="decode",
                                    caches=caches)
        with self._dispatch_scope():
            lg = logits_fn(self.cfg, params["embed"], h)
        return caches, lg

    def prefill_chunk(self, params, caches, tokens, pos0):
        """One prefill chunk: C prompt tokens written into decode-shaped
        `caches` as if they were C fused decode steps.

        tokens: (B, C) int32 prompt slice; pos0: (B,) int32 absolute
        position of tokens[:, 0]. Returns (caches, last-token logits) —
        the same contract as `prefill`, so the scheduler's donated
        admission path treats the staging cache like a prefill cache. The
        shape (B, C) is the whole program signature: every chunk of every
        prompt reuses one ProgramCache entry per chunk size."""
        b, c = tokens.shape
        positions = pos0[:, None] + jnp.arange(c, dtype=pos0.dtype)[None]
        h, caches, _ = self.forward(params, tokens, positions, mode="decode",
                                    caches=caches)
        with self._dispatch_scope():
            lg = logits_fn(self.cfg, params["embed"], h[:, -1:])
        return caches, lg

    # ------------------------------------------------------------------
    # Dry-run stand-ins
    # ------------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this shape
        cell — weak-type-correct, shardable, no device allocation."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind in ("train", "prefill"):
            specs = {
                "tokens": jax.ShapeDtypeStruct((b, s), i32),
            }
            if shape.kind == "train":
                specs["targets"] = jax.ShapeDtypeStruct((b, s), i32)
            if cfg.family == "encdec":
                specs["frames"] = jax.ShapeDtypeStruct(
                    (b,) + cfg.frame_shape, self.dtype)
            return specs
        # decode: one new token against a cache of seq_len
        cache_spec = jax.eval_shape(
            functools.partial(self.init_cache, b, s))
        return {
            "caches": cache_spec,
            "token": jax.ShapeDtypeStruct((b, 1), i32),
            "pos": jax.ShapeDtypeStruct((b,), i32),
        }


def _xent(lg: jnp.ndarray, targets: jnp.ndarray, vocab: int, mask=None):
    """CE over the true vocab (padded slots masked), plus z-loss term.

    TP-friendly: `picked` contracts the (model-sharded) vocab axis with a
    fused compare-select-reduce instead of a take_along_axis gather, so no
    logits all-gather is forced (the vocab axis reduces with a psum)."""
    lg = lg.astype(jnp.float32)
    v = lg.shape[-1]
    vmask = jnp.arange(v) < vocab
    lg = jnp.where(vmask, lg, -1e30)
    m = jax.lax.stop_gradient(lg.max(axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1)) + m[..., 0]
    onehot = jnp.arange(v)[None, None, :] == targets[..., None]
    picked = jnp.sum(jnp.where(onehot, lg, 0.0), axis=-1)
    per_tok = lse - picked
    z_tok = lse ** 2
    if mask is not None:
        denom = jnp.maximum(mask.sum() * per_tok.shape[0] / mask.shape[0], 1.0)
        ce = (per_tok * mask).sum() / denom
        z = (z_tok * mask).sum() / denom
    else:
        ce = per_tok.mean()
        z = z_tok.mean()
    return ce, z


def build_model(cfg: ModelConfig, ctx: ParallelContext = CPU_CTX,
                dispatcher: KernelDispatcher | None = None) -> Model:
    return Model(cfg=cfg, ctx=ctx, dispatcher=dispatcher)
