"""Mixture-of-experts: dense reference path + expert-parallel production path.

Two implementations of the same routed-FFN semantics:

  * `moe_dense` — every expert computed on every token, combined by gate
    weight. Exact (no capacity drops), O(E) overcompute: the reference the
    EP path is tested against, and the path smoke tests take (E <= 4).

  * `moe_ep` — the production path: experts sharded over the "model" mesh
    axis inside `shard_map`. Tokens are split across model ranks (sequence
    split), routed top-k, packed into per-destination capacity buffers,
    exchanged with `all_to_all`, bucketed per local expert, run through the
    expert FFNs as one batched einsum, and combined back through the inverse
    permutation + a second all_to_all + an all_gather. Capacity overflow
    drops (deterministically, highest-rank copies first), exactly like
    GShard-style TPU MoE; the dense path has no drops, so tests compare at
    high capacity factor.

Routing: softmax-then-top-k with renormalized gates + the standard
load-balance auxiliary loss (Switch §2.2 form).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import compat
from repro.models import dispatched as dsp
from repro.models.layers import Params, apply_mlp, init_mlp
from repro.parallel.ctx import ParallelContext


def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    ks = jax.random.split(key, 4)
    std = d ** -0.5
    p: Params = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * std,
        # stacked expert banks (E, d, f) / (E, f, d)
        "wg": jax.random.normal(ks[1], (e, d, f), dtype) * std,
        "wu": jax.random.normal(ks[2], (e, d, f), dtype) * std,
        "wd": jax.random.normal(ks[3], (e, f, d), dtype) * f ** -0.5,
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(jax.random.fold_in(key, 7), cfg, d,
                               cfg.d_ff_expert * cfg.n_shared_experts, dtype)
    return p


def _route(cfg: ModelConfig, router_w: jnp.ndarray, x: jnp.ndarray):
    """x: (T, d) -> (gates (T,k), idx (T,k), aux_loss). Router math in fp32:
    at default precision a TPU runs an fp32 dot as one bf16 pass, whose
    result may depend on the token count, so EP ranks and the dense path
    could route one token differently."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w,
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    e = cfg.n_experts
    me = jnp.mean(probs, axis=0)                                  # P_e
    ce = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / (x.shape[0] * cfg.experts_per_token))               # f_e
    aux = e * jnp.sum(me * ce)
    return gates, idx, aux


def _expert_ffn(cfg: ModelConfig, wg, wu, wd, x):
    """x: (E, C, d) through stacked expert banks -> (E, C, d).

    With a dispatcher in scope (or packed expert banks) each expert's three
    matmuls route through the kernel registry one bank at a time — the
    expert dim is a stack dim of the weight-form tag, sliced per expert.
    Otherwise: one batched einsum over the stacked banks (the seed path)."""
    if dsp.active_dispatcher() is not None or isinstance(wg, dsp.DispatchedWeight):
        act = jax.nn.silu if cfg.act != "gelu" else jax.nn.gelu
        slice_ = (lambda w, e: w.index(e)
                  if isinstance(w, dsp.DispatchedWeight) else w[e])
        outs = []
        for e in range(x.shape[0]):
            g = act(dsp.linear(x[e], slice_(wg, e)))
            u = dsp.linear(x[e], slice_(wu, e))
            outs.append(dsp.linear((g * u).astype(x.dtype), slice_(wd, e)))
        return jnp.stack(outs)
    act = jax.nn.silu if cfg.act != "gelu" else jax.nn.gelu
    g = jnp.einsum("ecd,edf->ecf", x, wg, preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", x, wu, preferred_element_type=jnp.float32)
    h = (act(g) * u).astype(x.dtype)
    return jnp.einsum("ecf,efd->ecd", h, wd,
                      preferred_element_type=jnp.float32).astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense reference path
# ---------------------------------------------------------------------------


def moe_dense(cfg: ModelConfig, p: Params, x: jnp.ndarray,
              ctx: ParallelContext | None = None):
    """x: (B, S, d). Every expert on every token; exact combine.

    With a mesh, the expert axis shards over 'model': each device computes
    only its local experts on (gathered) tokens and the combine contracts the
    expert axis with a psum. For decode (few tokens, weight-read-bound) this
    is the *right* production strategy: the HBM cost is reading each local
    expert bank once, identical to perfectly-routed compute."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    gates, idx, aux = _route(cfg, p["router"], xt)
    # (E, T, d): tokens against every expert bank; sharded over E on a mesh
    xe = jnp.broadcast_to(xt[None], (cfg.n_experts, xt.shape[0], d))
    if ctx is not None and ctx.active:
        xe = ctx.constrain(xe, "model", None, None)
    ye = _expert_ffn(cfg, p["wg"], p["wu"], p["wd"], xe)          # (E, T, d)
    if ctx is not None and ctx.active:
        ye = ctx.constrain(ye, "model", None, None)
    # combine as the EP path does (`_ep_block` step 8): each token's k
    # expert rows, then one gate-weighted contraction over k — the same
    # summation order, so EP and dense agree bit for bit when nothing drops
    yk = ye[idx, jnp.arange(xt.shape[0])[:, None]]                # (T, k, d)
    comb = jnp.einsum("tkd,tk->td", yk, gates.astype(ye.dtype))
    out = comb.reshape(b, s, d)
    if "shared" in p:
        out = out + apply_mlp(cfg, p["shared"], x).reshape(b, s, d)
    return out, aux


# ---------------------------------------------------------------------------
# Expert-parallel path (shard_map over the "model" axis)
# ---------------------------------------------------------------------------


def _ep_block(cfg: ModelConfig, capacity_src: int, x_loc, router_w, wg, wu, wd):
    """Per-device body. x_loc: (T_m, d) — this rank's EXCLUSIVE token slice
    (the caller does the sequence split); expert banks are local shards
    (E_loc, ...). Returns this rank's token outputs (T_m, d)."""
    msize = compat.axis_size("model")
    t_m, d = x_loc.shape
    k = cfg.experts_per_token
    e_loc = cfg.n_experts // msize

    # 1. route this rank's tokens
    gates, idx, aux = _route(cfg, router_w, x_loc)

    # 2. pack token copies into per-destination capacity buffers
    flat_e = idx.reshape(-1)                                      # (T_m*k,)
    dest = flat_e // e_loc
    order = jnp.argsort(dest, stable=True)                        # group by dest
    sorted_dest = dest[order]
    # rank within destination group
    start = jnp.searchsorted(sorted_dest, jnp.arange(msize))
    rank_in_dest = jnp.arange(t_m * k) - start[sorted_dest]
    slot = jnp.where(rank_in_dest < capacity_src, rank_in_dest, capacity_src)
    send_x = jnp.zeros((msize, capacity_src + 1, d), x_loc.dtype)
    send_e = jnp.full((msize, capacity_src + 1), e_loc, jnp.int32)  # pad expert id
    rows = x_loc[order // k]
    send_x = send_x.at[sorted_dest, slot].set(rows)
    send_e = send_e.at[sorted_dest, slot].set((flat_e % e_loc)[order])
    send_x, send_e = send_x[:, :capacity_src], send_e[:, :capacity_src]

    # 3. exchange: rows travel to the rank that owns their expert
    recv_x = jax.lax.all_to_all(send_x, "model", 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e, "model", 0, 0, tiled=False)
    rows_in = recv_x.reshape(msize * capacity_src, d)
    es_in = recv_e.reshape(msize * capacity_src)

    # 4. bucket by local expert with per-expert capacity (slack over the
    #    balanced expectation; overflow and padding rows land in a dump slot)
    cap_e = int((msize * capacity_src) / e_loc * 1.25) + 8
    cap_e = min(cap_e, msize * capacity_src)
    order2 = jnp.argsort(es_in, stable=True)
    sorted_e = es_in[order2]
    start_e = jnp.searchsorted(sorted_e, jnp.arange(e_loc))
    rank_e = jnp.arange(es_in.shape[0]) - start_e[jnp.clip(sorted_e, 0, e_loc - 1)]
    valid = (sorted_e < e_loc) & (rank_e < cap_e)
    buf = jnp.zeros((e_loc, cap_e + 1, d), x_loc.dtype)   # +1 = dump slot
    buf = buf.at[jnp.where(valid, sorted_e, e_loc - 1),
                 jnp.where(valid, jnp.clip(rank_e, 0, cap_e - 1), cap_e)].set(
        jnp.where(valid[:, None], rows_in[order2], 0.0))
    buf = buf[:, :cap_e]

    # 5. the expert FFNs, one batched einsum over the local bank
    yb = _expert_ffn(cfg, wg, wu, wd, buf)                        # (E_loc, cap_e, d)

    # 6. inverse of step 4: back to arrival order
    y_sorted = jnp.where(valid[:, None],
                         yb[jnp.clip(sorted_e, 0, e_loc - 1),
                            jnp.clip(rank_e, 0, cap_e - 1)], 0.0)
    y_arrival = jnp.zeros_like(rows_in).at[order2].set(y_sorted)

    # 7. return trip + inverse of step 2
    y_send = y_arrival.reshape(msize, capacity_src, d)
    y_back = jax.lax.all_to_all(y_send, "model", 0, 0, tiled=False)
    dropped = rank_in_dest >= capacity_src
    y_copy_sorted = jnp.where(
        dropped[:, None], 0.0,
        y_back[sorted_dest, jnp.clip(slot, 0, capacity_src - 1)])
    y_copies = jnp.zeros((t_m * k, d), x_loc.dtype).at[order].set(y_copy_sorted)

    # 8. gate-weighted combine of the k copies
    y_loc = jnp.einsum("tkd,tk->td", y_copies.reshape(t_m, k, d),
                       gates.astype(x_loc.dtype))
    return y_loc, jax.lax.pmean(aux, "model")


def _bank_spec(w, ctx: ParallelContext):
    """shard_map in_spec for one expert bank: plain (E, d, f) arrays shard
    the leading expert dim; a packed `DispatchedWeight` gets the same cut on
    every payload leaf (the expert dim is its leading stack dim), so each
    rank holds — and its palette/sparse kernels stream — only its own
    experts' compressed payload."""
    if isinstance(w, dsp.DispatchedWeight):
        return w.stack_specs(*ctx.spec("model"))
    return ctx.spec("model", None, None)


def moe_ep(cfg: ModelConfig, p: Params, x: jnp.ndarray, ctx: ParallelContext):
    """x: (B, S, d) sharded over batch axes; experts sharded over 'model'."""
    from jax.experimental.shard_map import shard_map

    b, s, d = x.shape
    msize = ctx.axis_size("model")
    t_m = (b * s) // (_batch_shards(ctx) * msize)
    cap = int(t_m * cfg.experts_per_token / msize * cfg.moe_capacity_factor)
    cap = max(8, ((cap + 7) // 8) * 8)
    # EP+SP fusion: with a sequence-sharded residual stream the MoE output
    # stays sequence-sharded and the per-layer output all-gather disappears
    seq_out = cfg.seq_shard and s % msize == 0

    def body(x_blk, router_w, wg, wu, wd):
        with dsp.use_mesh(None):       # already manual over every axis
            return _body(x_blk, router_w, wg, wu, wd)

    def _body(x_blk, router_w, wg, wu, wd):
        b_loc, s_full, dd = x_blk.shape
        m = jax.lax.axis_index("model")
        if seq_out:
            # per-row sequence split: rank m owns x[:, m*s_m:(m+1)*s_m, :],
            # matching the sequence-sharded out_spec exactly
            s_m = s_full // msize
            x_loc = jax.lax.dynamic_slice_in_dim(
                x_blk, m * s_m, s_m, axis=1).reshape(-1, dd)
            y_loc, aux = _ep_block(cfg, cap, x_loc, router_w, wg, wu, wd)
            return y_loc.reshape(b_loc, s_m, dd), aux[None]
        # flat token split + all-gather back to a replicated block
        tb = b_loc * s_full
        t_m = tb // msize
        x_loc = jax.lax.dynamic_slice_in_dim(
            x_blk.reshape(tb, dd), m * t_m, t_m)
        y_loc, aux = _ep_block(cfg, cap, x_loc, router_w, wg, wu, wd)
        y = jax.lax.all_gather(y_loc, "model", axis=0, tiled=True)
        return y.reshape(x_blk.shape), aux[None]

    pspec_x = ctx.spec(("pod", "data"), None, None)
    out_y_spec = ctx.spec(("pod", "data"), "model", None) if seq_out else pspec_x
    y, aux = shard_map(
        body, mesh=ctx.mesh,
        in_specs=(pspec_x, ctx.spec(None, None), _bank_spec(p["wg"], ctx),
                  _bank_spec(p["wu"], ctx), _bank_spec(p["wd"], ctx)),
        out_specs=(out_y_spec, ctx.spec("model")), check_rep=False,
    )(x, p["router"], p["wg"], p["wu"], p["wd"])
    out = y
    if "shared" in p:
        out = out + apply_mlp(cfg, p["shared"], x)
    return out, aux.mean()


def _batch_shards(ctx: ParallelContext) -> int:
    n = 1
    for a in ctx.batch_axes:
        n *= ctx.axis_size(a)
    return n


# Trace-time route ledger: which MoE path each traced forward compiled into.
# jit caches programs, so counts tick per *trace*, not per step — tests and
# the sharded-serve bench read "ep" > 0 to prove packed banks actually took
# the shard_map path rather than silently falling back to dense.
ROUTE_COUNTS: dict[str, int] = {"ep": 0, "dense": 0}


def moe_forward(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                ctx: ParallelContext):
    """Dispatch to EP when the mesh has a >1 'model' axis and the expert
    count divides it; dense reference otherwise. Packed `DispatchedWeight`
    banks take the same EP path: `shard_map` in_specs cover their payload
    leaves (expert stack dim over 'model'), so each rank streams only its
    local experts' compressed payload."""
    msize = ctx.axis_size("model")
    tokens = x.shape[0] * x.shape[1]
    batch_ok = x.shape[0] % _batch_shards(ctx) == 0
    if (ctx.active and ctx.use_ep and msize > 1 and batch_ok
            and cfg.n_experts % msize == 0
            and tokens % (_batch_shards(ctx) * msize) == 0):
        ROUTE_COUNTS["ep"] += 1
        return moe_ep(cfg, p, x, ctx)
    ROUTE_COUNTS["dense"] += 1
    return moe_dense(cfg, p, x, ctx)
