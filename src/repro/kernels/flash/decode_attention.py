"""flash decode: one-token GQA attention against a long KV cache.

The serving hot path (paper ch.14): a single query row per sequence scanned
against a 32k-500k entry cache. Decode attention is pure weight/cache
streaming — arithmetic intensity ~1 — so the kernel's job is to keep HBM
reads perfectly sequential and the softmax state in VMEM:

grid (B, S/bk), KV innermost; one step loads a (bk, KV*d) slab of the cache
— every kv head of bk slots, contiguous rows — and walks the kv heads with
static lane slices, carrying the online-softmax (m, l, acc) of each head's
g grouped query heads in VMEM scratch. The cache is read through its free
(B, S, KV*d) view, so every block's last two dims are either full or
(8, 128)-tiled, as Mosaic requires. The decode position is a scalar-
prefetch operand (SMEM); invalid cache slots (beyond the written length, or
outside a rolling window) mask via the positions array, which streams
alongside the cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat
from repro.kernels.common import cdiv, pad_to

NEG_INF = -1e30


def _attend_block(q_ref, k_ref, v_ref, valid, m_ref, l_ref, acc_ref, *,
                  kvh: int, d: int, scale: float):
    """One cache block for every kv head: q_ref (1, KV, g, d), k/v_ref
    (1, bk, KV*d), valid (1, bk). Per head: one (g, d) x (bk, d)
    online-softmax update, shared by the monolithic and paged kernels so
    the two stay bit-identical."""
    for h in range(kvh):
        q = q_ref[0, h].astype(jnp.float32)                   # (g, d)
        k = k_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)                      # (g, bk)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
        m_ref[h] = m_new
        v = v_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _store(o_ref, l_ref, acc_ref, out_dtype):
    o_ref[0] = (acc_ref[...]
                / jnp.maximum(l_ref[...], 1e-30)).astype(out_dtype)


def _scratch(kvh: int, g: int, d: int):
    return [pltpu.VMEM((kvh, g, 1), jnp.float32),
            pltpu.VMEM((kvh, g, 1), jnp.float32),
            pltpu.VMEM((kvh, g, d), jnp.float32)]


def _kernel(cur_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, nk: int, kvh: int, d: int,
            scale: float, window, out_dtype):
    bi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    pos = pos_ref[0]                                  # (1, bk) written positions
    cur = cur_ref[bi]
    valid = (pos >= 0) & (pos <= cur)
    if window is not None:
        valid &= (cur - pos) < window
    _attend_block(q_ref, k_ref, v_ref, valid, m_ref, l_ref, acc_ref,
                  kvh=kvh, d=d, scale=scale)

    @pl.when(ki == nk - 1)
    def _done():
        _store(o_ref, l_ref, acc_ref, out_dtype)


@functools.partial(jax.jit, static_argnames=("window", "bk", "scale"))
def decode_attention(
    q: jnp.ndarray,            # (B, H, d) one query row per sequence
    k_cache: jnp.ndarray,      # (B, S, KV, d)
    v_cache: jnp.ndarray,      # (B, S, KV, d)
    positions: jnp.ndarray,    # (B, S) written absolute position per slot (-1 empty)
    current: jnp.ndarray,      # (B,) current decode position
    *,
    window: int | None = None,
    bk: int = 512,
    scale: float | None = None,
) -> jnp.ndarray:
    b, h, d = q.shape
    _, s, kvh, _ = k_cache.shape
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    bk = min(bk, max(s, 8))
    # (B, S, KV*d): a free view; one block row is every kv head of one slot
    kp = pad_to(k_cache.reshape(b, s, kvh * d), 1, bk)
    vp = pad_to(v_cache.reshape(b, s, kvh * d), 1, bk)
    # padded slots must read as empty
    pp = jnp.pad(positions, ((0, 0), (0, kp.shape[1] - s)),
                 constant_values=-1).reshape(b, 1, -1)
    nk = cdiv(kp.shape[1], bk)
    # (B, KVH, g, d) query layout: kv-head-major groups
    qg = q.reshape(b, kvh, g, d)

    grid_spec = compat.prefetch_grid_spec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, kvh, g, d), lambda bi, ki, c: (bi, 0, 0, 0)),
            pl.BlockSpec((1, bk, kvh * d), lambda bi, ki, c: (bi, ki, 0)),
            pl.BlockSpec((1, bk, kvh * d), lambda bi, ki, c: (bi, ki, 0)),
            pl.BlockSpec((1, 1, bk), lambda bi, ki, c: (bi, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, kvh, g, d),
                               lambda bi, ki, c: (bi, 0, 0, 0)),
        scratch_shapes=_scratch(kvh, g, d),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, kvh=kvh, d=d, scale=scale,
                          window=window, out_dtype=q.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=compat.interpret_mode(),
        **compat.pallas_call_params(
            dimension_semantics=("parallel", "arbitrary")),
    )(current.astype(jnp.int32), qg, kp, vp, pp)
    return out.reshape(b, h, d)


def decode_attention_ref(q, k_cache, v_cache, positions, current,
                         *, window=None, scale=None):
    """jnp oracle (mirrors models/attention._decode_attention)."""
    b, h, d = q.shape
    _, s, kvh, _ = k_cache.shape
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, kvh, g, d)
    sc = jnp.einsum("bkgd,bckd->bkgc", qg.astype(jnp.float32),
                    k_cache.astype(jnp.float32)) * scale
    valid = (positions >= 0) & (positions <= current[:, None])
    if window is not None:
        valid &= (current[:, None] - positions) < window
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgc,bckd->bkgd", w, v_cache.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged decode: the KV cache lives in a shared block arena and each lane
# reads it through a page table (launch/kv_pool.py builds both). One grid
# step processes one page; the BlockSpec index maps resolve the arena block
# from the scalar-prefetched table, so the gather happens in the DMA engine,
# not as a materialized copy.
# ---------------------------------------------------------------------------


def _paged_kernel(bt_ref, cur_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, nb: int, kvh: int, d: int,
                  scale: float, window, out_dtype):
    bi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    pos = pos_ref[0]                                  # (1, bs) written positions
    cur = cur_ref[bi]
    mapped = bt_ref[bi, ki] >= 0                      # -1 = unmapped page
    valid = mapped & (pos >= 0) & (pos <= cur)
    if window is not None:
        valid &= (cur - pos) < window
    _attend_block(q_ref, k_ref, v_ref, valid, m_ref, l_ref, acc_ref,
                  kvh=kvh, d=d, scale=scale)

    @pl.when(ki == nb - 1)
    def _done():
        _store(o_ref, l_ref, acc_ref, out_dtype)


def gather_pages(arena: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Materialize a per-lane view from a block arena: ``arena`` is
    ``(N, bs, ...)``, ``block_tables`` is ``(B, nb)`` int32 with -1 for
    unmapped pages (clamped to row 0; callers mask via positions/table).
    Returns ``(B, nb*bs, ...)`` — the monolithic-slab layout."""
    n, bs = arena.shape[:2]
    b, nb = block_tables.shape
    g = jnp.take(arena, jnp.maximum(block_tables, 0).reshape(-1), axis=0)
    return g.reshape((b, nb * bs) + arena.shape[2:])


@functools.partial(jax.jit, static_argnames=("window", "scale"))
def paged_decode_attention(
    q: jnp.ndarray,            # (B, H, d) one query row per sequence
    k_arena: jnp.ndarray,      # (N, bs, KV, d) shared block arena
    v_arena: jnp.ndarray,      # (N, bs, KV, d)
    pos_arena: jnp.ndarray,    # (N, bs) written absolute position per slot
    block_tables: jnp.ndarray,  # (B, nb) arena block per lane page; -1 empty
    current: jnp.ndarray,      # (B,) current decode position
    *,
    window: int | None = None,
    scale: float | None = None,
) -> jnp.ndarray:
    b, h, d = q.shape
    n, bs, kvh, _ = k_arena.shape
    nb = block_tables.shape[1]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, kvh, g, d)
    bt = block_tables.astype(jnp.int32)

    def page(bi, ki, t, c):                 # arena block of lane page ki
        return jnp.maximum(t[bi, ki], 0), 0, 0

    grid_spec = compat.prefetch_grid_spec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, kvh, g, d), lambda bi, ki, t, c: (bi, 0, 0, 0)),
            pl.BlockSpec((1, bs, kvh * d), page),
            pl.BlockSpec((1, bs, kvh * d), page),
            pl.BlockSpec((1, 1, bs), page),
        ],
        out_specs=pl.BlockSpec((1, kvh, g, d),
                               lambda bi, ki, t, c: (bi, 0, 0, 0)),
        scratch_shapes=_scratch(kvh, g, d),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, nb=nb, kvh=kvh, d=d, scale=scale,
                          window=window, out_dtype=q.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=compat.interpret_mode(),
        **compat.pallas_call_params(
            dimension_semantics=("parallel", "arbitrary")),
    )(bt, current.astype(jnp.int32), qg,
      k_arena.reshape(n, bs, kvh * d), v_arena.reshape(n, bs, kvh * d),
      pos_arena.reshape(n, 1, bs))
    return out.reshape(b, h, d)


def paged_decode_attention_ref(q, k_arena, v_arena, pos_arena, block_tables,
                               current, *, window=None, scale=None):
    """jnp oracle: materialize the page-table gather, then the monolithic
    oracle. Unmapped pages (-1) read as empty slots."""
    bs = k_arena.shape[1]
    bt = block_tables.astype(jnp.int32)
    k_cache = gather_pages(k_arena, bt)
    v_cache = gather_pages(v_arena, bt)
    pos = jnp.where(
        jnp.repeat(bt >= 0, bs, axis=1), gather_pages(pos_arena, bt), -1)
    return decode_attention_ref(q, k_cache, v_cache, pos, current,
                                window=window, scale=scale)
