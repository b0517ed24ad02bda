"""Ring-attention serving-path suite (tier: chunked prefill/long context).

`parallel.ring_attention` is the context-parallel prefill route: the
sequence axis shards over the mesh's "model" axis, KV blocks rotate around
the ring (`ppermute`) while every rank accumulates its local queries'
online softmax. These tests pin the serve-facing wrapper `ring_prefill`:

  * numerical parity against the monolithic flash path
    (`chunked_attention`) at serve shapes — ring multiples, chunk
    boundaries, ragged tails that need padding, GQA head groups;
  * the degenerate ring (null context / 1-rank model axis) falls back to
    the flash path *exactly* (bit-identical, no padding round trip);
  * routed end to end: a mesh scheduler with `ring_prefill_min` set emits
    greedy token streams identical to the single-device run.

Multi-device cases force 8 CPU devices via XLA_FLAGS in a subprocess-free
way only when the session already has them; otherwise they skip loudly.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import hal
from repro.core.dispatch import ExecutionStream, ProgramCache
from repro.launch.mesh import make_mesh
from repro.launch.scheduler import Request, ServeConfig, build_scheduler
from repro.models.attention import chunked_attention
from repro.models.model import build_model
from repro.parallel.ctx import ParallelContext
from repro.parallel.ring_attention import ring_prefill

V5E = hal.get_target("tpu-v5e")

_multi = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@functools.lru_cache(maxsize=None)
def _ring_ctx(min_tokens=1):
    mesh = make_mesh((2, 4), ("data", "model"))
    return dataclasses.replace(ParallelContext(mesh=mesh),
                               ring_prefill_min=min_tokens)


def _qkv(s, *, b=2, h=8, kvh=4, dh=16, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kvh, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kvh, dh)), jnp.float32)
    return q, k, v


# ---------------------------------------------------------------------------
# Parity at serve shapes
# ---------------------------------------------------------------------------

@_multi
@pytest.mark.parametrize("s", [64,    # ring multiple
                               96,    # prefill-chunk boundary (12 x 8)
                               17,    # ragged: pads 17 -> 20 on a 4-ring
                               23,    # prime, maximal padding
                               4])    # one token per rank
def test_ring_prefill_matches_flash(s):
    q, k, v = _qkv(s)
    ref = chunked_attention(q, k, v, causal=True)
    out = ring_prefill(q, k, v, _ring_ctx(), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@_multi
def test_ring_prefill_scale_override():
    q, k, v = _qkv(32)
    ref = chunked_attention(q, k, v, causal=True, scale=0.5)
    out = ring_prefill(q, k, v, _ring_ctx(), causal=True, scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_degenerate_ring_is_exact_fallback():
    """Null context and 1-rank rings take the flash path bit-identically:
    no padding, no shard_map, no ulp drift."""
    q, k, v = _qkv(23)
    ref = np.asarray(chunked_attention(q, k, v, causal=True))
    np.testing.assert_array_equal(
        np.asarray(ring_prefill(q, k, v, None, causal=True)), ref)
    np.testing.assert_array_equal(
        np.asarray(ring_prefill(q, k, v, ParallelContext(mesh=None),
                                causal=True)), ref)


# ---------------------------------------------------------------------------
# Routed end to end through the scheduler
# ---------------------------------------------------------------------------

def _serve_tokens(ctx, lens=(24, 33, 17), gen=5):
    cfg = configs.get_smoke("tinyllama-1.1b")
    model = build_model(cfg, ctx)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=(L,)).astype(np.int32),
                    max_new_tokens=gen) for i, L in enumerate(lens)]
    config = ServeConfig(schedule="continuous", max_len=max(lens) + gen,
                         n_slots=len(lens),
                         stream=ExecutionStream(ProgramCache(), target=V5E),
                         ctx=ctx)
    sched = build_scheduler(config, model, params, cfg)
    return {r.rid: r.tokens for r in sched.run(reqs)}


@_multi
def test_ring_routed_serve_matches_single_device():
    """`ring_prefill_min` on a live mesh: every monolithic prefill of >=
    min tokens routes through the ring, and greedy streams stay identical
    to the single-device scheduler (argmax survives the ulp drift at smoke
    scale; this is the same parity bar every serve schedule meets)."""
    single = _serve_tokens(ParallelContext(mesh=None))
    ringed = _serve_tokens(_ring_ctx(min_tokens=8))
    for rid in single:
        np.testing.assert_array_equal(single[rid], ringed[rid])


@_multi
def test_ring_off_by_default_on_mesh():
    """Without opting in, a mesh context keeps ring routing OFF —
    `ring_prefill_min` defaults to None, protecting the bit-parity
    guarantee mesh serving CI gates."""
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelContext(mesh=mesh)
    assert ctx.ring_prefill_min is None
    single = _serve_tokens(ParallelContext(mesh=None))
    meshed = _serve_tokens(ctx)
    for rid in single:
        np.testing.assert_array_equal(single[rid], meshed[rid])
