"""The routed logits head reads its weight as stored.

`layers.logits` under a dispatcher hands the activations (bf16) and the
head weight — the tied embedding table as (V, d), or the untied `unembed`
as (d, V) — to `anemm_head`, which accumulates in fp32 and stores fp32. It
must compute what the unrouted `dot_general(bf16, bf16,
preferred_element_type=fp32)` computes, up to fp32 summation order, with a
vocabulary that leaves the last column block ragged; its VJP must match
`jax.grad` of the plain path; and the serving programs must hold no fp32
cast and no transpose of the table.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
import numpy as np
import pytest

from repro import configs
from repro.core.dispatch import KernelDispatcher
from repro.kernels import compat
from repro.kernels.anemm.anemm import head_blocks
from repro.models import dispatched as dsp
from repro.models import layers
from repro.models.model import build_model

K, VOCAB = 128, 5000          # 5000 = 2 x 2048 + 904: a ragged last block
BF16 = jnp.bfloat16


def _head_params(tied: bool, seed: int = 0):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(size=(VOCAB, K)), BF16)
    p = {"table": table}
    if not tied:
        p["unembed"] = jnp.asarray(rng.normal(size=(K, VOCAB)), BF16)
    w = table if tied else p["unembed"]
    return types.SimpleNamespace(tie_embeddings=tied), p, w


def _routed(cfg, p, x):
    disp = KernelDispatcher()
    with dsp.use_dispatcher(disp):
        out = jax.jit(lambda p, x: layers.logits(cfg, p, x))(p, x)
    return out, disp


@pytest.mark.parametrize("m", [1, 16])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_routed_head_matches_plain(tied, m):
    assert VOCAB % head_blocks(m, K, VOCAB, 2)[1], "want a ragged last block"
    cfg, p, w = _head_params(tied)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(m, 1, K)), BF16)
    plain = layers.logits(cfg, p, x)
    routed, disp = _routed(cfg, p, x)
    assert routed.dtype == plain.dtype == jnp.float32
    assert routed.shape == (m, 1, VOCAB)
    assert [(r.kernel, r.dtype, r.backend) for r in disp.routes] == \
        [("anemm", "float32", "pallas")]
    # fp32 summation order only: |error| <= K * 2^-24 * sum_k |x_k w_k|
    w32 = np.asarray(w, np.float32)
    mag = np.abs(np.asarray(x, np.float32)) @ np.abs(w32.T if tied else w32)
    err = np.abs(np.asarray(routed) - np.asarray(plain))
    assert np.all(err <= K * 2.0 ** -24 * mag)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_head_vjp_matches_plain(tied):
    cfg, p, w = _head_params(tied, seed=2)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 1, K)), BF16)
    r = jnp.asarray(rng.normal(size=(4, 1, VOCAB)), jnp.float32)
    key = "table" if tied else "unembed"

    def loss(x, w):
        return (layers.logits(cfg, {**p, key: w}, x) * r).sum()

    dx, dw = jax.grad(loss, (0, 1))(x, w)
    disp = KernelDispatcher()
    with dsp.use_dispatcher(disp):
        rdx, rdw = jax.jit(jax.grad(loss, (0, 1)))(x, w)
    assert {r.backend for r in disp.routes} == {"pallas"}
    assert (rdx.dtype, rdw.dtype) == (x.dtype, w.dtype)
    assert rdw.shape == w.shape
    # both sides sum in fp32 and round once to bf16: one bf16 ulp apart
    for got, want in ((rdx, dx), (rdw, dw)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2.0 ** -7, atol=1e-6)


def _aliases_reach(jaxpr, aliases: set, found: list) -> None:
    """Walk `jaxpr` and every jaxpr nested in it, following the variables
    in `aliases` into the bodies they are passed to; collect each equation
    that consumes one of them, with the operands that are aliases. A Pallas
    kernel body is not entered: its operands are the equation's own."""
    for eqn in jaxpr.eqns:
        hits = [v for v in eqn.invars
                if not isinstance(v, jex_core.Literal) and v in aliases]
        if not hits:
            continue
        found.append((eqn, hits))
        if eqn.primitive.name == "pallas_call":
            continue
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if not isinstance(sub, jex_core.Jaxpr):
                    continue
                # a body's inputs are the equation's trailing operands (a
                # cond's branches skip the predicate)
                outer = eqn.invars[len(eqn.invars) - len(sub.invars):]
                inner = {i for o, i in zip(outer, sub.invars) if o in hits}
                if inner:
                    _aliases_reach(sub, aliases | inner, found)


def _table_uses(fn, params, *args):
    closed = jax.make_jaxpr(fn)(params, *args)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    idx = [i for i, (path, _) in enumerate(leaves)
           if compat.tree_path_str(path) == "embed/table"]
    assert len(idx) == 1
    found: list = []
    _aliases_reach(closed.jaxpr, {closed.jaxpr.invars[idx[0]]}, found)
    return found


def test_serving_programs_read_the_tied_table_as_stored():
    cfg = dataclasses.replace(configs.get_smoke("phi4-mini-3.8b"),
                              dtype="bfloat16")
    assert cfg.tie_embeddings
    model = build_model(cfg, dispatcher=KernelDispatcher())
    params = model.init(jax.random.PRNGKey(0))
    table = params["embed"]["table"]
    b, s = 2, 8
    tokens = jnp.zeros((b, s), jnp.int32)
    caches = model.init_cache(b, 16)
    programs = {
        "prefill": (model.prefill, ({"tokens": tokens},)),
        "decode_step": (model.decode_step,
                        (caches, tokens[:, :1], jnp.full((b,), s, jnp.int32))),
    }
    for name, (fn, args) in programs.items():
        uses = _table_uses(fn, params, *args)
        prims = [e.primitive.name for e, _ in uses]
        assert "transpose" not in prims, name
        assert "convert_element_type" not in prims, f"{name}: table cast"
        heads = [hits for e, hits in uses if e.primitive.name == "pallas_call"]
        assert len(heads) == 1, f"{name}: {prims}"
        assert [(v.aval.shape, v.aval.dtype) for v in heads[0]] == \
            [(table.shape, table.dtype)], name
