import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights

M = {"n_layers": 3, "d_model": 32, "n_heads": 4, "n_kv_heads": 2,
     "d_head": 8, "d_ff": 48, "vocab": 64}


def test_stacked_layers_equal_layers_made_alone():
    """The program's stacked weights and the reference's per-layer
    regeneration are the same numbers."""
    key = weights.root_key(2 ** 40 + 17)
    st = jax.jit(lambda k: weights.stacked(k, M))(key)
    for i in range(M["n_layers"]):
        one = jax.jit(lambda k, i: weights.layer(k, i, M))(key, i)
        for name in weights.LAYER_LEAVES:
            assert np.array_equal(np.asarray(st[name][i], np.float32),
                                  np.asarray(one[name], np.float32)), name


def test_served_type_and_scale():
    w = weights.layer(weights.root_key(3), 0, M)
    assert w["wq"].dtype == jnp.bfloat16 and w["attn_norm"].dtype == jnp.float32
    assert float(jnp.std(w["wg"].astype(jnp.float32))) == pytest.approx(
        M["d_model"] ** -0.5, rel=0.1)
    o = weights.outer(weights.root_key(3), M)
    assert o["embed"].shape == (64, 32)


def test_seeds_differ_and_repeat():
    a = weights.outer(weights.root_key(2 ** 33), M)["embed"]
    b = weights.outer(weights.root_key(2 ** 33), M)["embed"]
    c = weights.outer(weights.root_key(2 ** 33 + 1), M)["embed"]
    assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert not np.array_equal(np.asarray(a, np.float32),
                              np.asarray(c, np.float32))
    with pytest.raises(ValueError):
        weights.root_key(-1)
