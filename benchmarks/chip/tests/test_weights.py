import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import BENCH_DIR, check, weights

M = {"n_layers": 3, "d_model": 32, "n_heads": 4, "n_kv_heads": 2,
     "d_head": 8, "d_ff": 48, "vocab": 64, "tie_embeddings": True}
dense = check.load_reference(BENCH_DIR, "dense_gqa")


def test_stacked_layers_equal_layers_made_alone():
    """The program's stacked weights and the reference's per-layer
    regeneration are the same numbers."""
    key = weights.root_key(2 ** 40 + 17)
    st = jax.jit(lambda k: weights.stacked(lambda i: dense.layer(k, i, M),
                                           M["n_layers"]))(key)
    for i in range(M["n_layers"]):
        one = jax.jit(lambda k, i: dense.layer(k, i, M))(key, i)
        for name in dense.LAYER_LEAVES:
            assert np.array_equal(np.asarray(st[name][i], np.float32),
                                  np.asarray(one[name], np.float32)), name


def test_served_type_and_scale():
    w = dense.layer(weights.root_key(3), 0, M)
    assert w["wq"].dtype == jnp.bfloat16 and w["attn_norm"].dtype == jnp.float32
    assert float(jnp.std(w["wg"].astype(jnp.float32))) == pytest.approx(
        M["d_model"] ** -0.5, rel=0.1)
    o = dense.outer(weights.root_key(3), M)
    assert o["embed"].shape == (64, 32) and "unembed" not in o
    u = dense.outer(weights.root_key(3), dict(M, tie_embeddings=False))
    assert u["unembed"].shape == (32, 64) and u["unembed"].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(u["embed"], np.float32),
                          np.asarray(o["embed"], np.float32))


def test_seeds_differ_and_repeat():
    a = dense.outer(weights.root_key(2 ** 33), M)["embed"]
    b = dense.outer(weights.root_key(2 ** 33), M)["embed"]
    c = dense.outer(weights.root_key(2 ** 33 + 1), M)["embed"]
    assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert not np.array_equal(np.asarray(a, np.float32),
                              np.asarray(c, np.float32))
    with pytest.raises(ValueError):
        weights.root_key(-1)


def test_named_key_folds_name_then_indices():
    key = weights.root_key(5)
    same = weights.named_key(key, "moe/wg", 3, 7)
    assert np.array_equal(jax.random.key_data(same), jax.random.key_data(
        weights.named_key(key, "moe/wg", 3, 7)))
    for other in (weights.named_key(key, "moe/wu", 3, 7),
                  weights.named_key(key, "moe/wg", 3, 8),
                  weights.named_key(key, "moe/wg", 4, 7)):
        assert not np.array_equal(jax.random.key_data(same),
                                  jax.random.key_data(other))


def test_dense_module_refuses_an_unknown_leaf():
    with pytest.raises(KeyError):
        dense.program_leaf(weights.root_key(1), M, "layers/1/sub0/moe/wg",
                           (2, 4, 32, 48))
    with pytest.raises(ValueError, match="does not fill"):
        dense.program_leaf(weights.root_key(1), M, "embed/table", (128, 32))

