"""KernelDispatcher routing decisions + serving-residency regressions.

Three batteries:

  * a property-based sweep (hypothesis): for ANY (registered kernel, HAL
    target, activation dtype) the resolved route must be *legal* — the
    chosen kernel is registered, a native route passes every capability
    gate, and the oracle fires exactly when one gate fails (including the
    unknown-dtype and op-floor edge cases);
  * decode-step residency: the KV cache stays donated/resident across N
    dispatches — shapes and dtypes unchanged, and the decode program's
    content hash is stable, so no step forces a recompile or a host
    round-trip through a new buffer;
  * weight-form tags survive the checkpoint boundary.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import dispatch, hal
from repro.kernels import registry
from repro.launch.serve import _merge_prefill
from repro.models.model import build_model
from repro.optim.compression import (compress_model_params,
                                     weight_form_census)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:              # the exhaustive sweep below still runs
    HAVE_HYPOTHESIS = False

DTYPES = ("float32", "bfloat16", "float16", "int8", "float64", "int32")


def _dtype_surface(spec) -> set[str]:
    return {jnp.dtype(d).name for d in spec.dtypes}


def _check_route_legal(name: str, target_name: str, dtype: str) -> None:
    """Any (kernel, target, dtype) cell resolves to a registered kernel
    whose native leg is capability-legal, with oracle fallback exactly when
    a gate fails."""
    target = hal.get_target(target_name)
    route = dispatch.KernelDispatcher(target).resolve(name, dtype)
    spec = registry.get(route.kernel)              # registered, or KeyError
    assert route.kernel == name
    assert route.target == target.name
    assert route.backend in ("pallas", "oracle")

    dtype_ok = dtype in _dtype_surface(spec)
    op_ok = target.attests(spec.capability_op) and \
        target.reaches(spec.capability_op)
    stream_ok = spec.weight_form is None or target.streams(spec.weight_form)
    datapath_ok = target.supports_dtype(dtype)
    all_gates = dtype_ok and op_ok and stream_ok and datapath_ok

    if route.native:
        assert all_gates, (route, dtype_ok, op_ok, stream_ok, datapath_ok)
        assert route.reason == ""
    else:
        # fallback fires exactly when gated, and says why
        assert not all_gates, route
        assert route.reason


class TestRoutingExhaustive:
    """The full (kernel x target x dtype) cube, deterministically — the
    matrix is small enough to enumerate, so no cell ever goes unchecked."""

    @pytest.mark.parametrize("target_name", sorted(hal.TARGETS))
    def test_every_cell_is_legal(self, target_name):
        for name in registry.names():
            for dtype in DTYPES:
                _check_route_legal(name, target_name, dtype)

    def test_matrix_rows_agree_with_resolve(self):
        """The census (`matrix()`) and point resolution never disagree."""
        for target_name in sorted(hal.TARGETS):
            d = dispatch.KernelDispatcher(hal.get_target(target_name))
            for dtype in DTYPES[:3]:
                by_name = {r.kernel: r for r in d.matrix(dtype)}
                for name in registry.names():
                    assert by_name[name] == d.resolve(name, dtype)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
class TestRoutingProperty:
    """Property form of the same invariant (hypothesis shrinks failures to
    a minimal cell); extends past the pinned dtype list via dtype names
    drawn from jnp itself."""

    if HAVE_HYPOTHESIS:
        @settings(max_examples=200, deadline=None)
        @given(name=st.sampled_from(registry.names()),
               target_name=st.sampled_from(sorted(hal.TARGETS)),
               dtype=st.sampled_from(DTYPES + ("uint8", "int16", "float64")))
        def test_route_is_legal(self, name, target_name, dtype):
            _check_route_legal(name, target_name, dtype)


class TestRoutingEdges:
    # -- pinned edge cells of the op-by-device matrix -----------------------
    def test_unknown_dtype_routes_to_oracle(self):
        route = dispatch.KernelDispatcher(hal.TPU_V5E).resolve(
            "anemm", jnp.int8)
        assert route.backend == "oracle"
        assert "dtype" in route.reason

    def test_op_floor_gates_decode_attention_on_m1(self):
        # gather is absent from the H13 op table (hal.T4.1)
        route = dispatch.KernelDispatcher(hal.ANE_M1).resolve(
            "decode_attention", jnp.float32)
        assert route.backend == "oracle"
        assert "gather" in route.reason

    def test_non_native_dtype_gates_on_ane(self):
        # the ANE datapath is fp16-only: bf16 activations must fold back
        route = dispatch.KernelDispatcher(hal.ANE_M1).resolve(
            "anemm", jnp.bfloat16)
        assert route.backend == "oracle"

    def test_unknown_kernel_raises(self):
        with pytest.raises(KeyError):
            dispatch.KernelDispatcher(hal.TPU_V5E).resolve("nope")


# ---------------------------------------------------------------------------
# Decode residency: donated KV caches across N dispatches
# ---------------------------------------------------------------------------


def _tree_spec(tree):
    return jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)


class TestDecodeResidency:
    def test_kv_cache_resident_across_dispatches(self):
        """N decode steps against a donated cache: the cache pytree keeps
        its exact shapes/dtypes (the buffer is rebound, never reshaped or
        host-copied) and the decode program's content hash is stable — no
        step requires a new compile."""
        cfg = configs.get_smoke("tinyllama-1.1b")
        disp = dispatch.KernelDispatcher(hal.TPU_V5E)
        model = build_model(cfg, dispatcher=disp)
        params = model.init(jax.random.PRNGKey(0))
        b, s, n_steps = 2, 16, 6
        batch = {"tokens": jnp.ones((b, s), jnp.int32)}
        pf_caches, lg = jax.jit(model.prefill)(params, batch)
        caches = _merge_prefill(model, model.init_cache(b, s + n_steps + 1),
                                pf_caches, s)

        spec0 = _tree_spec(caches)
        tok = jnp.ones((b, 1), jnp.int32)
        pos0 = jnp.full((b,), s, jnp.int32)
        key0 = dispatch.content_hash(model.decode_step,
                                     (params, caches, tok, pos0))

        cache_mgr = dispatch.ProgramCache()
        decode, _ = cache_mgr.compile(model.decode_step, params, caches, tok,
                                      pos0, jit_kwargs={"donate_argnums": (1,)})
        for i in range(n_steps):
            pos = jnp.full((b,), s + i, jnp.int32)
            caches, lg = decode(params, caches, tok, pos)
            tok = jnp.argmax(lg[:, -1, : cfg.vocab], -1).astype(
                jnp.int32)[:, None]
            # resident-state invariant: the updated cache is bit-compatible
            # with the donated slot — same structure, shapes, dtypes
            assert _tree_spec(caches) == spec0
        # content-hash stability: the program for step N is the program for
        # step 0 — nothing about the evolved cache forces a recompile
        assert dispatch.content_hash(
            model.decode_step, (params, caches, tok, pos)) == key0
        assert not cache_mgr.is_new_compile_required(
            model.decode_step, params, caches, tok, pos)
        assert cache_mgr.stats.misses == 1
        # and the cache really advanced (the steps were not no-ops)
        pos_rows = np.asarray(caches[0]["sub0"]["pos"])
        assert (pos_rows >= s).any()

    def test_content_hash_distinguishes_shapes(self):
        cfg = configs.get_smoke("tinyllama-1.1b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        b1 = {"tokens": jnp.ones((2, 16), jnp.int32)}
        b2 = {"tokens": jnp.ones((2, 24), jnp.int32)}
        k1 = dispatch.content_hash(model.prefill, (params, b1))
        k2 = dispatch.content_hash(model.prefill, (params, b2))
        assert k1 != k2

    def test_content_hash_stable_across_traces(self):
        """Regression: custom_vjp closures print object addresses into the
        jaxpr; the hash must scrub them or every retrace is a cache miss."""
        cfg = configs.get_smoke("tinyllama-1.1b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((2, 16), jnp.int32)}
        keys = {dispatch.content_hash(model.prefill, (params, batch))
                for _ in range(3)}
        assert len(keys) == 1

    def test_content_hash_raises_on_untraceable(self):
        """A program that cannot be traced has no content to key on: the
        hash raises instead of keying by function name, which would let two
        different programs share one ProgramCache entry."""
        def branchy(x):
            return x + 1 if x.sum() > 0 else x - 1   # concrete bool of a tracer

        with pytest.raises(jax.errors.TracerBoolConversionError):
            dispatch.content_hash(branchy, (jnp.ones(3),))


# ---------------------------------------------------------------------------
# Weight-form tags across the checkpoint boundary
# ---------------------------------------------------------------------------


class TestWeightFormPersistence:
    def test_checkpoint_round_trips_packed_params(self, tmp_path):
        from repro.checkpoint.checkpoint import CheckpointManager

        cfg = configs.get_smoke("tinyllama-1.1b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        cparams = compress_model_params(params, "sparse")
        census = weight_form_census(cparams)
        assert census and set(census.values()) == {"sparse"}

        mgr = CheckpointManager(str(tmp_path))
        mgr.save(7, cparams)
        restored, step = mgr.restore(cparams)
        assert step == 7
        rcensus = weight_form_census(restored)
        assert rcensus == census
        for a, b in zip(jax.tree.leaves(cparams), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_form_mismatch_is_rejected(self, tmp_path):
        from repro.checkpoint.checkpoint import CheckpointManager

        cfg = configs.get_smoke("tinyllama-1.1b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, compress_model_params(params, "sparse"))
        with pytest.raises(ValueError, match="weight form"):
            mgr.restore(compress_model_params(params, "int4_palette"))
        # a dense-saved checkpoint into a packed template is also a form
        # mismatch, not a bare missing-key crash
        mgr.save(2, params)
        with pytest.raises(ValueError, match="weight form"):
            mgr.restore(compress_model_params(params, "sparse"), step=2)

    def test_restore_placer_never_sees_form_markers(self, tmp_path):
        """Elastic restore device_puts every array through a placer; the
        weight-form marker is a host-side string and must bypass it."""
        from repro.checkpoint.checkpoint import CheckpointManager

        cfg = configs.get_smoke("tinyllama-1.1b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        cparams = compress_model_params(params, "int4_palette")
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, cparams)

        seen = []

        def placer(path, arr):
            seen.append(path)
            assert arr.dtype.kind != "U", f"string marker reached placer: {path}"
            return jnp.asarray(arr)

        restored, _ = mgr.restore(cparams, placer=placer)
        assert seen
        assert weight_form_census(restored) == weight_form_census(cparams)

    def test_planner_spares_non_matmul_leaves(self):
        cfg = configs.get_smoke("dbrx-132b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        cparams = compress_model_params(params, "int4_palette")
        packed = weight_form_census(cparams)
        assert packed, "MoE config must pack expert banks"
        # routing tables, norms and the embedding gather table stay dense
        for path in packed:
            assert "router" not in path
            assert "scale" not in path and "ln" not in path.split("/")[-1]
            assert not path.endswith("table")
