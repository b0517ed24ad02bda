"""Oracle-backed conformance sweep over the kernel registry.

Every case here is *generated* from `repro.kernels.registry` — there is no
hard-coded kernel list. A kernel family added to the registry is swept
against its ref oracle for every declared dtype and shape class (including
the padding/alignment edge cases), has its VJP checked when it declares one,
gets a sane cost-model entry, and is routed by the capability-gated
dispatcher — for free.

Tiering: the full kernel x dtype x shape sweep is `slow` (it runs Pallas in
interpret mode); a one-case-per-kernel smoke subset stays in the fast lane.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch, hal
from repro.kernels import compat, registry


def _seed(*parts) -> np.random.Generator:
    # deterministic per-case seeding (stable hash: str hash() is salted) so
    # sweep cases are order-independent and reproducible across runs
    digest = hashlib.sha256("|".join(str(p) for p in parts).encode()).digest()
    return np.random.default_rng(int.from_bytes(digest[:4], "little"))


def _check_case(spec, case, dtype):
    inputs = spec.make_inputs(case, dtype, _seed(spec.name, case.name, dtype))
    got = np.asarray(spec.run_kernel(inputs), np.float32)
    ref = np.asarray(spec.run_oracle(inputs), np.float32)
    rtol, atol = spec.tol(dtype)
    np.testing.assert_allclose(
        got, ref, rtol=rtol, atol=atol,
        err_msg=f"{spec.name}/{case.name} diverged from its oracle")


def _sweep_params(edge_only=None):
    for spec, case, dtype in registry.iter_conformance_cases():
        if edge_only is not None and case.edge != edge_only:
            continue
        yield pytest.param(spec, case, dtype,
                           id=f"{spec.name}-{case.name}-{jnp.dtype(dtype).name}")


class TestRegistrySurface:
    def test_registry_is_populated(self):
        # every kernel family the tree ships must be registered — count only,
        # no name list, so new families extend rather than break this
        assert len(registry.names()) >= 6
        assert len(set(registry.names())) == len(registry.names())

    def test_every_spec_declares_edge_cases(self):
        for spec in registry.all_specs():
            assert spec.edge_cases, f"{spec.name} has no padding/alignment case"
            assert spec.dtypes, f"{spec.name} declares no dtypes"

    def test_cost_entries_are_roofline_usable(self):
        for spec in registry.all_specs():
            for case in spec.cases:
                c = spec.cost(case, spec.dtypes[0])
                assert c.flops > 0 and c.bytes > 0, (spec.name, case.name)
                # a cost entry prices on both roofline axes
                t = hal.TPU_V5E
                assert max(c.flops / t.peak_flops,
                           c.bytes / t.hbm_bandwidth) > 0

    def test_capability_ops_exist_in_hal(self):
        # the gate key must be a real row of the op floor on the TPU target,
        # otherwise the dispatcher would silently oracle everything
        for spec in registry.all_specs():
            assert hal.TPU_V5E.attests(spec.capability_op), spec.name

    def test_no_direct_compiler_params_outside_compat(self):
        # the acceptance grep, as a test: kernels reach Pallas compiler params
        # only through the compat surface
        import pathlib
        root = pathlib.Path(__file__).resolve().parents[1] / "src"
        offenders = [str(p) for p in root.rglob("*.py")
                     if p.name != "compat.py"
                     and "CompilerParams" in p.read_text()]
        assert not offenders, offenders


class TestConformanceSmoke:
    """Fast lane: first (non-edge) case x first dtype per registered kernel."""

    @pytest.mark.parametrize(
        "spec", registry.all_specs(), ids=registry.names())
    def test_kernel_matches_oracle(self, spec):
        case = next(c for c in spec.cases if not c.edge)
        _check_case(spec, case, spec.dtypes[0])


@pytest.mark.slow
class TestConformanceSweep:
    """The full generated sweep: kernel x dtype x shape class vs oracle."""

    @pytest.mark.parametrize("spec,case,dtype", _sweep_params(edge_only=False))
    def test_kernel_matches_oracle(self, spec, case, dtype):
        _check_case(spec, case, dtype)


class TestPaddingAlignment:
    """Edge cases (ragged/tiny/off-block shapes) stay in the fast lane at the
    widest dtype — padding bugs are shape bugs, not dtype bugs."""

    @pytest.mark.parametrize("spec,case,dtype", [
        p for p in _sweep_params(edge_only=True)
        if p.values[2] == jnp.float32])
    def test_kernel_matches_oracle(self, spec, case, dtype):
        _check_case(spec, case, dtype)

    @pytest.mark.slow
    @pytest.mark.parametrize("spec,case,dtype", [
        p for p in _sweep_params(edge_only=True)
        if p.values[2] != jnp.float32])
    def test_kernel_matches_oracle_narrow(self, spec, case, dtype):
        _check_case(spec, case, dtype)


class TestVJP:
    """Gradient conformance for every kernel that declares a VJP."""

    @pytest.mark.parametrize(
        "spec",
        [s for s in registry.all_specs() if s.make_vjp is not None],
        ids=[s.name for s in registry.all_specs() if s.make_vjp is not None])
    def test_vjp_matches_oracle(self, spec):
        case = next(c for c in spec.cases if not c.edge)
        inputs = spec.make_inputs(case, jnp.float32, _seed(spec.name, "vjp"))
        kernel_fn, ref_fn, args = spec.make_vjp(inputs)
        argnums = tuple(range(len(args)))
        g_kernel = jax.grad(kernel_fn, argnums)(*args)
        g_ref = jax.grad(ref_fn, argnums)(*args)
        for gk, gr in zip(g_kernel, g_ref):
            np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                       rtol=5e-3, atol=5e-3,
                                       err_msg=f"{spec.name} VJP diverged")


class TestDispatcher:
    """Capability-gated routing: the op-by-device matrix, live."""

    def test_tpu_routes_all_native(self):
        for route in dispatch.KernelDispatcher(hal.TPU_V5E).matrix():
            assert route.native, route

    def test_m1_gates_decode_attention_on_gather(self):
        # H13 attests gather but cannot lower it — the paper's attested-vs-
        # reachable split decides a kernel route here
        d = dispatch.KernelDispatcher(hal.ANE_M1)
        by_name = {r.kernel: r for r in d.matrix()}
        assert not by_name["decode_attention"].native
        assert "gather" in by_name["decode_attention"].reason
        # ...and the gate lifts on the generation that ships gather (H15)
        d3 = dispatch.KernelDispatcher(hal.ANE_M3)
        assert {r.kernel: r for r in d3.matrix()}["decode_attention"].native

    def test_bf16_falls_back_on_ane(self):
        d = dispatch.KernelDispatcher(hal.ANE_M1)
        for route in d.matrix(jnp.bfloat16):
            assert not route.native, route

    def test_oracle_fallback_executes_and_matches(self):
        # a gated route still computes — through the oracle — and agrees with
        # the native path on the same inputs
        spec = registry.get("decode_attention")
        case = spec.cases[0]
        inputs = spec.make_inputs(case, jnp.float32, _seed("fallback"))
        native = dispatch.KernelDispatcher(hal.TPU_V5E)("decode_attention",
                                                        inputs)
        fallback = dispatch.KernelDispatcher(hal.ANE_M1)("decode_attention",
                                                         inputs)
        rtol, atol = spec.tol(jnp.float32)
        np.testing.assert_allclose(np.asarray(native), np.asarray(fallback),
                                   rtol=rtol, atol=atol)

    def test_routes_are_recorded(self):
        d = dispatch.KernelDispatcher(hal.TPU_V5E)
        spec = registry.get("act_lut")
        d("act_lut", spec.make_inputs(spec.cases[0], jnp.float32, _seed("r")))
        assert len(d.routes) == 1 and d.routes[0].kernel == "act_lut"

    def test_full_matrix_covers_all_targets(self):
        rows = dispatch.kernel_matrix()
        assert len(rows) == len(hal.TARGETS) * len(registry.names())


class TestCompatLayer:
    def test_compiler_params_class_resolved(self):
        # the surface produces the params object pallas_call takes
        kw = compat.pallas_call_params(
            dimension_semantics=("parallel", "arbitrary"))
        assert set(kw) == {"compiler_params"}
        assert kw["compiler_params"].dimension_semantics == (
            "parallel", "arbitrary")

    def test_unknown_fields_are_dropped(self):
        # a field the installed params class does not know is an error,
        # not a silently ignored hint
        with pytest.raises(TypeError):
            compat.compiler_params(dimension_semantics=("parallel",),
                                   field_from_the_future=1)

    def test_tree_flatten_with_path(self):
        leaves, _ = compat.tree_flatten_with_path({"a": {"b": jnp.ones(2)}})
        (path, leaf), = leaves
        assert compat.tree_path_str(path) == "a/b"
        assert leaf.shape == (2,)
