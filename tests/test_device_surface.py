"""Where the program meets the device: the device-kind -> HAL target
table, the persistent compile-cache placement, and the mesh helper."""

import jax
import pytest
from jax.sharding import AxisType

from repro.core import dispatch, hal
from repro.launch import serve
from repro.launch.mesh import make_mesh


@pytest.mark.parametrize("kind,target", [
    ("TPU v5 lite", hal.TPU_V5E),
    ("TPU v5e", hal.TPU_V5E),
    ("TPU v5", hal.TPU_V5P),
    ("TPU v5p", hal.TPU_V5P),
])
def test_device_kind_maps_to_target(kind, target):
    assert hal.target_for_device_kind(kind) is target


@pytest.mark.parametrize("kind", ["TPU v6 lite", "cpu", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no HAL target"):
        hal.target_for_device_kind(kind)


def test_serve_target_off_the_chip_is_the_emulated_v5e():
    # on a TPU the device kind decides; elsewhere the interpret-mode
    # kernels emulate v5e, so that is the target serving gates against
    assert jax.devices()[0].platform != "tpu"
    assert serve.device_target() is hal.TPU_V5E


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_respects_environment(monkeypatch, tmp_path,
                                            cache_dir_config):
    """JAX_COMPILATION_CACHE_DIR is JAX's own: the helper sets nothing."""
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert dispatch.use_persistent_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_fallback_is_fixed(monkeypatch, cache_dir_config):
    """Without the variable the cache lands in <checkout>/.jax_cache on a
    TPU — a fixed path, the same on every call and in every process — and
    the CPU keeps none."""
    import pathlib

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    assert dispatch.use_persistent_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    checkout = pathlib.Path(__file__).resolve().parents[1]
    want = str(checkout / ".jax_cache")
    assert dispatch.use_persistent_compile_cache() == want
    assert dispatch.use_persistent_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


@pytest.mark.parametrize("explicit_devices", [False, True])
def test_make_mesh_axes_are_auto(explicit_devices):
    """with_sharding_constraint accepts only Auto axes; jax.make_mesh on
    jax 0.9 would give Explicit ones."""
    devices = jax.devices()[:1] if explicit_devices else None
    mesh = make_mesh((1, 1), ("data", "model"), devices=devices)
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    x = jax.numpy.ones((4, 4))
    y = jax.jit(lambda v: jax.lax.with_sharding_constraint(
        v, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
            "data", "model"))))(x)
    assert (y == x).all()
