"""Compile rehearsals: the main serving path's kernels through Mosaic, the
TPU kernel compiler, for a described (not attached) TPU v5e.

Every test lowers one kernel at phi4-mini-3.8b widths in bf16 (d_model
3072, d_ff 8192, 24/8 heads of 128, vocab 200064) against one chip of a
`v5e:2x2` topology description and asserts the compiled program holds a
`tpu_custom_call`, i.e. the Pallas body went through Mosaic rather than the
interpreter. Nothing runs: these catch what the chip's compiler refuses
(block tiling, VMEM budget, unsupported ops) without chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so describing it while a
module is imported would give parallel test workers different tests to
collect. Interpret mode is switched off by patching
`compat.interpret_mode`, the one attribute every kernel reads at trace time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import compat

D_MODEL, D_FF, VOCAB = 3072, 8192, 200064
HEADS, KV_HEADS, D_HEAD = 24, 8, 128
LANES, SEQ = 4, 2048
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels lower through Mosaic; traces cached in interpret mode are
    dropped on the way in and the Mosaic ones on the way out. The
    persistent compile cache is off meanwhile: an entry compiled for a
    described device cannot be read back on this host."""
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(compat, "interpret_mode", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was_on)


def _compile(fn, *shapes) -> str:
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


@pytest.mark.parametrize("m,k,n,dtype", [
    (LANES, D_MODEL, D_FF, BF16),       # decode step, MLP up
    (512, D_MODEL, D_FF, BF16),         # prefill bucket, MLP up
    (LANES, D_MODEL, VOCAB, BF16),      # logits head
    (LANES, D_MODEL, VOCAB, jnp.float32),  # logits head, fp32 anchor
], ids=["decode", "prefill", "logits", "logits_fp32"])
def test_anemm_compiles(mosaic, one_chip, m, k, n, dtype):
    from repro.kernels.anemm.ops import matmul
    a = jax.ShapeDtypeStruct((m, k), dtype, sharding=one_chip)
    b = jax.ShapeDtypeStruct((k, n), dtype, sharding=one_chip)
    _compile(matmul, a, b)


@pytest.mark.parametrize("layout", ["nk", "kn"], ids=["tied", "untied"])
def test_anemm_head_compiles(mosaic, one_chip, layout):
    """The logits head at 16 lanes: bf16 activations against the bf16 head
    weight as stored — the tied (V, d) table or an untied (d, V) — fp32
    out, within the VMEM limit its blocks ask for; no copy or cast of the
    weight in the program."""
    from repro.kernels.anemm.ops import head
    a = jax.ShapeDtypeStruct((16, D_MODEL), BF16, sharding=one_chip)
    shape = (VOCAB, D_MODEL) if layout == "nk" else (D_MODEL, VOCAB)
    w = jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
    compiled = jax.jit(lambda a, w: head(a, w, layout)).lower(a, w).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "anemm_head" in text
    assert f"f32[16,{VOCAB}]" in text
    assert " copy(" not in text and " convert(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_flash_attention_prefill_compiles(mosaic, one_chip):
    from repro.kernels.flash.flash_attention import flash_attention
    q = jax.ShapeDtypeStruct((1, HEADS, SEQ, D_HEAD), BF16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, KV_HEADS, SEQ, D_HEAD), BF16,
                              sharding=one_chip)
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True), q, kv, kv)


@pytest.mark.parametrize("lanes,heads,kv_heads,d_head", [
    (4, HEADS, KV_HEADS, D_HEAD),
    (8, HEADS, KV_HEADS, D_HEAD),
    (4, 32, 4, 64),                     # d_head 64: kv heads share a lane tile
], ids=["b4", "b8", "d64"])
def test_decode_attention_compiles(mosaic, one_chip, lanes, heads, kv_heads,
                                   d_head):
    from repro.kernels.flash.decode_attention import decode_attention
    q = jax.ShapeDtypeStruct((lanes, heads, d_head), BF16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((lanes, SEQ, kv_heads, d_head), BF16,
                              sharding=one_chip)
    pos = jax.ShapeDtypeStruct((lanes, SEQ), jnp.int32, sharding=one_chip)
    cur = jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one_chip)
    _compile(decode_attention, q, kv, kv, pos, cur)


def test_paged_decode_attention_compiles(mosaic, one_chip):
    from repro.kernels.flash.decode_attention import paged_decode_attention
    n_blocks, block, pages = 1024, 16, SEQ // 16
    q = jax.ShapeDtypeStruct((LANES, HEADS, D_HEAD), BF16, sharding=one_chip)
    arena = jax.ShapeDtypeStruct((n_blocks, block, KV_HEADS, D_HEAD), BF16,
                                 sharding=one_chip)
    pos = jax.ShapeDtypeStruct((n_blocks, block), jnp.int32,
                               sharding=one_chip)
    table = jax.ShapeDtypeStruct((LANES, pages), jnp.int32, sharding=one_chip)
    cur = jax.ShapeDtypeStruct((LANES,), jnp.int32, sharding=one_chip)
    _compile(paged_decode_attention, q, arena, arena, pos, table, cur)


def test_anemm_fused_epilogue_compiles(mosaic, one_chip):
    """The fused LUT epilogue rounds at an fp16 output port; v5e Mosaic
    cannot pack fp32 into fp16, so the rounding is integer emulation."""
    from repro.kernels.anemm.ops import linear
    a = jax.ShapeDtypeStruct((512, 768), BF16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((768, 3072), BF16, sharding=one_chip)
    _compile(lambda a, b: linear(a, b, epilogue="gelu"), a, b)
