"""Hardware abstraction layer (HAL): per-target capability and roofline tables.

The paper reads the ANE's per-chip behavior out of a hardware-abstraction-layer
table: feature bytes that gate operations and compressed-weight streaming, shape
limits, core counts, and the roofline constants (ch. 1, 4, 7, 9, 12). This module
is that table, for two families of targets:

  * The ANE generations the paper decodes (H13/M1 ... H17s/M5) — used by the
    paper-faithful reproduction, the numerics oracle, and the compression gates.
  * The TPU targets we actually compile for (v5e, v5p) — used by the three-term
    roofline of the dry-run and the perf loop.

Every number carries its provenance in a comment: `paper:<table>` for values the
paper measures/decodes, `public` for public TPU datasheet values, `assignment`
for the constants fixed by the task statement.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Mapping


class WeightForm(enum.Enum):
    """Compressed-weight forms the datapath reconstructs (paper ch. 7)."""

    FP16 = "fp16"
    INT8 = "int8"                # per-tensor / per-channel affine
    INT4_PALETTE = "int4_palette"  # 16-entry fp16 codebook, 4-bit indices
    SPARSE = "sparse"            # keep-mask + packed fp16 nonzeros
    BLOCKWISE = "blockwise"      # per-block affine scales


# Stored bytes per weight element, including side tables, relative to fp16=2.0.
# paper:T7.4 (sparse 0.43x dense at ~63% zeros; int8 0.5x; int4 = 4 bit + codebook)
BYTES_PER_ELEMENT: Mapping[WeightForm, float] = {
    WeightForm.FP16: 2.0,
    WeightForm.INT8: 1.0,
    WeightForm.INT4_PALETTE: 0.5,     # + 32B codebook per channel group (amortized)
    WeightForm.SPARSE: 0.86,          # 0.43 x dense fp16 bytes (paper:T7.4), vs 2.0
    WeightForm.BLOCKWISE: 1.0625,     # int8 + per-32-block fp16 scale
}


@dataclasses.dataclass(frozen=True)
class ShapeLimits:
    """Per-generation kernel/tensor shape limits (paper:T4.3)."""

    max_kernel_width_default: int
    max_kernel_width_fp16: int
    max_tensor_extent: int        # per-axis cap (2^14 on M1; 2^16 on A16+)
    max_tensor_batch: int
    max_rank: int
    matmul_working_set_bytes: int  # the on-chip working-set threshold


@dataclasses.dataclass(frozen=True)
class Target:
    """One hardware target: roofline constants + capability surface."""

    name: str
    family: str                   # "ane" | "tpu"
    generation: str               # e.g. "H13", "v5e"
    # --- roofline constants ---
    peak_flops: float             # FLOP/s at the native wide-multiply dtype
    hbm_bandwidth: float          # bytes/s, DRAM/HBM roof (B)
    link_bandwidth: float         # bytes/s per ICI link (0 for single-chip ANE)
    num_links: int
    onchip_bytes: int             # ANE working set / TPU VMEM budget per core
    dispatch_floor_s: float       # per-dispatch fixed cost t0
    energy_pj_per_flop: float     # at the compute optimum
    energy_pj_per_flop_sustained: float
    native_dtype: str             # multiply dtype: fp16 (ANE) / bf16 (TPU)
    cores: int                    # architectural core count (HAL 0x238 on ANE)
    # --- capability surface ---
    feature_bytes: Mapping[str, int]      # named HAL gate bytes -> 0/1
    weight_streams: Mapping[WeightForm, bool]  # stream (True) vs fold (False)
    op_floor: Mapping[str, bool]          # op name -> reachable on this target
    limits: ShapeLimits

    # ------------------------------------------------------------------
    @property
    def ridge_flop_per_byte(self) -> float:
        """I* = P / B (paper:§9.1)."""
        return self.peak_flops / self.hbm_bandwidth

    @property
    def collective_bandwidth(self) -> float:
        """Aggregate per-chip ICI bytes/s (all links)."""
        return self.link_bandwidth * max(self.num_links, 1)

    def streams(self, form: WeightForm) -> bool:
        """Does `form` stream compressed bytes (vs fold to dense fp16)?

        paper:T7.1/T7.2 — the stream-vs-fold split is a HAL decision read from
        the per-chip feature bytes, not a property of the reconstruction op.
        """
        return self.weight_streams.get(form, False)

    def supports_dtype(self, dtype_name: str) -> bool:
        """Does this target's datapath carry `dtype_name` activations?

        fp32 is universal (every engine widens); narrow dtypes must match
        the native multiply dtype on single-dtype engines (the ANE's fp16
        datapath has no bf16 path — paper §3.1), while the TPU MXU takes
        both 16-bit forms."""
        if dtype_name == "float32":
            return True
        if self.family == "tpu":
            return dtype_name in ("bfloat16", "float16")
        return dtype_name == self.native_dtype

    def attests(self, op: str) -> bool:
        """Capability *attestation* — a claim about one layer (paper §4.4).

        Deliberately includes ops that are attested but NOT reachable
        (conv3d on every ANE family); `core.capability.confirm_op` is the
        compile-and-run check that tells them apart.
        """
        return op in self.op_floor

    def reaches(self, op: str) -> bool:
        """Ground truth the validator should agree with after confirm_op."""
        return self.op_floor.get(op, False)


# ----------------------------------------------------------------------------
# ANE generations (paper-faithful). All constants paper:T1.3/T3.3/T4.3/T7.1/T9.2.
# ----------------------------------------------------------------------------

_ANE_LIMITS_H13 = ShapeLimits(
    max_kernel_width_default=29, max_kernel_width_fp16=13,
    max_tensor_extent=16384, max_tensor_batch=65536, max_rank=5,
    matmul_working_set_bytes=2 * 1024 * 1024,
)
_ANE_LIMITS_H14 = dataclasses.replace(_ANE_LIMITS_H13, max_kernel_width_default=32,
                                      max_kernel_width_fp16=16)
_ANE_LIMITS_H16 = dataclasses.replace(_ANE_LIMITS_H14, max_tensor_extent=65536)

# Ops used for the attested-vs-reachable census (paper ch.4 + Appendix A shape).
# True = compiles and runs; a key that is PRESENT but False is "attested only".
_ANE_OPS_COMMON = {
    "conv2d": True, "conv2d_transpose": True, "depthwise_conv2d": True,
    "matmul": True, "linear": True, "attention_fused": True,
    "layer_norm": True, "instance_norm": True, "group_norm": True,
    "batch_norm_folded": True, "l2_norm": True,
    "avg_pool": True, "max_pool": True,
    "relu": True, "sigmoid": True, "tanh": True, "gelu": True, "swish": True,
    "softmax": True, "erf": True, "exp": True, "log": True,
    "argmax": True,   # hw argmax port, gated by feature byte 0x4f2_argmax_hw
    "reshape": True, "transpose": True, "concat": True, "split": True,
    "pad": True, "slice": True, "cumsum": True,
    # attested-but-unreachable (paper §4.4: capability byte set, lowering fails)
    "conv3d": False,
    # no hardware path on any family (paper §4.2)
    "reduce_prod": False, "scatter": False, "one_hot": False, "non_zero": False,
    "band_part": False, "reverse_sequence": False, "shape_op": False,
    "logical_and": False, "logical_or": False, "logical_xor": False,
    "gru": False, "lstm": False, "rnn": False,
    "asin": False, "sinh": False, "atanh": False, "mod": False,
}

_H13_OPS = dict(_ANE_OPS_COMMON)
_H13_OPS.update({
    # family-gated: not yet on M1 (paper:T4.1)
    "resize_texture": False, "crop_resize": False, "sin": False, "cos": False,
    "gather": False,  # only a tiny software envelope on M1; treat as unreachable
})
_H14_OPS = dict(_H13_OPS)
_H14_OPS.update({"resize_texture": True, "crop_resize": True})
_H15_OPS = dict(_H14_OPS)
_H15_OPS.update({"sin": True, "cos": True, "gather": True})
_H17_OPS = dict(_H15_OPS)

ANE_M1 = Target(
    name="ane-m1", family="ane", generation="H13",
    peak_flops=12e12,              # paper:T9.2 overhead-isolated slope
    hbm_bandwidth=85e9,            # paper:T9.2
    link_bandwidth=0.0, num_links=0,
    onchip_bytes=2 * 1024 * 1024,  # paper:T9.2 working set
    dispatch_floor_s=0.23e-3,      # paper:T9.2
    energy_pj_per_flop=0.37, energy_pj_per_flop_sustained=0.5,  # paper:T1.3
    native_dtype="float16", cores=4,  # paper:§1.3 HAL 0x238
    feature_bytes={
        "0x48f_kernel_stream_master": 1,  # paper:T7.2
        "0x529_palette_gate": 1,
        "0x528_int8_stream": 0, "0x520_blockwise_stream": 0,
        "0x815_softmax": 1, "0x816_instance_norm": 1, "0x4f2_argmax_hw": 1,
        "0x494_square_after_reduce": 0, "0x81d_texture_engine": 0,
        "0x4a9_dropout_random": 0,
    },
    weight_streams={
        WeightForm.FP16: True, WeightForm.INT4_PALETTE: True,   # paper:T7.1
        WeightForm.SPARSE: True, WeightForm.INT8: False,
        WeightForm.BLOCKWISE: False,
    },
    op_floor=_H13_OPS, limits=_ANE_LIMITS_H13,
)

ANE_M2 = dataclasses.replace(
    ANE_M1, name="ane-m2", generation="H14",
    feature_bytes={**ANE_M1.feature_bytes, "0x528_int8_stream": 1,
                   "0x81d_texture_engine": 1, "0x494_square_after_reduce": 1},
    weight_streams={**ANE_M1.weight_streams, WeightForm.INT8: True},
    op_floor=_H14_OPS, limits=_ANE_LIMITS_H14,
)

ANE_M3 = dataclasses.replace(
    ANE_M2, name="ane-m3", generation="H15",
    feature_bytes={**ANE_M2.feature_bytes, "0x520_blockwise_stream": 1,
                   "0x4a9_dropout_random": 1},
    weight_streams={**ANE_M2.weight_streams, WeightForm.BLOCKWISE: True},
    op_floor=_H15_OPS,
)

ANE_M5 = dataclasses.replace(
    ANE_M3, name="ane-m5", generation="H17s",
    peak_flops=48e12,              # paper:§1.3 — 16 cores vs 4, same form
    hbm_bandwidth=153e9,           # scaled per paper ch.12 family scaling
    onchip_bytes=int(4.72 * 1024 * 1024),  # paper:§9.2 (M5 working set)
    cores=16, op_floor=_H17_OPS, limits=_ANE_LIMITS_H16,
)

# ----------------------------------------------------------------------------
# TPU targets (the machine we compile the framework for).
# ----------------------------------------------------------------------------

_TPU_OPS = {k: True for k, v in _ANE_OPS_COMMON.items()}
_TPU_OPS.update({"sin": True, "cos": True, "gather": True, "scatter": True,
                 "one_hot": True, "conv3d": True, "resize_texture": True,
                 "crop_resize": True, "reduce_prod": True,
                 "logical_and": True, "logical_or": True, "logical_xor": True})
# TPU MXU has no native data-dependent recurrence either: recurrent cells are
# lowered to scans, but as *ops* they are reachable.
_TPU_OPS.update({"gru": True, "lstm": True, "rnn": True})

_TPU_LIMITS = ShapeLimits(
    max_kernel_width_default=2**16, max_kernel_width_fp16=2**16,
    max_tensor_extent=2**31 - 1, max_tensor_batch=2**31 - 1, max_rank=32,
    matmul_working_set_bytes=16 * 1024 * 1024,   # VMEM budget guideline
)

TPU_V5E = Target(
    name="tpu-v5e", family="tpu", generation="v5e",
    peak_flops=197e12,             # assignment: 197 TFLOP/s bf16 per chip
    hbm_bandwidth=819e9,           # assignment: 819 GB/s
    link_bandwidth=50e9,           # assignment: ~50 GB/s/link ICI
    num_links=4,
    onchip_bytes=16 * 1024 * 1024,  # VMEM per core (Pallas budget)
    dispatch_floor_s=30e-6,        # typical per-step launch overhead (modeled)
    energy_pj_per_flop=0.9, energy_pj_per_flop_sustained=1.4,  # modeled
    native_dtype="bfloat16", cores=1,
    feature_bytes={"mxu_int8_double_rate": 1, "mxu_int4_double_rate": 0},
    # On TPU, "streams" == our Pallas kernel dequantizes in-kernel (HBM bytes
    # stay compressed); every form we implement a kernel for streams.
    weight_streams={
        WeightForm.FP16: True, WeightForm.INT4_PALETTE: True,
        WeightForm.SPARSE: True, WeightForm.INT8: True,
        WeightForm.BLOCKWISE: True,
    },
    op_floor=_TPU_OPS, limits=_TPU_LIMITS,
)

TPU_V5P = dataclasses.replace(
    TPU_V5E, name="tpu-v5p", generation="v5p",
    peak_flops=459e12, hbm_bandwidth=2765e9, link_bandwidth=100e9, num_links=6,
)

TARGETS: Mapping[str, Target] = {
    t.name: t for t in (ANE_M1, ANE_M2, ANE_M3, ANE_M5, TPU_V5E, TPU_V5P)
}


def get_target(name: str) -> Target:
    if name not in TARGETS:
        raise KeyError(f"unknown target {name!r}; have {sorted(TARGETS)}")
    return TARGETS[name]


# `jax.Device.device_kind` -> the target that chip is. JAX reports a v5e as
# "TPU v5 lite" and a v5p as "TPU v5"; the marketing names are accepted too.
DEVICE_KIND_TARGETS: Mapping[str, Target] = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
    "TPU v5": TPU_V5P,
    "TPU v5p": TPU_V5P,
}


def target_for_device_kind(kind: str) -> Target:
    """The HAL target of a chip, by the `device_kind` JAX reports. A kind
    the table does not hold is an error, never a default."""
    if kind not in DEVICE_KIND_TARGETS:
        raise KeyError(f"no HAL target for device kind {kind!r}; "
                       f"have {sorted(DEVICE_KIND_TARGETS)}")
    return DEVICE_KIND_TARGETS[kind]


# ----------------------------------------------------------------------------
# ANE numeric constants (paper:T3.3) — shared by numerics oracle and kernels.
# ----------------------------------------------------------------------------

FP16_MAX = 65504.0                    # paper:T3.3
ACCUM_OUT_CEILING = 32768.0           # 2^15 multiply-accumulate output port ceiling
WIDTH_SLICE_GAIN = 16.0               # crop-DMA fixed gain on width-axis offset slice
WIDTH_SLICE_FINITE_FILL = 4094.0      # 4094*16 == 65504 passes
WIDTH_SLICE_OVERFLOW_FILL = 4096.0    # 4096*16 == 65536 -> inf
FIRST_STAGE_TILE = 4                  # first reduction-stage lane tile width
LUT_KNOTS = 33                        # activation table knot count
EXP_OVERFLOW_INPUT = 11.094           # ln(65504)
SIGMOID_DOMAIN = (-9.938, 8.320)      # paper:T3.3 table domain clamp
