"""DispatchedLinear: the model stack's route into `core.dispatch`.

The paper's execution story (ch. 4/5/7) is op-by-device routing for the
*whole graph*: every matmul an application runs is resolved against the
target's capability surface, and compressed weights (palettized or sparse)
are consumed in their packed form — dequantized at the multiplier input —
rather than folded to dense on the host. This module is that route for our
model stack:

  * `DispatchedWeight` — a pytree node carrying a packed weight (palette
    nibbles + codebook, or 1:2 sparse values + selector bits) together with
    its static `WeightForm` tag. The tag rides in the pytree aux data, so it
    survives jit tracing, `lax.scan` stacking/slicing over layers, expert
    indexing, and checkpoint round trips (`checkpoint/` knows the node).
  * `linear(x, w)` — the single matmul entry point the layers call. Every
    projection, MLP matrix, MoE expert bank, and packed logits head
    resolves here: with a dispatcher in scope the call is routed through
    `core.dispatch.KernelDispatcher` (`anemm` for dense, `palette`/`sparse`
    for packed forms) with oracle fallback when the configured HAL target
    gates the kernel; with no dispatcher and a plain dense weight it lowers
    to the exact `dot_general` the seed emitted (bit-stable default path).
  * `head(x, w, layout=)` — the dense logits head on the same `anemm` row:
    the weight read as stored (the tied table as (V, d)), fp32 out.
  * `flash_route` / `decode_route` — the attention matmuls, routed through
    the `flash` and `decode_attention` registry rows the same way.

Scope is managed with `use_dispatcher(d)`; `launch/serve.py`, the examples,
and the parity harness (`tests/test_model_dispatch_parity.py`) activate it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.dispatch import KernelDispatcher
from repro.core.hal import WeightForm

# ---------------------------------------------------------------------------
# Weight-form-tagged packed weights
# ---------------------------------------------------------------------------

# WeightForm -> kernel-registry row that streams it
FORM_KERNELS: dict[WeightForm, str] = {
    WeightForm.INT4_PALETTE: "palette",
    WeightForm.SPARSE: "sparse",
}


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class DispatchedWeight:
    """A packed weight + its static routing tag, as one pytree node.

    `payload` holds the form-specific arrays (children); everything else is
    aux data, so tree ops that stack or slice the payload (scan over layers,
    per-expert indexing, vmap) keep the tag intact.

    The payload is packed over the 2-D matmul view (K = prod of contracted
    dims, N = prod of output dims); `contract_shape`/`out_shape` remember the
    logical dense layout and `dtype_name` the dense dtype, so `dense()` can
    reconstruct exactly what the kernel streams.
    """

    form: WeightForm
    contract_shape: tuple[int, ...]
    out_shape: tuple[int, ...]
    dtype_name: str
    payload: dict[str, Any]

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten_with_keys(self):
        keys = tuple(sorted(self.payload))
        children = [(jax.tree_util.DictKey(k), self.payload[k]) for k in keys]
        aux = (self.form, self.contract_shape, self.out_shape,
               self.dtype_name, keys)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        form, contract_shape, out_shape, dtype_name, keys = aux
        return cls(form, contract_shape, out_shape, dtype_name,
                   dict(zip(keys, children)))

    # -- views --------------------------------------------------------------
    @property
    def kernel(self) -> str:
        return FORM_KERNELS[self.form]

    @property
    def n_stack(self) -> int:
        """Leading stack dims still carried by the payload (layer-scan /
        expert dims); 0 once scan slicing has reached the 2-D matmul view."""
        ref = self.payload["packed" if self.form == WeightForm.INT4_PALETTE
                           else "values"]
        return ref.ndim - 2

    def index(self, i) -> "DispatchedWeight":
        """Slice one leading stack dim (expert banks inside the MoE loop)."""
        return jax.tree.map(lambda a: a[i], self)

    def stack_specs(self, *axes) -> "DispatchedWeight":
        """PartitionSpec pytree (same structure, one spec per payload leaf)
        assigning `axes[i]` to leading stack dim `i`. Only stack dims are
        addressable for sharding: the packed 2-D matmul view interleaves
        logical K/N into nibble planes / codebooks / selector bits, so
        whole-bank (layer/expert) partitioning is the sole meaningful cut.
        Every payload leaf carries the same leading stack dims, so one
        prefix spec serves them all (trailing dims replicate). The result
        is valid as a `shard_map` in_spec or `NamedSharding` spec tree."""
        if len(axes) > self.n_stack:
            raise ValueError(f"{len(axes)} spec axes for {self.n_stack} "
                             "stack dims; packed matmul dims cannot shard")
        spec = jax.sharding.PartitionSpec(*axes)
        return jax.tree.map(lambda _: spec, self)

    def dense(self) -> jnp.ndarray:
        """Decode the 2-D packed payload back to the logical dense weight —
        the FOLD path the oracle and the parity reference multiply against."""
        if self.n_stack:
            raise ValueError("dense() wants the 2-D matmul view; "
                             "slice stack dims first")
        if self.form == WeightForm.INT4_PALETTE:
            from repro.kernels.palette.palette_matmul import unpack_dense
            w2 = unpack_dense(self.payload["packed"],
                              self.payload["lut"].astype(jnp.float32))
        else:
            from repro.kernels.sparse.sparse_matmul import unpack_dense
            w2 = unpack_dense(self.payload["values"],
                              self.payload["selector"])
        return w2.reshape(self.contract_shape + self.out_shape).astype(
            jnp.dtype(self.dtype_name))


def pack_linear_weight(w: np.ndarray, form: WeightForm, *,
                       n_contract: int, n_out: int,
                       palette_iters: int = 4) -> DispatchedWeight:
    """Pack one logical weight (stack dims + contract dims + out dims) into
    `form`. Stack dims (layer-scan, expert) are preserved as leading payload
    dims: `lax.scan`/`index()` slice them back to the 2-D matmul view."""
    from repro.kernels.palette.palette_matmul import pack_kn
    from repro.kernels.sparse.sparse_matmul import pack_pair_sparse

    w = np.asarray(w)
    dtype_name = jnp.dtype(w.dtype).name
    n_stack = w.ndim - n_contract - n_out
    if n_stack < 0:
        raise ValueError(f"weight rank {w.ndim} < contract {n_contract} + "
                         f"out {n_out}")
    contract_shape = w.shape[n_stack:n_stack + n_contract]
    out_shape = w.shape[n_stack + n_contract:]
    k = int(np.prod(contract_shape))
    n = int(np.prod(out_shape))
    lead = w.shape[:n_stack]
    w2 = np.asarray(w, np.float32).reshape(lead + (k, n))

    def pack2d(mat: np.ndarray) -> dict[str, np.ndarray]:
        if form == WeightForm.INT4_PALETTE:
            packed, lut = pack_kn(mat, iters=palette_iters)
            return {"packed": packed, "lut": lut}
        vals, sel = pack_pair_sparse(mat)
        return {"values": vals, "selector": sel}

    if not lead:
        payload = {k_: jnp.asarray(v) for k_, v in pack2d(w2).items()}
    else:
        slices = [pack2d(w2[idx]) for idx in np.ndindex(*lead)]
        payload = {
            k_: jnp.asarray(
                np.stack([s[k_] for s in slices]).reshape(
                    lead + slices[0][k_].shape))
            for k_ in slices[0]
        }
    return DispatchedWeight(form, contract_shape, out_shape, dtype_name,
                            payload)


def packable(form: WeightForm, k: int) -> bool:
    """Can a matmul view with contraction extent `k` pack into `form`?"""
    if form == WeightForm.INT4_PALETTE:
        return k % 2 == 0
    if form == WeightForm.SPARSE:
        return k % 16 == 0
    return False


# ---------------------------------------------------------------------------
# Dispatcher scope
# ---------------------------------------------------------------------------

_SCOPE: list[KernelDispatcher] = []
_DEFAULT: KernelDispatcher | None = None


@contextlib.contextmanager
def use_dispatcher(dispatcher: KernelDispatcher | None) -> Iterator[None]:
    """Route every `linear`/attention matmul traced inside through
    `dispatcher`. `None` is a no-op scope (keeps call sites unconditional)."""
    if dispatcher is None:
        yield
        return
    _SCOPE.append(dispatcher)
    try:
        yield
    finally:
        _SCOPE.pop()


def active_dispatcher() -> KernelDispatcher | None:
    return _SCOPE[-1] if _SCOPE else None


_MESH: list[Any] = []


@contextlib.contextmanager
def use_mesh(mesh) -> Iterator[None]:
    """Run every Pallas kernel traced inside on each device of `mesh`, in a
    `shard_map` with replicated operands. Mosaic kernels cannot be
    partitioned automatically, so a multi-device program must hand each one
    a manual region; replication computes exactly what one device would,
    which keeps mesh streams bit-identical to single-device. `None` or a
    one-device mesh is a no-op scope; a body that is already manual (the
    MoE EP `shard_map`) opens `use_mesh(None)`."""
    _MESH.append(mesh if mesh is not None and mesh.devices.size > 1 else None)
    try:
        yield
    finally:
        _MESH.pop()


def _on_every_device(fn: Callable, args: tuple):
    mesh = _MESH[-1] if _MESH else None
    if mesh is None:
        return fn(*args)
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


_FUSION: list[bool] = []


@contextlib.contextmanager
def fuse_epilogues(on: bool) -> Iterator[None]:
    """Scope the conv/matmul LUT-epilogue fusion choice. Fused (the default)
    runs the activation at the producing kernel's output port — one engine
    dispatch; unfused routes a separate `act_lut` op afterwards — the
    two-dispatch pipeline `bench_encoder` measures against."""
    _FUSION.append(on)
    try:
        yield
    finally:
        _FUSION.pop()


def epilogue_fusion_active() -> bool:
    return _FUSION[-1] if _FUSION else True


def _dispatcher_for(w: Any) -> KernelDispatcher | None:
    """The dispatcher a call must use: the scoped one, or — for a packed
    weight that *cannot* run undispatched — a default TPU-target one."""
    d = active_dispatcher()
    if d is None and isinstance(w, DispatchedWeight):
        global _DEFAULT
        if _DEFAULT is None:
            _DEFAULT = KernelDispatcher()
        return _DEFAULT
    return d


# ---------------------------------------------------------------------------
# Routed execution
# ---------------------------------------------------------------------------


def route_and_run(disp: KernelDispatcher, name: str, dtype,
                  native: Callable[..., Any], oracle: Callable[..., Any],
                  *args):
    """One op-by-device cell: resolve through the dispatcher's capability
    gates, record the route, run the winning backend on `args`. Unlike
    `KernelDispatcher.__call__` the two legs are callables, so call sites
    can pass extra kwargs (window, causal) or differentiable wrappers. The
    Pallas leg runs on every device of the mesh in scope (`use_mesh`)."""
    route = disp.resolve(name, dtype)
    disp.routes.append(route)
    if route.native:
        return _on_every_device(native, args)
    return oracle(*args)


def _matmul_dense(disp: KernelDispatcher, a2: jnp.ndarray,
                  w2: jnp.ndarray) -> jnp.ndarray:
    from repro.kernels.anemm import ops as anemm_ops
    from repro.kernels.anemm.ref import anemm_ref

    return route_and_run(
        disp, "anemm", a2.dtype,
        lambda a, w: anemm_ops.matmul(a, w.astype(a.dtype)),
        lambda a, w: anemm_ref(a, w.astype(a.dtype)), a2, w2)


def head(x: jnp.ndarray, w: jnp.ndarray, *, layout: str) -> jnp.ndarray:
    """The routed logits head, fp32 out: `x` (..., K) against the dense
    weight as stored — (K, N) for layout "kn", the tied embedding table
    (N, K) for "nk" — with fp32 accumulation over operands in `x`'s dtype.
    The route resolves the `anemm` row under fp32, the head's output
    dtype, so the op-by-device matrix keys the head as before."""
    from repro.kernels.anemm import ops as anemm_ops
    from repro.kernels.anemm.ref import anemm_head_ref

    k = x.shape[-1]
    n = w.shape[0] if layout == "nk" else w.shape[1]
    out2 = route_and_run(
        active_dispatcher(), "anemm", jnp.float32,
        lambda a, w: anemm_ops.head(a, w, layout),
        lambda a, w: anemm_head_ref(a, w, layout),
        x.reshape(-1, k), w.astype(x.dtype))
    return out2.reshape(x.shape[:-1] + (n,))


def _matmul_packed(disp: KernelDispatcher, a2: jnp.ndarray,
                   w: DispatchedWeight) -> jnp.ndarray:
    from repro.kernels import registry

    if w.form == WeightForm.INT4_PALETTE:
        bundle = {"a": a2, "packed": w.payload["packed"],
                  "lut": w.payload["lut"]}
    elif w.form == WeightForm.SPARSE:
        bundle = {"a": a2, "values": w.payload["values"],
                  "selector": w.payload["selector"]}
    else:
        raise ValueError(f"no streaming kernel for {w.form}")
    spec = registry.get(w.kernel)
    return route_and_run(disp, w.kernel, a2.dtype, spec.run_kernel,
                         spec.run_oracle, bundle)


def linear(x: jnp.ndarray, w: Any, *, n_contract: int = 1,
           bias: jnp.ndarray | None = None) -> jnp.ndarray:
    """The matmul every layer calls: contract the trailing `n_contract` dims
    of `x` with the leading dims of `w`.

    * packed weight -> `palette`/`sparse` kernel through the dispatcher
      (oracle fallback when the HAL gates the form/op/dtype);
    * dense weight + dispatcher in scope -> the `anemm` row, same gates;
    * dense weight, no dispatcher -> the seed's exact wide-accumulator
      `dot_general` (train-time default; numerically unchanged).
    """
    disp = _dispatcher_for(w)
    if isinstance(w, DispatchedWeight):
        if w.n_stack:
            raise ValueError("packed weight still carries stack dims "
                             f"{w.n_stack}; slice before linear()")
        k = int(np.prod(x.shape[x.ndim - n_contract:]))
        out2 = _matmul_packed(disp, x.reshape(-1, k), w)
        out = out2.reshape(x.shape[:x.ndim - n_contract] + w.out_shape)
    elif disp is not None:
        k = int(np.prod(x.shape[x.ndim - n_contract:]))
        out2 = _matmul_dense(disp, x.reshape(-1, k), w.reshape(k, -1))
        out = out2.reshape(x.shape[:x.ndim - n_contract] + w.shape[n_contract:])
    else:
        dims = ((tuple(range(x.ndim - n_contract, x.ndim)),
                 tuple(range(n_contract))), ((), ()))
        out = jax.lax.dot_general(
            x, w, dims, preferred_element_type=jnp.float32).astype(x.dtype)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# Attention routes (flash prefill/train, one-token decode)
# ---------------------------------------------------------------------------


def flash_route(disp: KernelDispatcher, q: jnp.ndarray, k: jnp.ndarray,
                v: jnp.ndarray, *, causal: bool = True) -> jnp.ndarray:
    """Fused-attention cell for (B, S, H, dh)-layout q/k/v. Native = the
    Pallas flash kernel (recompute backward, so the train path
    differentiates); gated = the chunked online-softmax reference."""
    def native(q, k, v):
        from repro.kernels.flash import ops as flash_ops
        out = flash_ops.attention(q.transpose(0, 2, 1, 3),
                                  k.transpose(0, 2, 1, 3),
                                  v.transpose(0, 2, 1, 3), causal, None)
        return out.transpose(0, 2, 1, 3)

    def oracle(q, k, v):
        from repro.models.attention import chunked_attention
        return chunked_attention(q, k, v, causal=causal)

    return route_and_run(disp, "flash", q.dtype, native, oracle, q, k, v)


def decode_route(disp: KernelDispatcher, q: jnp.ndarray,
                 k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                 positions: jnp.ndarray, current: jnp.ndarray, *,
                 window: int | None = None) -> jnp.ndarray:
    """One-token decode cell: q (B, H, dh) against a (B, S, KV, dh) cache.
    Gated on `gather` (H13/M1 has none), so the op-by-device matrix sends
    this to the oracle on early ANE targets — the paper's cell, live."""
    from repro.kernels.flash.decode_attention import (decode_attention,
                                                      decode_attention_ref)

    return route_and_run(
        disp, "decode_attention", q.dtype,
        functools.partial(decode_attention, window=window),
        functools.partial(decode_attention_ref, window=window),
        q, k_cache, v_cache, positions, current)


# ---------------------------------------------------------------------------
# Conv-family routes (encoder stems, vision front ends)
# ---------------------------------------------------------------------------


def conv2d(x: jnp.ndarray, w: jnp.ndarray,
           bias: jnp.ndarray | None = None, *,
           stride: tuple[int, int] = (1, 1), padding: str = "SAME",
           act: str | None = None) -> jnp.ndarray:
    """The conv every encoder stem calls (NHWC x, HWIO w).

    * dispatcher in scope + `act` + fusion on (default): ONE routed `conv2d`
      dispatch with the LUT activation fused at the output port;
    * dispatcher + `act` + fusion off: a routed `conv2d` followed by a
      routed `act_lut` — the separate-op pipeline, bit-identical output;
    * no dispatcher: the differentiable jnp reference with the same LUT
      numerics, so dispatched-vs-reference parity differs only by the conv
      kernel's accumulation order.
    """
    from repro.kernels.conv.ref import conv2d_ref

    disp = active_dispatcher()
    if disp is None:
        return conv2d_ref(x, w, bias, stride=stride, padding=padding,
                          epilogue=act)

    from repro.kernels.conv import ops as conv_ops

    fused = act if epilogue_fusion_active() else None
    out = route_and_run(
        disp, "conv2d", x.dtype,
        lambda x, w, b: conv_ops.conv2d(x, w, b, stride=stride,
                                        padding=padding, epilogue=fused),
        lambda x, w, b: conv2d_ref(x, w, b, stride=stride, padding=padding,
                                   epilogue=fused), x, w, bias)
    if act is None or fused is not None:
        return out

    from repro.kernels.act_lut.ops import lut_activation, lut_apply_ref

    return route_and_run(
        disp, "act_lut", out.dtype, lut_activation(act),
        lambda y: lut_apply_ref(y, act), out)


def _pool(x: jnp.ndarray, *, window, stride, padding, kind: str):
    from repro.kernels.conv import ops as conv_ops
    from repro.kernels.conv import ref as conv_ref

    native = conv_ops.avg_pool if kind == "avg_pool" else conv_ops.max_pool
    oracle = (conv_ref.avg_pool_ref if kind == "avg_pool"
              else conv_ref.max_pool_ref)
    disp = active_dispatcher()
    if disp is None:
        return oracle(x, window=window, stride=stride, padding=padding)
    return route_and_run(
        disp, kind, x.dtype,
        functools.partial(native, window=window, stride=stride,
                          padding=padding),
        functools.partial(oracle, window=window, stride=stride,
                          padding=padding), x)


def avg_pool(x: jnp.ndarray, *, window: tuple[int, int],
             stride: tuple[int, int] | None = None,
             padding: str = "VALID") -> jnp.ndarray:
    """Routed NHWC average pooling (count-include-pad)."""
    return _pool(x, window=window, stride=stride or window, padding=padding,
                 kind="avg_pool")


def max_pool(x: jnp.ndarray, *, window: tuple[int, int],
             stride: tuple[int, int] | None = None,
             padding: str = "VALID") -> jnp.ndarray:
    """Routed NHWC max pooling."""
    return _pool(x, window=window, stride=stride or window, padding=padding,
                 kind="max_pool")
