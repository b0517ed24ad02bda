"""Compile-once / dispatch-many: program cache, execution streams, residency.

The paper's execution model (ch. 2, 5, 6): work reaches the engine in two
phases whose costs are far apart. The compile phase lowers and lays out once,
keyed by a content hash (`model.anehash` is a double SHA-256 over the
program; two structurally identical compiles hit the cache). The dispatch
phase binds operands and posts one command; a buffer can stay resident across
dispatches so KV caches and optimizer state never round-trip the host.

The XLA mapping:
  * program cache          -> our ProgramCache keyed by double-SHA256 of the
                              (jaxpr text, shapes, shardings, options)
  * load_for_execution     -> lowered.compile()
  * resident buffers       -> donated arguments (the output aliases the input
                              buffer, XLA's form of output->input port binding)
  * execution stream       -> ExecutionStream with dispatch-floor accounting
  * overlapping streams    -> AsyncExecutionStream: encode -> submit -> sync
                              with a bounded in-flight window (the firmware
                              drains command buffers while the host encodes)
  * op-by-device routing   -> KernelDispatcher over the kernel registry:
                              capability-gated Pallas kernel, oracle fallback
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import pathlib
import queue as queue_mod
import re
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Hashable

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import hal


def content_hash(fn: Callable, args_spec: Any, options: str = "") -> str:
    """Double SHA-256 over the traced program + shapes + options — the
    paper's cacheURLIdentifier/anehash scheme (§5.6): identical structure and
    options resolve to the same key; changing any shape, op, device mask, or
    option changes it. A program that cannot be traced raises: keying it by
    name instead would let two different programs share one entry."""
    jaxpr = jax.make_jaxpr(fn)(*args_spec)
    # custom_vjp params print closure objects with their memory address;
    # scrub addresses so structurally identical traces hash identically
    body = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    spec_txt = str(jax.tree.map(
        lambda x: (getattr(x, "shape", None), str(getattr(x, "dtype", None))),
        args_spec))
    inner = hashlib.sha256((body + spec_txt + options).encode()).digest()
    return hashlib.sha256(inner).hexdigest()


#: where compiled programs persist when nothing else says: a fixed path in
#: the checkout (listed in .gitignore), since the path is part of the cache
#: key and a directory that moves never hits
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache on a TPU, before the first
    compile, so a later process (or the second half of this one) loads what
    an earlier one compiled. `JAX_COMPILATION_CACHE_DIR`, when set, is JAX's
    own and wins: no directory is set in code then. Other backends keep no
    cache: they run the tests, whose interpret-mode programs are cheap to
    compile, and XLA:CPU warns on every entry it loads. Returns the
    directory in use, or None."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    compile_seconds: float = 0.0


class ProgramCache:
    """Content-addressed compiled-program cache (one per process, like the
    daemon's e5bundlecache; ours is in-memory, keyed the same way, and sits
    over JAX's on-disk cache — `use_persistent_compile_cache`)."""

    def __init__(self) -> None:
        self._programs: dict[str, Any] = {}
        # (fn, treedef, avals) -> content hash: a warm-start lookup must not
        # pay the full retrace content_hash performs (the jaxpr of a repeat
        # call is determined by the function + arg structure/avals)
        self._hash_memo: dict[Any, tuple[str, Callable]] = {}
        self.stats = CacheStats()

    def _key(self, fn: Callable, args_spec, options: str) -> str:
        leaves, treedef = jax.tree.flatten(args_spec)
        specs = tuple((getattr(x, "shape", None), str(getattr(x, "dtype", None)))
                      for x in leaves)
        # bound methods are re-created per attribute access: key on the
        # underlying function + receiver id (the receiver is pinned in the
        # memo value, so the id cannot be recycled while the entry lives)
        fast = (getattr(fn, "__func__", fn), id(getattr(fn, "__self__", None)),
                treedef, specs, options)
        try:
            hit = self._hash_memo.get(fast)
        except TypeError:               # unhashable leaf/aux somewhere
            return content_hash(fn, args_spec, options)
        if hit is not None:
            return hit[0]
        key = content_hash(fn, args_spec, options)
        self._hash_memo[fast] = (key, fn)
        return key

    def compile(self, fn: Callable, *args_spec, options: str = "",
                force_recompilation: bool = False, jit_kwargs: dict | None = None):
        """compile-or-hit. `force_recompilation` defeats the warm start and
        rewrites the entry unconditionally (the paper's documented inverse of
        force_fetch_from_cache)."""
        key = self._key(fn, args_spec, options)
        if not force_recompilation and key in self._programs:
            self.stats.hits += 1
            return self._programs[key], key
        t0 = time.perf_counter()
        jitted = jax.jit(fn, **(jit_kwargs or {}))
        compiled = jitted.lower(*args_spec).compile()
        self.stats.compile_seconds += time.perf_counter() - t0
        self.stats.misses += 1
        self._programs[key] = compiled
        return compiled, key

    def is_new_compile_required(self, fn: Callable, *args_spec,
                                options: str = "") -> bool:
        return self._key(fn, args_spec, options) not in self._programs

    def purge(self) -> None:
        self._programs.clear()
        self._hash_memo.clear()


@dataclasses.dataclass
class DispatchRecord:
    key: str
    wall_s: float
    work_s: float          # wall minus the costmodel floor estimate, >= 0
    floor_s: float = 0.0   # the per-dispatch floor charged against this call
    queue_depth: int = 0   # ops already encoded ahead of this one at encode time
    batch: int = 1         # samples this dispatch carried (amortization denom)
    seq: int = 0           # submission index on this stream (total order)
    submit_ts: float = 0.0     # perf_counter at submission (host hand-off)
    complete_ts: float = 0.0   # perf_counter when the drain saw it complete
    inflight_depth: int = 0    # ops submitted and not yet complete at submit
                               # time: 0 on a sync stream, < window on async
    span: tuple[int, int] | None = None
                               # token span [lo, hi) this dispatch covered —
                               # set by chunked prefill so the bench can
                               # audit that chunks tile the prompt and each
                               # one was floor-charged; None elsewhere


class ExecutionStream:
    """One dispatch queue with per-call floor accounting (paper §2.3/§9.3).

    The engine keeps one command in flight (submissions serialize, §2.4);
    a jit stream behaves the same way per device. `execute_sync` measures the
    per-call wall time so the dispatch-floor benchmark can isolate t0 exactly
    the way the paper's slope method does. Each record carries the costmodel
    floor estimate of its target (`Target.dispatch_floor_s`), so
    `work_s = max(0, wall - floor)` splits every dispatch into fixed overhead
    and useful work — the split the batching scheduler amortizes (§9.4).

    Inside a profiler session each dispatch writes two host spans on the
    profiler's clock: ``stream.launch`` (argument handling and the launch)
    and ``stream.wait`` (blocking until the outputs are ready). Outside one
    they record nothing.
    """

    def __init__(self, cache: ProgramCache | None = None, *,
                 target: hal.Target | None = None,
                 floor_s: float | None = None) -> None:
        self.cache = cache or ProgramCache()
        self.target = target or hal.TPU_V5E
        self.floor_s = self.target.dispatch_floor_s if floor_s is None \
            else floor_s
        self.records: list[DispatchRecord] = []
        self._encoded: list[tuple[Any, tuple, dict, str, int, int, Any]] = []
        self._seq = 0

    @property
    def queue_depth(self) -> int:
        """Ops encoded but not yet executed."""
        return len(self._encoded)

    def encode_operation(self, compiled, args: tuple, key: str = "",
                         kwargs: dict | None = None, *,
                         batch: int = 1,
                         span: tuple[int, int] | None = None) -> None:
        """Queue one compiled program. `batch` is the number of samples the
        dispatch carries — the denominator of per-sample floor amortization.
        `span` tags the token range a chunked-prefill dispatch covers."""
        self._encoded.append((compiled, args, kwargs or {}, key, batch,
                              len(self._encoded), span))

    def execute_sync(self) -> list:
        """Run everything encoded, in order, blocking (the sound default the
        paper recommends; overlapping streams is the unfinished path).
        Always returns a list of outputs, one per encoded op, in encode
        order — including for a single op."""
        outs = []
        for compiled, args, kwargs, key, batch, depth, span in self._encoded:
            t0 = time.perf_counter()
            with TraceAnnotation("stream.launch"):
                out = compiled(*args, **kwargs)
            with TraceAnnotation("stream.wait"):
                out = jax.block_until_ready(out)
            t1 = time.perf_counter()
            wall = t1 - t0
            self.records.append(DispatchRecord(
                key, wall, max(0.0, wall - self.floor_s), self.floor_s,
                depth, batch, self._seq, submit_ts=t0, complete_ts=t1,
                span=span))
            self._seq += 1
            outs.append(out)
        self._encoded.clear()
        return outs

    # -- floor accounting over the record log -------------------------------
    def total_floor_s(self) -> float:
        """Fixed dispatch cost accumulated so far (#dispatches x floor)."""
        return sum(r.floor_s for r in self.records)

    def total_work_s(self) -> float:
        return sum(r.work_s for r in self.records)

    def reset(self) -> None:
        self._encoded.clear()


@dataclasses.dataclass
class _Inflight:
    """One submitted-but-unconfirmed dispatch: the record being timed, the
    (possibly still executing) outputs, and the completion latch."""

    record: DispatchRecord
    out: Any
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    error: BaseException | None = None


def _drain_loop(stream_ref, drain_q) -> None:
    """Background drain: confirm in-flight dispatches in submission order via
    `jax.block_until_ready`, stamp completion, and retire them to the record
    log. Runs as a daemon thread holding only a weakref to the stream so a
    dropped stream (plus its finalizer sentinel) shuts the thread down.

    A leaf that was donated forward into the *next* submission raises
    "deleted or donated buffer" on sync — completion of the consumer implies
    completion of the producer, so those leaves are skipped and the
    non-donated leaves (tokens, logits, scalars) carry the timestamp."""
    while True:
        h = drain_q.get()
        if h is None:
            return
        try:
            for leaf in jax.tree.leaves(h.out):
                try:
                    if hasattr(leaf, "block_until_ready"):
                        leaf.block_until_ready()
                except Exception as e:
                    msg = str(e).lower()
                    if "donated" not in msg and "deleted" not in msg:
                        raise
        except BaseException as e:  # surface on the next sync()
            h.error = e
        t = time.perf_counter()
        stream = stream_ref()
        if stream is None:
            h.done.set()
            return
        r = h.record
        r.complete_ts = t
        r.wall_s = t - r.submit_ts
        r.work_s = max(0.0, r.wall_s - r.floor_s)
        with stream._lock:
            stream.records.append(r)
            if h.error is not None:
                stream._errors.append(h.error)
            try:                      # FIFO: h is the leftmost entry
                stream._pending.remove(h)
            except ValueError:        # pragma: no cover - defensive
                pass
        h.done.set()
        del stream, h, r   # no strong refs held while parked on the queue


class AsyncExecutionStream(ExecutionStream):
    """Overlapped dispatch: encode -> submit -> sync with a bounded in-flight
    window (paper §2.4's open overlapping-streams path).

    The sound default (`ExecutionStream.execute_sync`) serializes: every
    dispatch pays its floor with the host idle in between. This stream keeps
    the host encoding while the device drains, the way the firmware drains
    command buffers while the host keeps encoding:

      * **double-buffered submission queues** — `encode_operation` fills the
        encode queue; `submit` hands each op to the device without blocking
        and moves it to the in-flight queue. With the default window of 2
        the device executes one submission while the host encodes the next.
      * **bounded in-flight window** — `submit` throttles when
        `max_in_flight` submissions are unconfirmed, so run-ahead (and
        resident-buffer lifetime) stays bounded.
      * **background drain** — a daemon thread confirms completions in
        submission order via `jax.block_until_ready`, stamping
        `complete_ts` and retiring the `DispatchRecord`. Floor accounting
        stays truthful: every dispatch still charges the costmodel floor
        once, and `wall_s = complete_ts - submit_ts` now *includes* the
        overlap (two overlapped dispatches show overlapping [submit,
        complete] intervals instead of summed walls).

    Outputs returned by `submit` are live JAX arrays (async futures): they
    can be fed straight into the next encoded op to chain device work
    without a host round-trip. `sync` is the barrier; `execute_sync`
    degenerates to submit-then-sync so the base contract holds. The
    profiler spans are the base stream's: ``stream.launch`` around each
    submitted call, ``stream.wait`` around the barrier on the calling thread
    (the drain thread writes none).
    """

    def __init__(self, cache: ProgramCache | None = None, *,
                 target: hal.Target | None = None,
                 floor_s: float | None = None,
                 max_in_flight: int = 2) -> None:
        super().__init__(cache, target=target, floor_s=floor_s)
        if max_in_flight < 1:
            raise ValueError(f"max_in_flight must be >= 1, got {max_in_flight}")
        self.max_in_flight = max_in_flight
        self._pending: deque[_Inflight] = deque()
        self._errors: list[BaseException] = []
        self._lock = threading.Lock()
        self._drain_q: queue_mod.SimpleQueue = queue_mod.SimpleQueue()
        self._drainer: threading.Thread | None = None

    # -- window state -------------------------------------------------------
    @property
    def in_flight_depth(self) -> int:
        """Submissions handed to the device and not yet confirmed complete."""
        with self._lock:
            return len(self._pending)

    def _ensure_drainer(self) -> None:
        if self._drainer is None or not self._drainer.is_alive():
            self._drainer = threading.Thread(
                target=_drain_loop, args=(weakref.ref(self), self._drain_q),
                name="stream-drain", daemon=True)
            # a dropped stream must not strand the drain thread
            weakref.finalize(self, self._drain_q.put, None)
            self._drainer.start()

    def _throttle(self) -> None:
        """Block until the in-flight window has a free slot."""
        while True:
            with self._lock:
                if len(self._pending) < self.max_in_flight:
                    return
                oldest = self._pending[0]
            oldest.done.wait()

    # -- encode -> submit -> sync -------------------------------------------
    def submit(self) -> list:
        """Hand every encoded op to the device without waiting for results.
        Returns the per-op outputs in encode order — live async values,
        usable immediately as inputs of the next encoded op."""
        self._ensure_drainer()
        outs = []
        for compiled, args, kwargs, key, batch, depth, span in self._encoded:
            self._throttle()
            with self._lock:
                depth_now = len(self._pending)
            t_sub = time.perf_counter()
            with TraceAnnotation("stream.launch"):
                out = compiled(*args, **kwargs)     # async: returns now
            rec = DispatchRecord(
                key, 0.0, 0.0, self.floor_s, depth, batch, self._seq,
                submit_ts=t_sub, inflight_depth=depth_now, span=span)
            self._seq += 1
            h = _Inflight(rec, out)
            with self._lock:
                self._pending.append(h)
            self._drain_q.put(h)
            outs.append(out)
        self._encoded.clear()
        return outs

    def sync(self) -> list:
        """Barrier: wait until every in-flight submission is confirmed.
        Returns the outputs of the ops that were still in flight, in
        submission order; re-raises any execution error the drain saw."""
        with self._lock:
            handles = list(self._pending)
        with TraceAnnotation("stream.wait"):
            for h in handles:
                h.done.wait()
        with self._lock:
            errors, self._errors = list(self._errors), []
        if errors:
            raise errors[0]
        return [h.out for h in handles]

    def execute_sync(self) -> list:
        """The base contract: run everything encoded, in order, blocking.
        Drains the in-flight window first so the record order stays total,
        then runs inline — a barrier gains nothing from the drain thread,
        and skipping it keeps per-dispatch admissions off the wakeup path."""
        self.sync()
        return super().execute_sync()

    def close(self) -> None:
        """Drain outstanding work and stop the background thread."""
        self.sync()
        if self._drainer is not None and self._drainer.is_alive():
            self._drain_q.put(None)
            self._drainer.join(timeout=5.0)
            self._drainer = None


def resident(fn: Callable, state_argnums: int | tuple[int, ...]):
    """Mark state arguments resident: the output buffer aliases the input
    buffer across dispatches (paper §2.6 output->input port binding). In XLA
    this is argument donation; the held tensor never re-crosses the host."""
    if isinstance(state_argnums, int):
        state_argnums = (state_argnums,)
    return jax.jit(fn, donate_argnums=state_argnums)


# ---------------------------------------------------------------------------
# Registry-routed kernel dispatch (the paper's operation-by-device matrix)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelRoute:
    """One resolved cell of the operation-by-device matrix."""

    kernel: str
    target: str
    dtype: str
    backend: str           # "pallas" | "oracle"
    reason: str            # why the fallback fired ("" for the native path)

    @property
    def native(self) -> bool:
        return self.backend == "pallas"


class KernelDispatcher:
    """Route kernel calls through the registry with capability-gated fallback.

    The paper's rule (§4): an operation runs on the engine only when the
    layer that executes it accepts it — everything else falls back, silently,
    to the next backend. Here: a registered Pallas kernel runs natively when
    the target's op floor reaches its capability op, the weight form it
    streams actually streams on that target, and the activation dtype is one
    the kernel (and the target's datapath) carries. Any miss routes to the
    kernel's ref oracle — same arithmetic, dense bytes — and the route taken
    is recorded so `matrix()` can print the census.
    """

    # retained route records per dispatcher — enough for any census/debug
    # readout while keeping a serving-loop dispatcher O(1) in memory
    ROUTE_LOG_LIMIT = 4096

    def __init__(self, target: hal.Target | None = None) -> None:
        self.target = target or hal.TPU_V5E
        self.routes: deque[KernelRoute] = deque(maxlen=self.ROUTE_LOG_LIMIT)

    # -- routing decision ---------------------------------------------------
    def resolve(self, name: str, dtype: Any = jnp.float32) -> KernelRoute:
        from repro.kernels import registry   # lazy: keep core importable alone

        spec = registry.get(name)
        t = self.target
        dt = jnp.dtype(dtype).name
        reason = ""
        if dt not in {jnp.dtype(d).name for d in spec.dtypes}:
            reason = f"dtype {dt} outside kernel surface"
        elif not t.attests(spec.capability_op):
            reason = f"{spec.capability_op}: not in the {t.generation} op table"
        elif not t.reaches(spec.capability_op):
            reason = f"{spec.capability_op}: attested but fails lowering"
        elif spec.weight_form is not None and not t.streams(spec.weight_form):
            reason = f"{spec.weight_form.value}: folds on {t.generation}"
        elif not t.supports_dtype(dt):
            reason = (f"{dt} is not native on {t.generation} "
                      f"({t.native_dtype} datapath)")
        backend = "oracle" if reason else "pallas"
        return KernelRoute(name, t.name, dt, backend, reason)

    # -- execution ----------------------------------------------------------
    def __call__(self, name: str, inputs: dict) -> Any:
        """Run kernel `name` on `inputs` (the registry's input bundle),
        through the Pallas path when the target reaches it, else the oracle."""
        from repro.kernels import registry

        spec = registry.get(name)
        route = self.resolve(name, _bundle_dtype(inputs))
        self.routes.append(route)
        if route.native:
            return spec.run_kernel(inputs)
        return spec.run_oracle(inputs)

    # -- the census ---------------------------------------------------------
    def matrix(self, dtype: Any = jnp.float32) -> list[KernelRoute]:
        """One row per registered kernel: the op-by-device matrix column for
        this target (paper Appendix A shape, kernel-registry rows)."""
        from repro.kernels import registry

        return [self.resolve(n, dtype) for n in registry.names()]


def _bundle_dtype(inputs: dict) -> Any:
    """The activation dtype of a registry input bundle: the first floating
    jnp array wins (weights/selectors are integer side tables)."""
    for v in inputs.values():
        dt = getattr(v, "dtype", None)
        if dt is not None and jnp.issubdtype(dt, jnp.floating):
            return dt
    return jnp.float32


def kernel_matrix(targets: list[hal.Target] | None = None,
                  dtype: Any = jnp.float32) -> list[KernelRoute]:
    """The full operation-by-device matrix across targets — every registered
    kernel x every HAL target, each cell a capability-resolved route."""
    targets = targets or list(hal.TARGETS.values())
    rows: list[KernelRoute] = []
    for t in targets:
        rows.extend(KernelDispatcher(t).matrix(dtype))
    return rows


def measure_dispatch_floor(n: int = 200) -> dict[str, float]:
    """Isolate t0 on this host the way the paper does (§2.3): a tiny program
    in a hot loop; the floor is the wall time with negligible work. Returns
    the stage split we can observe from user space."""
    import jax.numpy as jnp

    x = jnp.ones((8, 8), jnp.float32)
    f = jax.jit(lambda a: (a * 1.0).sum())
    f(x).block_until_ready()                      # warm
    t0 = time.perf_counter()
    for _ in range(n):
        f(x).block_until_ready()
    per_call = (time.perf_counter() - t0) / n
    # trace-dispatch split: calling with donated/aot compiled skips tracing
    g = jax.jit(lambda a: (a * 1.0).sum()).lower(x).compile()
    g(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        g(x).block_until_ready()
    aot_call = (time.perf_counter() - t0) / n
    return {"per_call_s": per_call, "aot_call_s": aot_call,
            "python_overhead_s": per_call - aot_call}
