"""Continuous-batching request scheduler on `ExecutionStream` (paper §9.4).

The paper's dispatch-floor measurements put a fixed ~t0 on every command the
engine executes; batching to 512 samples drops the per-sample share ~127x
(§9.4). Serving lives or dies on amortizing exactly that floor across queued
requests, so this module schedules a *request queue* onto the decode program
rather than serving fixed-shape rounds:

  * **request queue** — FIFO of `Request`s (own prompt, own generation
    budget, own arrival step), admitted in arrival order.
  * **prompt-length bucketing** — heterogeneous prompts compile against a
    bounded set of prefill shapes: a prompt prefills at the largest bucket
    <= its length and catches the remainder up through the (single-shape)
    decode program, so the content-hash `ProgramCache` sees at most
    `len(buckets)` prefill programs + 1 decode program, no matter how many
    distinct prompt lengths arrive.
  * **slot-masked decode** — `n_slots` decode lanes step together with
    per-slot absolute positions; idle lanes carry a masked dummy token.
    Admission writes a new request's prefill state into a free lane
    mid-flight (`_admit_into_slot`), while the other lanes keep decoding.
  * **encode-many / execute-once** — every model dispatch goes through
    `ExecutionStream.encode_operation` + one `execute_sync` per scheduler
    tick, and every `DispatchRecord` carries the costmodel floor estimate,
    so per-request dispatch overhead is measured, not modeled.

Scheduling policies
-------------------
Three ship here; all subclass `_SchedulerBase` and share admission/cache
machinery:

  * `SequentialSchedule` — the parity reference: one request at a time,
    full-length prefill + a private decode loop. One dispatch per token per
    request: the un-amortized floor.
  * `ContinuousSchedule` — slot-masked batched decode with mid-flight
    admission, serialized through `execute_sync` (the sound default).
  * `SLOSchedule` — overlapped decode on `AsyncExecutionStream` (the
    paper's unfinished overlapping-streams path): the host encodes decode
    step N+1 while step N executes, with sampling fused on-device so the
    token chain never round-trips the host, plus SLO-aware admission that
    defers a queued request while the costmodel-predicted token latency
    would breach `--slo-ms`.

Adding a policy: subclass `_SchedulerBase`, implement
`run(requests) -> list[RequestResult]` from the shared helpers
(`_prefill_program`, `_decode_program`, `_admit_into_slot`, `_reset_slot`,
`self.sampler`), and register it in `SCHEDULES`;
`launch/serve.py --schedule <name>` then drives it. Keep every model dispatch on `self.stream` so the floor accounting and
the `BENCH_serve.json` curve stay truthful.
"""

from __future__ import annotations

import dataclasses
import warnings
from collections import deque
from functools import partial
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding

from repro.configs.base import ShapeConfig
from repro.core import costmodel, hal
from repro.core.dispatch import (AsyncExecutionStream, ExecutionStream,
                                 ProgramCache)
from repro.kernels import compat
# TIME_MERGE_LEAVES historically lived here; the pool module owns the leaf
# taxonomy now and this re-export keeps existing imports working.
from repro.launch.kv_pool import PagedKVPool, TIME_MERGE_LEAVES  # noqa: F401
from repro.parallel import sharding as shard_rules
from repro.parallel.ctx import ParallelContext


# ---------------------------------------------------------------------------
# Requests
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One serving request: a prompt and a generation budget."""

    rid: int
    prompt: np.ndarray            # (L,) int32 token ids, L >= 1
    max_new_tokens: int
    arrival: int = 0              # scheduler step at which the request exists
    frames: np.ndarray | None = None   # encdec only: cfg.frame_shape

    def __post_init__(self) -> None:
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError(f"request {self.rid}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(f"request {self.rid}: max_new_tokens < 1")


@dataclasses.dataclass
class RequestResult:
    rid: int
    prompt_len: int
    tokens: np.ndarray            # (max_new_tokens,) generated ids
    bucket: int                   # prefill bucket used (0 = decode-only)
    admitted_step: int
    finished_step: int


def default_buckets(max_prompt_len: int) -> tuple[int, ...]:
    """Powers of two up to the longest prompt: ceil(log2) buckets total, so
    the prefill shape set stays logarithmic in prompt length."""
    out = []
    b = 8
    while b <= max_prompt_len:
        out.append(b)
        b *= 2
    return tuple(out) or (max(1, max_prompt_len),)


def bucket_for(prompt_len: int, buckets: Iterable[int]) -> int:
    """Largest bucket <= prompt_len (the prefilled prefix); 0 when every
    bucket is longer — the request then catches up entirely through decode."""
    fits = [b for b in buckets if b <= prompt_len]
    return max(fits) if fits else 0


# ---------------------------------------------------------------------------
# Prefill-cache -> decode-buffer merges
# ---------------------------------------------------------------------------


def _leaf_name(path: Any) -> str:
    return compat.tree_path_str(path).rsplit("/", 1)[-1]


def merge_prefill_caches(dec_caches: Any, pf_caches: Any) -> Any:
    """Copy prefill cache contents into the (longer time axis) decode
    buffers, whole-batch. Merging is by *named time axis*: a leaf may differ
    from its decode buffer on exactly one axis, and only when the leaf is a
    KV-time leaf (`TIME_MERGE_LEAVES`); the prefilled prefix lands at time
    offset 0, which is the ring-buffer slot for positions 0..s-1. Any rank
    mismatch, off-axis mismatch, or unnamed-axis mismatch raises with the
    tree path — prefill state (e.g. SSM conv/recurrent state) must never be
    silently dropped."""
    def merge(path, dst, src):
        loc = compat.tree_path_str(path)
        if dst.ndim != src.ndim:
            raise ValueError(
                f"cache leaf {loc!r}: prefill rank {src.ndim} {src.shape} != "
                f"decode buffer rank {dst.ndim} {dst.shape}; prefill state "
                f"would be dropped")
        diff = [i for i in range(dst.ndim) if dst.shape[i] != src.shape[i]]
        if not diff:
            return src.astype(dst.dtype)
        name = _leaf_name(path)
        if (len(diff) == 1 and name in TIME_MERGE_LEAVES
                and src.shape[diff[0]] <= dst.shape[diff[0]]):
            return jax.lax.dynamic_update_slice(
                dst, src.astype(dst.dtype), (0,) * dst.ndim)
        raise ValueError(
            f"cache leaf {loc!r}: cannot merge prefill {src.shape} into "
            f"decode buffer {dst.shape} (mismatched axes {diff}; only the "
            f"named time axis of {sorted(TIME_MERGE_LEAVES)} may differ)")
    return compat.tree_map_with_path(merge, dec_caches, pf_caches)


def _admit_leaf(path, dst, src, slot):
    """Write batch-1 prefill leaf `src` into decode lane `slot` of `dst`.

    Cache trees are stacked (stack/layer axis 0, batch axis 1); `src` has
    batch extent 1 and may be shorter than `dst` on its named time axis.
    `pos` lanes are re-initialized to -1 first so stale KV entries from the
    lane's previous occupant can never pass the validity mask."""
    loc = compat.tree_path_str(path)
    if dst.ndim != src.ndim:
        raise ValueError(
            f"cache leaf {loc!r}: prefill rank {src.ndim} != decode buffer "
            f"rank {dst.ndim}")
    if src.shape[1] != 1:
        raise ValueError(f"cache leaf {loc!r}: admission wants a batch-1 "
                         f"prefill cache, got batch {src.shape[1]}")
    diff = [i for i in range(dst.ndim)
            if i != 1 and dst.shape[i] != src.shape[i]]
    name = _leaf_name(path)
    row = src[:, 0].astype(dst.dtype)             # (stack, ...)
    if not diff:                                  # full-lane overwrite
        return dst.at[:, slot].set(row)
    if (len(diff) == 1 and name in TIME_MERGE_LEAVES
            and src.shape[diff[0]] <= dst.shape[diff[0]]):
        base = dst[:, slot]
        if name == "pos":                          # invalidate the stale tail
            base = jnp.full_like(base, -1)
        new_row = jax.lax.dynamic_update_slice(base, row, (0,) * base.ndim)
        return dst.at[:, slot].set(new_row)
    raise ValueError(
        f"cache leaf {loc!r}: cannot admit prefill {src.shape} into decode "
        f"buffer {dst.shape} (mismatched axes {diff})")


def _admit_into_slot_impl(dec_caches, pf_caches, slot):
    """Traceable admission body (speculative's joint two-model admission
    fuses this for target AND drafter caches inside one dispatch)."""
    return compat.tree_map_with_path(
        lambda p, d, s: _admit_leaf(p, d, s, slot), dec_caches, pf_caches)


@partial(jax.jit, donate_argnums=(0,))
def _admit_into_slot(dec_caches, pf_caches, slot):
    """One on-stream dispatch per admission: merge a batch-1 prefill cache
    into lane `slot` (resident buffers donated). Compiled once per prefill
    bucket shape via jit's own cache — deliberately outside the ProgramCache
    so the bucketing compile bound stays `#buckets x {prefill, decode}` —
    but executed through the ExecutionStream so the floor ledger charges
    it."""
    return _admit_into_slot_impl(dec_caches, pf_caches, slot)


# one fused dispatch for the sequential reference's whole-batch merge
_merge_prefill_jit = jax.jit(merge_prefill_caches, donate_argnums=(0,))


def _reset_slot_impl(dec_caches, slot):
    """Traceable reset body (see `_admit_into_slot_impl`)."""
    def reset(path, dst):
        name = _leaf_name(path)
        if name == "pos":
            return dst.at[:, slot].set(jnp.full_like(dst[:, slot], -1))
        if name in TIME_MERGE_LEAVES:
            return dst
        return dst.at[:, slot].set(jnp.zeros_like(dst[:, slot]))
    return compat.tree_map_with_path(reset, dec_caches)


@partial(jax.jit, donate_argnums=(0,))
def _reset_slot(dec_caches, slot):
    """Clear lane `slot` for a decode-only admission (no prefill prefix):
    `pos` lanes to -1 (nothing valid), recurrent/conv state to zeros (the
    init_cache state), KV payload left as-is (masked by pos)."""
    return _reset_slot_impl(dec_caches, slot)


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------

SAMPLING_MODES = ("greedy", "categorical")


class TokenSampler:
    """Per-request deterministic sampling: the key for the token placed at
    absolute position p of request r is fold_in(fold_in(seed, r), p), so a
    request's stream is identical under any schedule or batch composition."""

    def __init__(self, mode: str, vocab: int, seed: int) -> None:
        if mode not in SAMPLING_MODES:
            raise ValueError(f"sampling mode {mode!r} not in {SAMPLING_MODES}")
        self.mode = mode
        self.vocab = vocab
        self._root = jax.random.PRNGKey(seed)
        self._draw = jax.jit(
            lambda key, lg: jax.random.categorical(key, lg))

    def __call__(self, logits_row: np.ndarray, rid: int, position: int) -> int:
        lg = np.asarray(logits_row, np.float32)[: self.vocab]
        if self.mode == "greedy":
            return int(np.argmax(lg))
        key = jax.random.fold_in(jax.random.fold_in(self._root, rid), position)
        return int(self._draw(key, jnp.asarray(lg)))


# ---------------------------------------------------------------------------
# Schedulers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _ChunkedPrefill:
    """In-flight chunked prefill for one lane: a batch-1 decode-shaped
    staging cache accumulating chunk writes, and the [0, target) progress."""

    staging: Any
    done: int = 0
    target: int = 0


@dataclasses.dataclass
class _Slot:
    """One decode lane's host-side state machine."""

    req: Request | None = None
    next_pos: int = 0             # absolute position the next decode writes
    next_tok: int = 0             # token consumed by the next decode step
    generated: list[int] = dataclasses.field(default_factory=list)
    bucket: int = 0
    admitted_step: int = 0
    pending: _ChunkedPrefill | None = None

    @property
    def active(self) -> bool:
        return self.req is not None

    @property
    def prefilling(self) -> bool:
        """Mid chunked prefill: the lane holds the request but cannot decode
        yet — chunks still write into the staging cache."""
        return self.pending is not None

    @property
    def generating(self) -> bool:
        """Past the prompt: the next decode step's logits are sampled."""
        return self.active and not self.prefilling \
            and self.next_pos >= self.req.prompt.size


@dataclasses.dataclass
class ServeCounters:
    """Work counts of a scheduler, raised where the work happens and
    reported by `stats()`. Each decode dispatch adds one tick and splits its
    `n_slots` lanes into lane-steps: generated (the output is sampled),
    catch-up (a prompt token past the prefill bucket is teacher-forced) and
    empty (a masked dummy lane). Difference two snapshots
    (`dataclasses.asdict`) to count a window of a run."""

    decode_ticks: int = 0
    lane_steps_generated: int = 0
    lane_steps_catchup: int = 0
    lane_steps_empty: int = 0
    admissions: int = 0
    prefill_tokens: int = 0


class _SchedulerBase:
    """Shared machinery: bucketed prefill programs, admission, floor stats.

    Inside a profiler session the serve loops write host spans on the
    profiler's clock, beside the stream's ``stream.launch``/``stream.wait``:
    ``serve.tick`` (one loop iteration), ``serve.hook`` (the `step_hook`
    call), ``serve.admit`` (one admission or prefill chunk, with ``rid`` and
    ``bucket``), ``serve.frames`` (token/position frames to the device),
    ``serve.fetch`` (decode outputs to the host) and ``serve.advance`` (the
    lanes' host state, sampling included). Outside one they record nothing.
    """

    def __init__(self, model, params, cfg, *, max_len: int,
                 buckets: tuple[int, ...] | None = None,
                 sampling: str = "greedy", seed: int = 0,
                 program_cache: ProgramCache | None = None,
                 stream: ExecutionStream | None = None,
                 target: hal.Target | None = None,
                 ctx: ParallelContext | None = None) -> None:
        self.model = model
        self.cfg = cfg
        self.max_len = max_len
        self.buckets = tuple(sorted(buckets)) if buckets else \
            default_buckets(max_len)
        self.stream = stream or ExecutionStream(program_cache, target=target)
        self.cache = program_cache or self.stream.cache
        self.sampler = TokenSampler(sampling, cfg.vocab, seed)
        self.counters = ServeCounters()
        # Mesh serving: lanes span hosts over the batch axes, params
        # replicate except EP expert banks (serve_param_specs) — the
        # placement that keeps every token stream bit-identical to the
        # single-device path while the floor ledger stays per-dispatch
        # truthful (one SPMD program per tick, same dispatch count).
        self.ctx = ctx if ctx is not None else ParallelContext(mesh=None)
        # ProgramCache content hashes ignore shardings; the mesh descriptor
        # rides the `options` field so a mesh program can never collide with
        # a single-device program of identical shapes.
        self._copts = "" if not self.ctx.active else "mesh=" + "x".join(
            f"{a}{self.ctx.axis_size(a)}" for a in self.ctx.axis_names)
        if self.ctx.active:
            params = self._place(params,
                                 shard_rules.serve_param_specs(params,
                                                               self.ctx))
        self.params = params
        # called as step_hook(self, step) at the top of every serve-loop
        # tick; the elastic supervisor hangs heartbeat/failure checks here
        self.step_hook = None
        # run() aliases its live queue/results lists here so a supervisor
        # can read scheduler progress after an aborted run
        self._queue: list[Request] = []
        self._results: list[RequestResult] = []
        # decode-program handle per (token, pos) shape: the per-token hot
        # path must not re-flatten the whole (params, caches) pytree for a
        # ProgramCache key on every step (the warm start is free here)
        self._decode_memo: dict = {}

    # -- mesh placement -----------------------------------------------------
    def _place(self, tree, specs):
        """device_put every leaf to its NamedSharding; `specs` mirrors
        `tree` (DispatchedWeight nodes carry spec payloads)."""
        mesh = self.ctx.mesh
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree, specs)

    def _resident_jit(self, caches, *rest) -> dict:
        """jit kwargs for a program over the resident cache (arguments
        `params, caches, ...`; outputs `caches, *rest`). The cache is
        donated. On a mesh its output is pinned to its input placement and
        each other output to the sharding given (None: replicated), so the
        next dispatch gets the layout it was compiled for: kernels run
        replicated on a mesh (`dispatched.use_mesh`), and unpinned their
        outputs would pull the cache off its lane sharding."""
        kw = {"donate_argnums": (1,)}
        if self.ctx.active:
            rep = self.ctx.sharding()
            kw["out_shardings"] = (
                jax.tree.map(lambda a: a.sharding, caches),
                *(rep if s is None else s for s in rest))
        return kw

    def _batch_put(self, x):
        """Host batch array -> device, lane dim sharded over the batch axes
        when divisible (replicated otherwise) — `batch_specs`, applied to
        the scheduler's token/position frames."""
        xj = jnp.asarray(x)
        if not self.ctx.active:
            return xj
        spec = shard_rules.batch_specs(xj, self.ctx)
        return jax.device_put(xj, NamedSharding(self.ctx.mesh, spec))

    # -- programs -----------------------------------------------------------
    def _prefill_batch(self, tokens: np.ndarray,
                       frames: np.ndarray | None) -> dict:
        batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
        if self.cfg.family == "encdec":
            if frames is None:
                raise ValueError("encdec serving needs per-request frames")
            batch["frames"] = jnp.asarray(frames[None],
                                          self.model.dtype)
        return batch

    def _prefill_program(self, batch: dict):
        compiled, key = self.cache.compile(self.model.prefill, self.params,
                                           batch, options=self._copts)
        return compiled, key

    def _decode_program(self, caches, tok, pos):
        """Compile-or-hit the decode program. Cache shapes are fixed per
        scheduler (n_slots x max_len), so the handle is memoized by the
        (token, pos) shapes after the first ProgramCache resolution."""
        sig = (tok.shape, str(tok.dtype), pos.shape, str(pos.dtype))
        hit = self._decode_memo.get(sig)
        if hit is not None:
            return hit
        compiled, key = self.cache.compile(
            self.model.decode_step, self.params, caches, tok, pos,
            options=self._copts,
            jit_kwargs=self._resident_jit(caches, None))
        self._decode_memo[sig] = (compiled, key)
        return compiled, key

    def _check(self, req: Request) -> None:
        need = req.prompt.size + req.max_new_tokens
        if need > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt.size} + gen "
                f"{req.max_new_tokens} exceeds max_len {self.max_len}")
        if self.cfg.family == "encdec" and bucket_for(
                req.prompt.size, self.buckets) == 0:
            raise ValueError(
                f"request {req.rid}: encdec prompts must reach a prefill "
                f"bucket (cross-attention cache is built at prefill); "
                f"buckets={self.buckets}")

    # -- floor accounting ---------------------------------------------------
    def stats(self, n_requests: int) -> dict:
        recs = self.stream.records
        n = max(n_requests, 1)
        out = {
            "n_dispatches": len(recs),
            "floor_s": self.stream.total_floor_s(),
            "work_s": self.stream.total_work_s(),
            "dispatch_wall_s": sum(r.wall_s for r in recs),
            "per_request_dispatch_overhead_s": self.stream.total_floor_s() / n,
            "per_request_dispatches": len(recs) / n,
            "counters": dataclasses.asdict(self.counters),
        }
        if self.ctx.active:
            # SPMD: every host issues the same command sequence, so each
            # batch-axis rank (one "host": its model ranks are co-located
            # engine slices) pays the full per-dispatch floor — the fleet
            # floor is hosts x the ledger, an identity the sharded-serve
            # bench gates.
            n_hosts = 1
            for a in self.ctx.batch_axes:
                n_hosts *= self.ctx.axis_size(a)
            out.update({
                "mesh_axes": {a: self.ctx.axis_size(a)
                              for a in self.ctx.axis_names},
                "n_hosts": n_hosts,
                "per_host_floor_s": out["floor_s"],
                "fleet_floor_s": out["floor_s"] * n_hosts,
            })
        return out


class SequentialSchedule(_SchedulerBase):
    """The parity reference: requests served one at a time, full-length
    prefill + a private batch-1 decode loop. Every token pays its own
    dispatch floor — the §9.4 worst case the continuous schedule amortizes.
    This is the seed serve loop's semantics, kept bit-compatible."""

    name = "sequential"

    def run(self, requests: list[Request]) -> list[RequestResult]:
        results = []
        for step, req in enumerate(sorted(requests, key=lambda r:
                                          (r.arrival, r.rid))):
            self._check(req)
            L = req.prompt.size
            self.counters.admissions += 1
            self.counters.prefill_tokens += L
            batch = self._prefill_batch(req.prompt[None], req.frames)
            prefill, pkey = self._prefill_program(batch)
            self.stream.encode_operation(prefill, (self.params, batch),
                                         pkey, batch=1)
            pf_caches, logits = self.stream.execute_sync()[0]

            caches = self.model.init_cache(1, self.max_len)
            self.stream.encode_operation(_merge_prefill_jit,
                                         (caches, pf_caches),
                                         "merge_prefill", batch=1)
            caches = self.stream.execute_sync()[0]
            tok = self.sampler(np.asarray(logits)[0, -1], req.rid, L)
            generated = [tok]
            for i in range(req.max_new_tokens - 1):
                pos = L + i
                tokj = jnp.asarray([[tok]], jnp.int32)
                posj = jnp.full((1,), pos, jnp.int32)
                decode, dkey = self._decode_program(caches, tokj, posj)
                self.stream.encode_operation(
                    decode, (self.params, caches, tokj, posj), dkey, batch=1)
                caches, logits = self.stream.execute_sync()[0]
                self.counters.decode_ticks += 1
                self.counters.lane_steps_generated += 1
                tok = self.sampler(np.asarray(logits)[0, -1], req.rid, pos + 1)
                generated.append(tok)
            results.append(RequestResult(
                req.rid, L, np.asarray(generated, np.int32),
                bucket=L, admitted_step=step, finished_step=step))
        return results


class ContinuousSchedule(_SchedulerBase):
    """Continuous batching: `n_slots` decode lanes in one resident cache,
    stepping together. New requests are admitted into free lanes mid-flight:
    prefill at the largest bucket <= the prompt, catch the tail up through
    the shared decode program (teacher-forced prompt tokens), then generate.
    All lanes share each decode dispatch, so the per-request floor share is
    floor / n_active."""

    name = "continuous"

    def __init__(self, model, params, cfg, *, n_slots: int, max_len: int,
                 prefix_cache: bool = False, prefix_blocks: int = 64,
                 prefix_block_size: int = 8,
                 prefix_pool: PagedKVPool | None = None,
                 prefill_chunk: int | None = None, **kw) -> None:
        super().__init__(model, params, cfg, max_len=max_len, **kw)
        if n_slots < 1:
            raise ValueError(f"continuous schedule needs n_slots >= 1, "
                             f"got {n_slots}")
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, "
                                 f"got {prefill_chunk}")
            if cfg.family == "encdec":
                raise ValueError(
                    "chunked prefill cannot serve encdec: the cross-attention "
                    "cache is built by the monolithic prefill program, so a "
                    "decode-mode chunk has no frames to attend to")
        self.prefill_chunk = prefill_chunk
        self._chunk_memo: dict = {}
        self._chunk_keys: set[str] = set()
        self.n_slots = n_slots
        self.slots = [_Slot() for _ in range(n_slots)]
        self.caches = None        # allocated lazily on first run
        self.pool: PagedKVPool | None = None
        if prefix_cache or prefix_pool is not None:
            if cfg.family == "encdec":
                raise ValueError(
                    "prefix cache cannot serve encdec: the cross-attention "
                    "cache is built from per-request frames, so token-hash "
                    "block sharing would alias state across requests")
            # `prefix_pool` hands in an already-populated pool — the elastic
            # supervisor's rescale path, which carries resident blocks (and
            # their eviction policy) across scheduler rebuilds
            self.pool = prefix_pool if prefix_pool is not None else \
                PagedKVPool(prefix_blocks, prefix_block_size,
                            evict_cost_fn=self._re_prefill_cost)
            pool = self.pool

            # both admission-side pool programs are jitted outside the
            # ProgramCache, like `_admit_into_slot` (the compile bound stays
            # `#buckets x {prefill, decode}`), but dispatch on the stream so
            # every pool touch is floor-charged like any other command
            @partial(jax.jit, donate_argnums=(0,))
            def _prefix_admit(dec_caches, arenas, bids, anchor, slot):
                pf = pool.assemble_prefix(dec_caches, arenas, bids, anchor)
                return _admit_into_slot_impl(dec_caches, pf, slot)

            @partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
            def _pool_insert(arenas, pf_caches, bids, start):
                return pool.insert_blocks(arenas, pf_caches, bids, start)

            self._prefix_admit_jit = _prefix_admit
            self._pool_insert_jit = _pool_insert

    def _re_prefill_cost(self, n_tokens: int) -> float:
        """Costmodel floor+work of re-prefilling an `n_tokens` resident
        prefix at batch 1: what evicting a block whose chain ends
        `n_tokens` deep would cost to rebuild on a future hit-turned-miss.
        The pool minimizes this over refcount-0 eviction candidates
        (`PagedKVPool._evict_victim`), so cheap-to-recreate shallow chains
        go before deep ones."""
        shape = ShapeConfig("re_prefill", max(1, n_tokens), 1, "prefill")
        t = self.stream.target
        flops = costmodel.model_flops(self.cfg, shape) \
            + costmodel.attention_flops(self.cfg, shape)
        work = max(flops / t.peak_flops,
                   costmodel.weight_bytes(self.cfg) / t.hbm_bandwidth)
        return self.stream.floor_s + work

    def _ensure_caches(self) -> None:
        if self.caches is None:
            self.caches = self.model.init_cache(self.n_slots, self.max_len)
            if self.ctx.active:
                self.caches = self._place(
                    self.caches,
                    shard_rules.serve_cache_specs(self.caches, self.ctx))
        if self.pool is not None:
            if self.pool.arenas is None:
                self.pool.bind(self.caches, max_len=self.max_len)
            if self.ctx.active:
                # fresh or carried-over (supervisor rescale) arenas land
                # replicated on the *current* mesh — a carried pool's rows
                # may still be placed on the pre-failure device set
                self.pool.arenas = self._place(
                    self.pool.arenas,
                    shard_rules.serve_arena_specs(self.pool.arenas, self.ctx))

    # -- prefix-cache admission ---------------------------------------------
    def _prefix_hit_admit(self, req: Request, slot: _Slot, sidx,
                          bucket: int) -> bool:
        """Admit from resident blocks when the prompt's longest anchored
        resident prefix reaches at least the bucket a cold admission would
        prefill: ONE fused gather+merge dispatch replaces the prefill +
        lane-write pair, and the matched blocks' prefill work is never
        dispatched at all. The matched length M is capped at L-1 so at least
        one prompt token always remains to teacher-force the first decode
        step — hit admissions never need logits, and token streams stay
        bit-identical to cold admissions (sampling is keyed per (rid,
        position), and positions M..L-1 catch up through the shared decode
        program exactly as a bucket-M cold admission would)."""
        pool = self.pool
        if pool is None or pool.arenas is None:
            return False
        L = req.prompt.size
        keys = pool.anchored_match(req.prompt, limit=L - 1)
        M = len(keys) * pool.block_size
        if not keys or M < max(bucket, 1):
            return False
        bids = jnp.asarray(pool.bids_of(keys), jnp.int32)
        anchor = pool.anchor_of(keys[-1])
        self.stream.encode_operation(
            self._prefix_admit_jit,
            (self.caches, pool.arenas, bids, anchor, sidx),
            "prefix_admit", batch=1)
        self.caches = self.stream.execute_sync()[0]
        pool.acquire(req.rid, keys)
        pool.stats["hits"] += 1
        pool.stats["hit_tokens"] += M
        slot.next_pos = M
        slot.next_tok = int(req.prompt[M])
        return True

    def _pool_cold_insert(self, req: Request, bucket: int, pf_caches,
                          staging: bool = False) -> None:
        """Cold-path residency: reserve arena rows for the prefilled whole
        blocks and write them with one extra dispatch (floor-charged — the
        honest cost of caching); the chain end anchors the non-paged leaves
        (recurrent state, conv tails, ring-window KV) so later admissions
        can resume from exactly this boundary. Chunked admissions pass
        `staging=True`: the source is a decode-shaped staging cache whose
        time extent is `max_len`, valid through `bucket` (a chunk boundary,
        so the anchored chain lands exactly where chunks stopped writing)."""
        pool = self.pool
        pool.stats["misses"] += 1
        if bucket < pool.block_size:
            return
        keys, new_bids, first_new = pool.reserve(req.prompt[:bucket])
        if new_bids:
            pool.validate_prefill(pf_caches, bucket, staging=staging)
            bids = jnp.asarray(new_bids, jnp.int32)
            self.stream.encode_operation(
                self._pool_insert_jit,
                (pool.arenas, pf_caches, bids, first_new),
                "pool_insert", batch=1)
            pool.arenas = self.stream.execute_sync()[0]
        if keys:
            if len(keys) * pool.block_size == bucket:
                pool.set_anchor(keys[-1], pool.anchor_leaves(pf_caches))
            pool.acquire(req.rid, keys)

    def _release_lane(self, req: Request) -> None:
        if self.pool is not None:
            self.pool.release(req.rid)

    # -- chunked prefill ----------------------------------------------------
    def _chunk_program(self, staging, tok, pos0):
        """Compile-or-hit the prefill-chunk program. The staging cache is
        decode-shaped (batch 1 x max_len) whatever the prompt, so the handle
        is memoized by the chunk width alone: ONE ProgramCache entry per
        chunk size, not per prompt bucket — the whole point of chunking's
        compile economics."""
        sig = (tok.shape, str(tok.dtype))
        hit = self._chunk_memo.get(sig)
        if hit is not None:
            return hit
        compiled, key = self.cache.compile(
            self.model.prefill_chunk, self.params, staging, tok, pos0,
            options=self._copts, jit_kwargs={"donate_argnums": (1,)})
        self._chunk_keys.add(key)
        hit = (compiled, key)
        self._chunk_memo[sig] = hit
        return hit

    def _begin_chunked(self, slot: _Slot, req: Request, target: int) -> None:
        """Stage a chunked prefill: allocate a fresh batch-1 decode-shaped
        cache (attention positions init to -1, recurrent state zero — the
        same clean state `_reset_slot` produces, so no reset dispatch is
        needed) and mark the lane pending. Chunks advance one per serve
        tick, so in-flight decode lanes get a window between every pair of
        chunks instead of stalling behind one monolithic prefill."""
        staging = self.model.init_cache(1, self.max_len)
        if self.ctx.active:
            staging = self._place(
                staging, shard_rules.serve_staging_specs(staging, self.ctx))
        slot.pending = _ChunkedPrefill(staging=staging, done=0, target=target)
        slot.next_pos = 0
        slot.next_tok = 0

    def _advance_chunk(self, slot_idx: int, step: int) -> None:
        """Dispatch ONE chunk for a pending lane: C prompt tokens forward in
        decode mode against the staging cache, floor-charged on the stream
        like any other dispatch (`span` records the token range for the
        bench audit). The final chunk hands off to `_finish_chunked`."""
        slot = self.slots[slot_idx]
        pend, req = slot.pending, slot.req
        with TraceAnnotation("serve.admit", rid=req.rid, bucket=pend.target):
            c0 = pend.done
            n = min(self.prefill_chunk, pend.target - c0)
            self.counters.prefill_tokens += n
            tokj = jnp.asarray(req.prompt[None, c0:c0 + n], jnp.int32)
            pos0 = jnp.full((1,), c0, jnp.int32)
            compiled, ckey = self._chunk_program(pend.staging, tokj, pos0)
            self.stream.encode_operation(
                compiled, (self.params, pend.staging, tokj, pos0), ckey,
                batch=1, span=(c0, c0 + n))
            pend.staging, _ = self.stream.execute_sync()[0]
            pend.done = c0 + n
            if pend.done >= pend.target:
                self._finish_chunked(slot_idx)

    def _finish_chunked(self, slot_idx: int) -> None:
        """Admit the fully-staged prefix into the lane: the staging cache's
        time extent equals the lane's, so `_admit_into_slot` overwrites
        every leaf of the lane wholesale (positions included) in one donated
        dispatch — the same path bucketed admissions take. The chunk target
        is capped at L-1, so the first decode step is always teacher-forced
        and no finalize logits are needed."""
        slot = self.slots[slot_idx]
        pend, req = slot.pending, slot.req
        sidx = jnp.asarray(slot_idx, jnp.int32)
        if self.pool is not None:
            self._pool_cold_insert(req, pend.target, pend.staging,
                                   staging=True)
        self.stream.encode_operation(
            _admit_into_slot, (self.caches, pend.staging, sidx),
            "admit_slot", batch=1)
        self.caches = self.stream.execute_sync()[0]
        slot.pending = None
        slot.next_pos = pend.target
        slot.next_tok = int(req.prompt[pend.target])

    # -- admission ----------------------------------------------------------
    def _admit(self, slot_idx: int, req: Request, step: int) -> None:
        """Prefill the bucket prefix through the stream, then write the
        prefill state into the lane. Called after `_check`. With
        `prefill_chunk` set, the prompt prefills as chunks instead: the
        target is the largest chunk multiple <= L-1 (positions target..L-1
        catch up teacher-forced through the shared decode program, exactly
        like a bucket-target cold admission, which keeps token streams
        bit-identical to unchunked serving)."""
        slot = self.slots[slot_idx]
        L = req.prompt.size
        C = self.prefill_chunk
        if C is not None:
            bucket = C * ((L - 1) // C)
        else:
            bucket = bucket_for(L, self.buckets)
        self.counters.admissions += 1
        with TraceAnnotation("serve.admit", rid=req.rid, bucket=bucket):
            sidx = jnp.asarray(slot_idx, jnp.int32)
            # lane writes dispatch on the stream too: the floor ledger must
            # charge every real dispatch, admissions included
            if self._prefix_hit_admit(req, slot, sidx, bucket):
                pass                  # admitted from resident blocks
            elif C is not None and bucket > 0:
                self._begin_chunked(slot, req, bucket)
            elif bucket == 0:
                self.stream.encode_operation(_reset_slot, (self.caches, sidx),
                                             "reset_slot", batch=1)
                self.caches = self.stream.execute_sync()[0]
                if self.pool is not None:
                    self.pool.stats["misses"] += 1
                slot.next_pos, slot.next_tok = 0, int(req.prompt[0])
            else:
                self.counters.prefill_tokens += bucket
                batch = self._prefill_batch(req.prompt[None, :bucket],
                                            req.frames)
                prefill, pkey = self._prefill_program(batch)
                self.stream.encode_operation(prefill, (self.params, batch),
                                             pkey, batch=1)
                pf_caches, logits = self.stream.execute_sync()[0]
                if self.pool is not None:
                    self._pool_cold_insert(req, bucket, pf_caches)
                self.stream.encode_operation(
                    _admit_into_slot, (self.caches, pf_caches, sidx),
                    "admit_slot", batch=1)
                self.caches = self.stream.execute_sync()[0]
                slot.next_pos = bucket
                if bucket < L:        # catch up through decode, teacher-forced
                    slot.next_tok = int(req.prompt[bucket])
                else:                 # prompt fully prefilled: sample token L
                    tok = self.sampler(np.asarray(logits)[0, -1], req.rid, L)
                    slot.generated.append(tok)
                    slot.next_tok = tok
        slot.req = req
        slot.bucket = bucket
        slot.admitted_step = step

    def _advance(self, slot: _Slot, logits_row: np.ndarray,
                 results: list[RequestResult], step: int) -> None:
        """Consume one decode step's logits for an active lane."""
        req = slot.req
        pos_written = slot.next_pos
        slot.next_pos = pos_written + 1
        nxt = pos_written + 1
        if nxt < req.prompt.size:            # still catching up: teacher-force
            self.counters.lane_steps_catchup += 1
            slot.next_tok = int(req.prompt[nxt])
            return
        self.counters.lane_steps_generated += 1
        tok = self.sampler(logits_row, req.rid, nxt)
        slot.generated.append(tok)
        slot.next_tok = tok
        if len(slot.generated) >= req.max_new_tokens:
            results.append(RequestResult(
                req.rid, req.prompt.size,
                np.asarray(slot.generated[:req.max_new_tokens], np.int32),
                bucket=slot.bucket, admitted_step=slot.admitted_step,
                finished_step=step))
            self._release_lane(req)
            slot.req = None
            slot.generated = []

    # -- the serve loop -----------------------------------------------------
    def run(self, requests: list[Request]) -> list[RequestResult]:
        for r in requests:
            self._check(r)
        queue = sorted(requests, key=lambda r: (r.arrival, r.rid))
        self._ensure_caches()
        results: list[RequestResult] = []
        # alias live state for the elastic supervisor: both lists mutate in
        # place, so lane snapshots survive a mid-run HostFailure
        self._queue, self._results = queue, results
        step = 0
        while queue or any(s.active for s in self.slots):
            with TraceAnnotation("serve.tick"):
                if self.step_hook is not None:
                    with TraceAnnotation("serve.hook"):
                        self.step_hook(self, step)
                # admissions: free lanes x arrived requests, in arrival order
                for i, slot in enumerate(self.slots):
                    if not queue or queue[0].arrival > step:
                        break
                    if not slot.active:
                        self._admit(i, queue.pop(0), step)
                # pending lanes advance ONE chunk per tick, so a decode window
                # runs between every pair of chunks instead of the whole prompt
                # blocking the in-flight lanes at once
                for i, slot in enumerate(self.slots):
                    if slot.prefilling:
                        self._advance_chunk(i, step)
                active = [s for s in self.slots
                          if s.active and not s.prefilling
                          and not (s.generating and len(s.generated)
                                   >= s.req.max_new_tokens)]
                # a fully-prefilled request can finish without a decode step
                for s in list(self.slots):
                    if s.active and s.generating \
                            and len(s.generated) >= s.req.max_new_tokens:
                        self._advance_finished(s, results, step)
                if not active:
                    if queue or any(s.prefilling for s in self.slots):
                        step += 1     # idle tick: arrival or mid-chunk prefill
                        continue
                    break
                # one slot-masked decode dispatch for every lane
                with TraceAnnotation("serve.frames"):
                    tok = np.zeros((self.n_slots, 1), np.int32)
                    pos = np.zeros((self.n_slots,), np.int32)
                    for i, s in enumerate(self.slots):
                        if s.active and not s.prefilling:
                            tok[i, 0] = s.next_tok
                            pos[i] = s.next_pos
                    tokj = self._batch_put(tok)
                    posj = self._batch_put(pos)
                decode, dkey = self._decode_program(self.caches, tokj, posj)
                self.stream.encode_operation(
                    decode, (self.params, self.caches, tokj, posj), dkey,
                    batch=len(active))
                self.caches, logits = self.stream.execute_sync()[0]
                self.counters.decode_ticks += 1
                self.counters.lane_steps_empty += self.n_slots - len(active)
                with TraceAnnotation("serve.fetch"):
                    lg = np.asarray(logits[:, -1, : self.cfg.vocab],
                                    np.float32)
                with TraceAnnotation("serve.advance"):
                    for i, s in enumerate(self.slots):
                        if s.active and not s.prefilling:
                            self._advance(s, lg[i], results, step)
                step += 1
        results.sort(key=lambda r: r.rid)
        return results

    def _advance_finished(self, slot: _Slot, results: list[RequestResult],
                          step: int) -> None:
        req = slot.req
        results.append(RequestResult(
            req.rid, req.prompt.size,
            np.asarray(slot.generated[:req.max_new_tokens], np.int32),
            bucket=slot.bucket, admitted_step=slot.admitted_step,
            finished_step=step))
        self._release_lane(req)
        slot.req = None
        slot.generated = []

    # -- reporting ----------------------------------------------------------
    def stats(self, n_requests: int) -> dict:
        out = super().stats(n_requests)
        if self.pool is not None:
            out["prefix_cache"] = dict(self.pool.stats)
            out["prefix_cache"]["free_blocks"] = self.pool.free_blocks()
        if self.prefill_chunk is not None:
            recs = self.stream.records
            out["chunked_prefill"] = {
                "prefill_chunk": self.prefill_chunk,
                "n_chunks": sum(1 for r in recs
                                if r.key in self._chunk_keys),
                "chunk_tokens": sum(r.span[1] - r.span[0] for r in recs
                                    if r.span is not None),
            }
        return out


class SLOSchedule(ContinuousSchedule):
    """Overlapped continuous batching with SLO-aware admission.

    The decode loop is software-pipelined on `AsyncExecutionStream`: the
    host plans a *window* of decode steps whose control flow is fully
    deterministic (teacher-forcing vs sampling per lane follows positions,
    never logits), fuses next-token selection into the decode program
    (device argmax / per-(rid, pos) fold_in categorical — bit-identical to
    the host `TokenSampler`), and submits each step with the previous
    step's token output chained in as a live async value. The host never
    blocks per token: step N+1 is encoded and submitted while step N
    executes, and tokens materialize once per window at the sync barrier.
    Windows end exactly where host decisions live — a lane completing, or a
    queued arrival that could claim a free lane.

    Admission is gated on the costmodel: a queued request is admitted into
    a free lane only when the predicted token latency
    `dispatch_floor_s x in-flight depth + per-token work` (work = p99 of
    recent decode-step walls, the floor until observed) stays under the
    SLO. An idle engine always admits — the gate sheds load, it cannot
    starve. Deferred admissions are counted in `deferred_admissions`.

    Token streams are schedule-invariant by construction (greedy ignores
    the schedule; categorical is keyed per (request, position)), so this
    policy is token-exact against `ContinuousSchedule` and
    `SequentialSchedule` whatever the SLO defers.
    """

    name = "slo"

    #: decode-wall samples retained for the p99 work predictor
    WALL_WINDOW = 64

    #: default in-flight window when this schedule builds its own stream: a
    #: typical decode run-ahead, deep enough that submits inside one window
    #: rarely throttle (each throttle costs a drain-thread wakeup on the
    #: critical path); the stream's own default of 2 is plain double
    #: buffering for callers that hand-roll submit/sync
    MAX_IN_FLIGHT = 8

    def __init__(self, model, params, cfg, *, n_slots: int, max_len: int,
                 slo_ms: float | None = None, max_in_flight: int = MAX_IN_FLIGHT,
                 stream: ExecutionStream | None = None,
                 program_cache: ProgramCache | None = None,
                 target: hal.Target | None = None, **kw) -> None:
        if stream is None:
            stream = AsyncExecutionStream(program_cache, target=target,
                                          max_in_flight=max_in_flight)
        if not isinstance(stream, AsyncExecutionStream):
            raise ValueError(
                "SLOSchedule pipelines decode through AsyncExecutionStream; "
                f"got {type(stream).__name__} (a sync stream would serialize "
                "the window and the floor accounting would not reflect "
                "overlap)")
        super().__init__(model, params, cfg, n_slots=n_slots, max_len=max_len,
                         stream=stream, program_cache=program_cache,
                         target=target, **kw)
        self.slo_s = None if slo_ms is None else float(slo_ms) * 1e-3
        self.deferred_admissions = 0
        self._step_memo: dict = {}
        self._decode_keys: set[str] = set()
        self._decode_walls: deque[float] = deque(maxlen=self.WALL_WINDOW)
        self._records_seen = 0

    # -- fused decode + on-device sampling ----------------------------------
    def _fused_step_program(self, caches, tok, pos, forced, do_sample, rids):
        """Compile-or-hit the pipelined step: decode_step + next-token
        selection in one program, so the token chain stays on device. The
        sampling math mirrors `TokenSampler` exactly: fp32 logits sliced to
        the vocab, first-index argmax for greedy, fold_in(fold_in(seed,
        rid), pos) categorical otherwise."""
        sig = (tok.shape, str(tok.dtype), pos.shape)
        hit = self._step_memo.get(sig)
        if hit is not None:
            return hit
        model, vocab = self.model, self.cfg.vocab
        mode, root = self.sampler.mode, self.sampler._root

        def fused(params, caches, tok, pos, forced, do_sample, rids):
            caches, logits = model.decode_step(params, caches, tok, pos)
            lg = logits[:, -1, :vocab].astype(jnp.float32)
            if mode == "greedy":
                samp = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            else:
                def draw(rid, p, row):
                    key = jax.random.fold_in(jax.random.fold_in(root, rid), p)
                    return jax.random.categorical(key, row)
                samp = jax.vmap(draw)(rids, pos + 1, lg).astype(jnp.int32)
            nxt = jnp.where(do_sample, samp, forced).astype(jnp.int32)
            return caches, nxt[:, None], samp

        compiled, key = self.cache.compile(
            fused, self.params, caches, tok, pos, forced, do_sample, rids,
            options=self._copts,
            jit_kwargs=self._resident_jit(caches, tok.sharding, None))
        self._decode_keys.add(key)
        hit = (compiled, key)
        self._step_memo[sig] = hit
        return hit

    # -- the SLO admission gate ---------------------------------------------
    def _observe_decode_walls(self) -> None:
        """Fold any new decode-step records into the work predictor."""
        recs = self.stream.records
        for r in recs[self._records_seen:]:
            if r.key in self._decode_keys:
                self._decode_walls.append(r.wall_s)
        self._records_seen = len(recs)

    def predicted_token_latency_s(self) -> float:
        """Costmodel-predicted p99 token latency were one more request
        admitted now: each decode tick pays the dispatch floor once per
        submission that can sit in flight ahead of it (the window bound),
        plus the per-token work — the p99 of recently observed decode-step
        walls, or the floor itself before anything was observed."""
        if self._decode_walls:
            walls = sorted(self._decode_walls)
            work = walls[min(len(walls) - 1, int(0.99 * len(walls)))]
        else:
            work = self.stream.floor_s
        # the gate runs at drained barriers (live in-flight depth 0), so the
        # p99 queue-delay term uses the window bound the next pipelined
        # window will fill to, not the momentary depth
        return self.stream.floor_s * self.stream.max_in_flight + work

    def _admission_clear(self) -> bool:
        if self.slo_s is None:
            return True
        if not any(s.active for s in self.slots) \
                and self.stream.in_flight_depth == 0:
            return True          # idle engine: deferring forever would
                                 # starve without ever improving the SLO
        return self.predicted_token_latency_s() <= self.slo_s

    # -- the pipelined serve loop -------------------------------------------
    def _window_horizon(self, step: int, queue: list[Request]) -> int:
        """Decode steps encodable ahead without a host decision: up to the
        first lane completion, never past the step at which a queued
        arrival could claim a currently-free lane, and never deeper than
        the stream's in-flight window — submitting past the window would
        throttle every further step on a drain-thread wakeup, while
        syncing at the window boundary drains once per window."""
        remain = []
        for s in self.slots:
            if not s.active or s.prefilling:
                continue
            # steps still teacher-forced before sampling starts at this lane
            forced_left = max(0, s.req.prompt.size - 1 - s.next_pos)
            to_sample = s.req.max_new_tokens - len(s.generated)
            remain.append(forced_left + to_sample)
        k = min(remain + [self.stream.max_in_flight])
        if queue and any(not s.active for s in self.slots):
            k = min(k, max(1, queue[0].arrival - step))
        if any(s.prefilling for s in self.slots):
            k = 1          # a pending lane's next chunk bounds the window:
                           # decode one step, then give the chunk a turn
        return k

    def _pipelined_window(self, step: int, queue: list[Request],
                          results: list[RequestResult]) -> int:
        """Encode + submit `k` chained decode steps, then sync once and fold
        the materialized tokens back into the host state machines."""
        k = self._window_horizon(step, queue)
        n = self.n_slots
        c = self.counters
        with TraceAnnotation("serve.frames"):
            tok0 = np.zeros((n, 1), np.int32)
            rids = np.zeros((n,), np.int32)
            for i, s in enumerate(self.slots):
                if s.active and not s.prefilling:
                    tok0[i, 0] = s.next_tok
                    rids[i] = s.req.rid
            tok_dev = self._batch_put(tok0)   # becomes a chained async value
            ridsj = self._batch_put(rids)
        plan: list[tuple[Any, list[int]]] = []
        for _ in range(k):
            with TraceAnnotation("serve.frames"):
                pos = np.zeros((n,), np.int32)
                forced = np.zeros((n,), np.int32)
                mask = np.zeros((n,), bool)
                sampled_lanes: list[int] = []
                n_active = 0
                for i, s in enumerate(self.slots):
                    if not s.active or s.prefilling:
                        continue
                    n_active += 1
                    pos[i] = s.next_pos
                    nxt = s.next_pos + 1
                    if nxt < s.req.prompt.size:   # catch-up: teacher-force
                        forced[i] = int(s.req.prompt[nxt])
                    else:
                        mask[i] = True
                        sampled_lanes.append(i)
                    s.next_pos = nxt
                posj = self._batch_put(pos)
                forcedj = self._batch_put(forced)
                maskj = self._batch_put(mask)
            compiled, dkey = self._fused_step_program(
                self.caches, tok_dev, posj, forcedj, maskj, ridsj)
            self.stream.encode_operation(
                compiled, (self.params, self.caches, tok_dev, posj, forcedj,
                           maskj, ridsj), dkey, batch=n_active)
            # submit without blocking: caches/token chain forward as live
            # async values; the background drain confirms completions
            self.caches, tok_dev, samp = self.stream.submit()[0]
            plan.append((samp, sampled_lanes))
            c.decode_ticks += 1
            c.lane_steps_generated += len(sampled_lanes)
            c.lane_steps_catchup += n_active - len(sampled_lanes)
            c.lane_steps_empty += n - n_active
        self.stream.sync()
        self._observe_decode_walls()
        with TraceAnnotation("serve.fetch"):
            nxt_host = np.asarray(tok_dev)[:, 0]
            fetched = [(np.asarray(samp) if lanes else None, lanes)
                       for samp, lanes in plan]
        with TraceAnnotation("serve.advance"):
            for t, (samp_np, sampled_lanes) in enumerate(fetched):
                for i in sampled_lanes:
                    s = self.slots[i]
                    s.generated.append(int(samp_np[i]))
                    if len(s.generated) >= s.req.max_new_tokens:
                        self._advance_finished(s, results, step + t)
            for i, s in enumerate(self.slots):
                if s.active and not s.prefilling:
                    s.next_tok = int(nxt_host[i])
        return step + k

    def run(self, requests: list[Request]) -> list[RequestResult]:
        for r in requests:
            self._check(r)
        queue = sorted(requests, key=lambda r: (r.arrival, r.rid))
        self._ensure_caches()
        results: list[RequestResult] = []
        self._queue, self._results = queue, results
        step = 0
        while queue or any(s.active for s in self.slots):
            with TraceAnnotation("serve.tick"):
                if self.step_hook is not None:
                    with TraceAnnotation("serve.hook"):
                        self.step_hook(self, step)
                # admissions happen at a drained barrier (prefill + lane writes
                # are stream dispatches themselves); the gate reads the ledger
                for i, slot in enumerate(self.slots):
                    if not queue or queue[0].arrival > step:
                        break
                    if slot.active:
                        continue
                    if not self._admission_clear():
                        self.deferred_admissions += 1
                        break
                    self._admit(i, queue.pop(0), step)
                # pending lanes advance ONE chunk at this drained barrier, so
                # the SLO gate and the in-flight decode window both see each
                # chunk as an ordinary dispatch — never a monolithic stall
                for i, slot in enumerate(self.slots):
                    if slot.prefilling:
                        self._advance_chunk(i, step)
                # a fully-prefilled request can finish without a decode step
                for s in list(self.slots):
                    if s.active and s.generating \
                            and len(s.generated) >= s.req.max_new_tokens:
                        self._advance_finished(s, results, step)
                if not any(s.active for s in self.slots):
                    if queue:
                        step += 1     # idle tick: wait for the next arrival
                        continue
                    break
                if not any(s.active and not s.prefilling for s in self.slots):
                    step += 1         # only mid-chunk lanes: nothing to decode
                    continue
                step = self._pipelined_window(step, queue, results)
        results.sort(key=lambda r: r.rid)
        return results

    # -- reporting ----------------------------------------------------------
    def stats(self, n_requests: int) -> dict:
        out = super().stats(n_requests)
        recs = self.stream.records
        out.update({
            "deferred_admissions": self.deferred_admissions,
            "max_in_flight": self.stream.max_in_flight,
            "mean_inflight_depth": float(np.mean(
                [r.inflight_depth for r in recs])) if recs else 0.0,
            "predicted_token_latency_s": self.predicted_token_latency_s(),
        })
        return out


SCHEDULES = {
    "sequential": SequentialSchedule,
    "continuous": ContinuousSchedule,
    "slo": SLOSchedule,
    # "spec" (SpeculativeSchedule) registers itself from launch.speculative,
    # imported at the bottom of this module
}

# ---------------------------------------------------------------------------
# Typed serve configuration
# ---------------------------------------------------------------------------
# `ServeConfig` is the construction API: one dataclass per schedule-specific
# knob group, attached as a section (`slo=`, `spec=`, `prefix=`, `chunk=`).
# A section present on a schedule it does not apply to is a loud ValueError
# at `validate()` — the old `make_scheduler(**kw)` silently stripped such
# knobs, which let a misspelled or misplaced flag vanish without a trace.


@dataclasses.dataclass(frozen=True)
class SLOConfig:
    """`slo` schedule knobs: admission-gate target + in-flight window."""
    slo_ms: float | None = None
    max_in_flight: int = SLOSchedule.MAX_IN_FLIGHT


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """`spec` schedule knobs: drafter selection + window depth."""
    draft_depth: int = 4
    draft: str = "shrink"
    draft_ckpt: str | None = None
    draft_branches: int = 1
    drafter: Any = None           # a prebuilt Drafter overrides `draft`
    max_in_flight: int = 2


@dataclasses.dataclass(frozen=True)
class PrefixConfig:
    """Paged KV prefix pool (continuous/slo). Presence of the section
    enables the pool; `pool` hands in an already-populated PagedKVPool
    (the elastic supervisor's rescale path)."""
    blocks: int = 64
    block_size: int = 8
    pool: PagedKVPool | None = None


@dataclasses.dataclass(frozen=True)
class ChunkConfig:
    """Long-context knobs (continuous/slo): chunked prefill and/or
    ring-attention routing. `prefill_chunk` admits a long prompt as fixed-
    size chunk programs with decode windows between them; `ring_min` routes
    monolithic prefills of at least that many tokens through
    `parallel.ring_attention` (needs an active multi-device mesh — consumed
    at model build via `ParallelContext.ring_prefill_min`, not here)."""
    prefill_chunk: int | None = None
    ring_min: int | None = None

    def __post_init__(self) -> None:
        if self.prefill_chunk is None and self.ring_min is None:
            raise ValueError(
                "ChunkConfig needs prefill_chunk and/or ring_min; an empty "
                "section would silently do nothing")
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        if self.ring_min is not None and self.ring_min < 1:
            raise ValueError(f"ring_min must be >= 1, got {self.ring_min}")


#: which schedules each section applies to — the loud-rejection table
_SECTION_SCHEDULES = {
    "slo": ("slo",),
    "spec": ("spec",),
    "prefix": ("continuous", "slo"),
    "chunk": ("continuous", "slo"),
}


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Typed serving configuration: base knobs every schedule shares, plus
    per-schedule sections. `validate()` rejects a section attached to a
    schedule it cannot apply to; `build_scheduler(config, ...)` is the one
    construction path `launch/serve.py` uses."""
    schedule: str
    max_len: int
    n_slots: int = 1
    sampling: str = "greedy"
    seed: int = 0
    buckets: tuple[int, ...] | None = None
    stream: ExecutionStream | None = None
    program_cache: ProgramCache | None = None
    target: hal.Target | None = None
    ctx: ParallelContext | None = None
    slo: SLOConfig | None = None
    spec: SpecConfig | None = None
    prefix: PrefixConfig | None = None
    chunk: ChunkConfig | None = None

    def validate(self) -> "ServeConfig":
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"schedule {self.schedule!r} not in {sorted(SCHEDULES)}")
        for name, applies in _SECTION_SCHEDULES.items():
            if getattr(self, name) is not None \
                    and self.schedule not in applies:
                raise ValueError(
                    f"ServeConfig.{name} does not apply to the "
                    f"{self.schedule!r} schedule (only {applies}); drop the "
                    f"section instead of expecting it to be ignored")
        if self.chunk is not None and self.prefix is not None \
                and self.chunk.prefill_chunk is not None \
                and self.chunk.prefill_chunk % self.prefix.block_size != 0:
            raise ValueError(
                f"prefix.block_size ({self.prefix.block_size}) must divide "
                f"chunk.prefill_chunk ({self.chunk.prefill_chunk}): chunk "
                f"targets are chunk multiples, and a chain only anchors "
                f"when whole blocks tile the prefilled prefix exactly")
        return self

    def scheduler_kwargs(self) -> dict:
        """Flatten to the scheduler constructors' keyword surface."""
        kw: dict[str, Any] = dict(
            sampling=self.sampling, seed=self.seed, buckets=self.buckets,
            stream=self.stream, program_cache=self.program_cache,
            target=self.target)
        if self.ctx is not None:
            kw["ctx"] = self.ctx
        if self.slo is not None:
            kw.update(slo_ms=self.slo.slo_ms,
                      max_in_flight=self.slo.max_in_flight)
        if self.spec is not None:
            sp = self.spec
            kw.update(draft_depth=sp.draft_depth, draft=sp.draft,
                      draft_ckpt=sp.draft_ckpt,
                      draft_branches=sp.draft_branches,
                      max_in_flight=sp.max_in_flight)
            if sp.drafter is not None:
                kw["drafter"] = sp.drafter
        if self.prefix is not None:
            pf = self.prefix
            kw.update(prefix_cache=True, prefix_blocks=pf.blocks,
                      prefix_block_size=pf.block_size)
            if pf.pool is not None:
                kw["prefix_pool"] = pf.pool
        if self.chunk is not None and self.chunk.prefill_chunk is not None:
            kw["prefill_chunk"] = self.chunk.prefill_chunk
        return kw


def build_scheduler(config: ServeConfig, model, params, cfg) -> _SchedulerBase:
    """Construct the scheduler a validated `ServeConfig` describes."""
    config.validate()
    kw = config.scheduler_kwargs()
    if config.schedule == "sequential":
        return SequentialSchedule(model, params, cfg,
                                  max_len=config.max_len, **kw)
    return SCHEDULES[config.schedule](model, params, cfg,
                                      n_slots=config.n_slots,
                                      max_len=config.max_len, **kw)


# -- legacy keyword path ----------------------------------------------------
#: every keyword the legacy `make_scheduler(**kw)` surface ever accepted,
#: by the section (or base) it folds into
_BASE_KW = ("sampling", "seed", "buckets", "stream", "program_cache",
            "target", "ctx")
_SLO_KW = ("slo_ms",)
_SPEC_KW = ("draft_depth", "draft", "drafter", "draft_ckpt",
            "draft_branches")
_PREFIX_KW = ("prefix_cache", "prefix_blocks", "prefix_block_size",
              "prefix_pool")
_CHUNK_KW = ("prefill_chunk",)


def make_scheduler(schedule: str, model, params, cfg, *, n_slots: int = 1,
                   max_len: int, **kw) -> _SchedulerBase:
    """Deprecated keyword shim over `ServeConfig` + `build_scheduler`.

    The old surface silently stripped schedule-inapplicable knobs (a typo'd
    or misplaced flag vanished without a trace). This shim keeps every
    historical call working but is loud: unknown keywords raise TypeError,
    knobs that do not apply to `schedule` warn before being dropped, and
    every call emits a DeprecationWarning pointing at ServeConfig."""
    warnings.warn(
        "make_scheduler(**kw) is deprecated: build a ServeConfig and call "
        "build_scheduler(config, model, params, cfg) instead",
        DeprecationWarning, stacklevel=2)
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule {schedule!r} not in {sorted(SCHEDULES)}")
    known = set(_BASE_KW) | set(_SLO_KW) | set(_SPEC_KW) | set(_PREFIX_KW) \
        | set(_CHUNK_KW) | {"max_in_flight"}
    unknown = sorted(set(kw) - known)
    if unknown:
        raise TypeError(
            f"make_scheduler got unknown keyword(s) {unknown}; known "
            f"keywords: {sorted(known)}")

    def strip(keys: tuple[str, ...], why: str) -> None:
        hit = [k for k in keys if kw.get(k) not in (None, False)]
        for k in keys:
            kw.pop(k, None)
        if hit:
            warnings.warn(
                f"make_scheduler: {hit} do(es) not apply to the "
                f"{schedule!r} schedule ({why}); dropped — ServeConfig "
                f"rejects this outright", UserWarning, stacklevel=3)

    if schedule != "slo":
        strip(_SLO_KW, "SLO admission gate is slo-only")
    if schedule != "spec":
        strip(_SPEC_KW, "drafter knobs are spec-only")
    if schedule not in ("continuous", "slo"):  # pool rides slot admission
        strip(_PREFIX_KW, "the prefix pool rides slot admission")
        strip(_CHUNK_KW, "chunked prefill rides slot admission")
    if schedule == "spec":
        strip(_CHUNK_KW, "spec admission stages target+drafter jointly")
    if schedule not in ("slo", "spec"):   # in-flight window is async-only
        strip(("max_in_flight",), "the in-flight window is async-only")

    sections: dict[str, Any] = {}
    if schedule == "slo":
        slo_kw = {}
        if "slo_ms" in kw:
            slo_kw["slo_ms"] = kw.pop("slo_ms")
        if "max_in_flight" in kw:
            slo_kw["max_in_flight"] = kw.pop("max_in_flight")
        if slo_kw:
            sections["slo"] = SLOConfig(**slo_kw)
    if schedule == "spec":
        spec_kw = {k: kw.pop(k) for k in
                   _SPEC_KW + ("max_in_flight",) if k in kw}
        if spec_kw:
            sections["spec"] = SpecConfig(**spec_kw)
    if kw.pop("prefix_cache", False) or kw.get("prefix_pool") is not None:
        sections["prefix"] = PrefixConfig(
            blocks=kw.pop("prefix_blocks", 64),
            block_size=kw.pop("prefix_block_size", 8),
            pool=kw.pop("prefix_pool", None))
    else:  # pool disabled: blocks/block_size had no effect before either
        for k in ("prefix_blocks", "prefix_block_size", "prefix_pool"):
            kw.pop(k, None)
    if kw.get("prefill_chunk") is not None:
        sections["chunk"] = ChunkConfig(prefill_chunk=kw.pop("prefill_chunk"))
    kw.pop("prefill_chunk", None)

    config = ServeConfig(schedule=schedule, max_len=max_len, n_slots=n_slots,
                         **kw, **sections)
    return build_scheduler(config, model, params, cfg)


# registers SCHEDULES["spec"]; the bottom import keeps the cycle harmless
# (this module is fully defined by the time speculative imports it back)
from repro.launch import speculative as _speculative  # noqa: E402,F401
