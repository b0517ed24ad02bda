"""Serving driver: a thin CLI over the continuous-batching scheduler.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --batch 4 --prompt-len 64 --gen 32 --weight-form int4_palette \
        --schedule continuous --sampling greedy

The paper's serving shape (ch. 2/5/14), end to end:

  * **op-by-device routing** — the model is built with a
    `KernelDispatcher` for the configured HAL target, so every projection,
    MLP, MoE expert, attention and logits matmul resolves against the
    kernel registry: `anemm` for dense weights, `palette`/`sparse` for
    packed ones (`--weight-form`), with oracle fallback wherever the target
    gates the op/form/dtype (`--target ane-m1` exercises it live).
  * **compile once, dispatch many** — prefill and decode compile through
    the content-hash `ProgramCache`; prompt-length bucketing bounds the
    prefill shape set, so a stream of heterogeneous requests warm-starts
    from at most `#buckets` prefill programs + 1 decode program (the
    anehash warm start, §5.6).
  * **resident state** — the shared multi-lane KV cache is a donated
    argument of the decode program: the held buffer never re-crosses the
    host between steps, and admission writes new requests into free lanes
    in place.
  * **dispatch-floor amortization** — every model dispatch goes through an
    `ExecutionStream` whose records charge the costmodel floor estimate
    per call; `--schedule continuous` shares each decode dispatch across
    all active lanes (§9.4: batching to 512 drops per-sample cost ~127x),
    while `--schedule sequential` is the un-amortized one-request-at-a-time
    parity reference.
  * **overlapped streams** — `--schedule slo` pipelines decode on an
    `AsyncExecutionStream` (encode step N+1 while step N executes, on-device
    sampling, bounded `--max-in-flight` window) and gates admission on the
    costmodel-predicted token latency against `--slo-ms` (the paper's
    unfinished overlapping-streams path, §2.4).
  * **chunked prefill** — `--prefill-chunk C` admits a long prompt as a
    sequence of fixed-size chunk programs (one ProgramCache entry per chunk
    size) written incrementally into the lane's cache, with decode windows
    between chunks: the SLO admission gate schedules each chunk like any
    other dispatch, so in-flight decodes never stall behind one monolithic
    prefill — and greedy token streams stay bit-identical to unchunked.
    `--ring-prefill-min N` (mesh only) routes monolithic prefills of >= N
    tokens through ring attention over the "model" axis — the
    context-parallel path for prompts beyond one device's cache slab.
  * **speculative decoding** — `--schedule spec` serves draft->verify
    windows on the async stream: a drafter (`--draft shrink` depth-pruned
    second model, optionally loaded from a `launch.distill` checkpoint via
    `--draft-ckpt` / `--draft self` the target itself) proposes
    `--draft-depth` tokens — or `--draft-branches` sibling chains of them
    (tree verification) — in one dispatch, and one fused verify dispatch
    resamples them on device through the `specdec` kernel — two dispatch
    floors buy up to depth+1 tokens (§9 economics), token-exact against
    the sequential reference.

  * **multi-host mesh serving** — `--mesh-shape 4x2` runs the same
    scheduler under a device mesh: lanes (the decode batch dim) shard over
    the "data" axis, packed MoE expert banks shard over the "model" axis
    (the EP `shard_map` path), and the token streams stay bit-identical to
    the single-device run. `--evacuate-on-failure` (with `--fail-host N`
    to inject a loss) wraps the loop in the `ServeSupervisor`: heartbeats
    every tick, and on host loss the mesh shrinks to the survivors and the
    lost lanes re-admit token-exact.

All scheduling logic lives in `repro.launch.scheduler`; this module only
parses arguments, builds the model/requests, and reports.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from collections import Counter

import jax
import numpy as np

from repro import configs
from repro.core import hal
from repro.core.dispatch import (AsyncExecutionStream, ExecutionStream,
                                 KernelDispatcher, ProgramCache,
                                 use_persistent_compile_cache)
from repro.launch.mesh import make_mesh
from repro.launch.scheduler import (SAMPLING_MODES, SCHEDULES, ChunkConfig,
                                    PrefixConfig, Request, ServeConfig,
                                    SLOConfig, SpecConfig, build_scheduler,
                                    merge_prefill_caches)
from repro.launch.speculative import DRAFT_KINDS
from repro.models.model import build_model
from repro.optim.compression import compress_model_params
from repro.parallel.ctx import ParallelContext
from repro.runtime.supervisor import FailureInjection, ServeSupervisor

WEIGHT_FORMS = ("fp16", "int4_palette", "sparse")

_MESH_NAMES = {2: ("data", "model"), 3: ("pod", "data", "model")}


def parse_mesh(spec: str) -> ParallelContext:
    """'4x2' -> a ("data","model") mesh context; '' -> the null context.

    Two dims shard lanes over "data" and MoE expert banks over "model";
    three dims add a leading "pod" axis that also carries lanes (the
    cache/batch rules shard dim 0 over ("pod","data") jointly)."""
    if not spec:
        return ParallelContext(mesh=None)
    dims = tuple(int(x) for x in spec.lower().split("x"))
    names = _MESH_NAMES.get(len(dims))
    if names is None or any(d < 1 for d in dims):
        raise ValueError(f"--mesh-shape {spec!r}: want 2 or 3 positive "
                         "'x'-separated dims, e.g. 4x2 or 2x2x2")
    need = int(np.prod(dims))
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"--mesh-shape {spec} wants {need} devices, {have} visible "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            "fakes them on CPU)")
    return ParallelContext(mesh=make_mesh(dims, names))


def device_target() -> hal.Target:
    """The HAL target of the device JAX runs on: a TPU by its `device_kind`
    (a kind `hal.DEVICE_KIND_TARGETS` does not hold is an error), any other
    backend as the v5e that its interpret-mode kernels emulate."""
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return hal.target_for_device_kind(dev.device_kind)
    return hal.TPU_V5E


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=configs.ARCH_NAMES + ["ane-paper"])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode lanes (continuous) / requests per round")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--prompt-lens", default="",
                    help="comma-separated per-request prompt lengths "
                         "(heterogeneous round; overrides --prompt-len)")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--schedule", default="continuous",
                    choices=sorted(SCHEDULES),
                    help="continuous = slot-masked batched decode with "
                         "mid-flight admission; slo = overlapped decode "
                         "(async stream) with SLO-aware admission; "
                         "spec = speculative draft->verify windows on the "
                         "async stream (--draft-depth proposals per window, "
                         "fused on-device verify/accept); sequential = one "
                         "request at a time (parity reference)")
    ap.add_argument("--draft-depth", type=int, default=4,
                    help="spec schedule only: drafter proposals per window "
                         "(each window pays two dispatch floors for up to "
                         "draft-depth + 1 emitted tokens)")
    ap.add_argument("--draft", default="shrink", choices=DRAFT_KINDS,
                    help="spec schedule only: 'shrink' builds a depth-pruned "
                         "draft model from the target config (the real "
                         "two-model path; random-init unless --draft-ckpt "
                         "serves distilled weights), 'self' drafts with the "
                         "target itself (the agreement ceiling)")
    ap.add_argument("--draft-ckpt", default="",
                    help="spec schedule only: a `launch.distill` checkpoint "
                         "directory (the student/ subdir) with distilled "
                         "shrink-drafter weights; vocab/width mismatches "
                         "are rejected loudly at load")
    ap.add_argument("--draft-branches", type=int, default=1,
                    help="spec schedule only: sibling draft chains per lane "
                         "(tree verification; branch at the window root on "
                         "the drafter's top-N, one verify dispatch scores "
                         "the whole tree)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="slo schedule only: admit a queued request only "
                         "while the costmodel-predicted token latency stays "
                         "under this many milliseconds (default: no limit)")
    ap.add_argument("--max-in-flight", type=int, default=8,
                    help="slo schedule only: bounded in-flight submission "
                         "window of the async stream")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="continuous/slo schedules: route admissions through "
                         "the block-paged KV pool — a request whose prompt "
                         "prefix is resident (shared system prompt, repeated "
                         "round) admits with one gather dispatch instead of "
                         "re-prefilling the matched blocks")
    ap.add_argument("--prefix-blocks", type=int, default=64,
                    help="prefix cache only: arena capacity in blocks")
    ap.add_argument("--prefix-block-size", type=int, default=8,
                    help="prefix cache only: tokens per block (should divide "
                         "the prefill buckets, or chains never anchor)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="continuous/slo schedules: admit a long prompt as "
                         "fixed-size chunk programs (one ProgramCache entry "
                         "per chunk size) written incrementally into the "
                         "lane, with decode windows between chunks — the "
                         "SLO gate schedules each chunk like any dispatch "
                         "instead of stalling behind a monolithic prefill")
    ap.add_argument("--ring-prefill-min", type=int, default=None,
                    help="with --mesh-shape only: route monolithic prefills "
                         "of at least this many tokens through ring "
                         "attention (context-parallel over the 'model' "
                         "axis); default off — keeps mesh streams "
                         "bit-identical to single-device")
    ap.add_argument("--ckpt", default="",
                    help="load target params from this CheckpointManager "
                         "directory (e.g. a `launch.distill` run's teacher/ "
                         "subdir, so a --draft-ckpt student speculates for "
                         "the teacher it was distilled against) instead of "
                         "random init")
    ap.add_argument("--sampling", default="greedy", choices=SAMPLING_MODES,
                    help="greedy argmax or seeded categorical sampling")
    ap.add_argument("--weight-form", default="fp16", choices=WEIGHT_FORMS,
                    help="pack matmul weights into this streamed form")
    ap.add_argument("--target", default=None,
                    choices=sorted(hal.TARGETS),
                    help="HAL target whose capability surface gates routing "
                         "(also sets the costmodel dispatch floor); default: "
                         "the chip JAX runs on, by its device kind (off the "
                         "chip, the v5e the interpret-mode kernels emulate)")
    ap.add_argument("--no-dispatch", action="store_true",
                    help="bypass the dispatcher (seed dense path; "
                         "incompatible with a packed --weight-form)")
    ap.add_argument("--requests", type=int, default=1,
                    help="identical request rounds; round 2+ must hit the "
                         "program cache")
    ap.add_argument("--mesh-shape", default="",
                    help="serve on a device mesh, e.g. '4x2' = lanes over a "
                         "4-way 'data' axis x MoE expert banks over a 2-way "
                         "'model' axis (3 dims: pod x data x model); token "
                         "streams stay bit-identical to the null mesh")
    ap.add_argument("--evacuate-on-failure", action="store_true",
                    help="continuous/slo: wrap the scheduler in the "
                         "ServeSupervisor — heartbeat every tick, watchdog "
                         "on hangs, and on host loss shrink the mesh to the "
                         "survivors and re-admit the lost host's lanes "
                         "token-exact")
    ap.add_argument("--fail-host", type=int, default=-1,
                    help="inject a failure of this host (batch-axis rank) "
                         "mid-stream to exercise evacuation; -1 = none "
                         "(implies --evacuate-on-failure)")
    ap.add_argument("--fail-at-step", type=int, default=3,
                    help="scheduler tick the injected failure fires at")
    ap.add_argument("--fail-kind", default="vanish",
                    choices=("vanish", "hang"),
                    help="vanish = host stops heartbeating; hang = one tick "
                         "stalls past the watchdog deadline")
    args = ap.parse_args(argv)

    if args.no_dispatch and args.weight_form != "fp16":
        ap.error("packed weight forms require the dispatcher")
    use_supervisor = args.evacuate_on_failure or args.fail_host >= 0
    if use_supervisor and args.schedule not in ("continuous", "slo"):
        ap.error(f"--evacuate-on-failure serves --schedule continuous or "
                 f"slo, not {args.schedule}")
    try:
        ctx = parse_mesh(args.mesh_shape)
    except ValueError as e:
        ap.error(str(e))
    if args.ring_prefill_min is not None:
        if not ctx.active or ctx.axis_size("model") <= 1:
            ap.error("--ring-prefill-min needs --mesh-shape with a >1 "
                     "'model' axis: the ring rotates KV over that axis")
        # consumed at model build: attention's prefill branch reads it off
        # the context, so the route is baked into the compiled program
        ctx = dataclasses.replace(ctx,
                                  ring_prefill_min=args.ring_prefill_min)

    use_persistent_compile_cache()
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    target = hal.get_target(args.target) if args.target else device_target()
    dispatcher = None if args.no_dispatch else KernelDispatcher(target)
    model = build_model(cfg, ctx, dispatcher=dispatcher)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.ckpt:
        from repro.checkpoint.checkpoint import CheckpointManager
        template = jax.eval_shape(model.init, jax.random.PRNGKey(args.seed))
        params, step = CheckpointManager(args.ckpt).restore(template)
        params = jax.tree.map(jax.numpy.asarray, params)
        print(f"loaded target params from {args.ckpt} (step {step})")
    if args.weight_form != "fp16":
        params = compress_model_params(params, args.weight_form)

    # one round's requests, identical across rounds (warm-start discipline)
    if args.prompt_lens:
        lens = [int(x) for x in args.prompt_lens.split(",")]
    else:
        lens = [args.prompt_len] * args.batch
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab, size=(L,)).astype(np.int32)
               for L in lens]
    frames = None
    if cfg.family == "encdec":
        frames = [np.asarray(rng.normal(size=cfg.frame_shape),
                             np.float32) for _ in lens]
    max_len = max(lens) + args.gen

    program_cache = ProgramCache()
    if args.schedule in ("slo", "spec"):
        stream = AsyncExecutionStream(program_cache, target=target,
                                      max_in_flight=args.max_in_flight)
    else:
        stream = ExecutionStream(program_cache, target=target)

    # typed serve configuration: each schedule-specific knob group is a
    # section, and ServeConfig.validate() rejects a section the chosen
    # schedule cannot apply — a misplaced flag fails here, loudly, instead
    # of vanishing into a silently-stripped kwarg
    slo_cfg = SLOConfig(slo_ms=args.slo_ms,
                        max_in_flight=args.max_in_flight) \
        if args.schedule == "slo" else None
    spec_cfg = SpecConfig(draft_depth=args.draft_depth, draft=args.draft,
                          draft_ckpt=args.draft_ckpt or None,
                          draft_branches=args.draft_branches,
                          max_in_flight=args.max_in_flight) \
        if args.schedule == "spec" else None
    prefix_cfg = PrefixConfig(blocks=args.prefix_blocks,
                              block_size=args.prefix_block_size) \
        if args.prefix_cache else None
    chunk_cfg = ChunkConfig(prefill_chunk=args.prefill_chunk,
                            ring_min=args.ring_prefill_min) \
        if (args.prefill_chunk is not None
            or args.ring_prefill_min is not None) else None

    def make_sched(sctx, pool):
        # the supervisor rebuilds the scheduler on the shrunken mesh after
        # an evacuation; the stream (floor ledger) and program cache carry
        # across, the paged pool rides in via prefix.pool. The model's
        # internal sharding constraints are baked against its build mesh,
        # so a rescaled context needs a rebuilt model closure (params are
        # mesh-independent and re-place through the scheduler).
        m = model if sctx is ctx else build_model(cfg, sctx,
                                                  dispatcher=dispatcher)
        pfx = prefix_cfg
        if pool is not None:
            pfx = dataclasses.replace(prefix_cfg or PrefixConfig(),
                                      pool=pool)
        config = ServeConfig(schedule=args.schedule, max_len=max_len,
                             n_slots=args.batch, sampling=args.sampling,
                             seed=args.seed, stream=stream, ctx=sctx,
                             slo=slo_cfg, spec=spec_cfg, prefix=pfx,
                             chunk=chunk_cfg)
        try:
            return build_scheduler(config, m, params, cfg)
        except ValueError as e:
            ap.error(str(e))

    supervisor = None
    if use_supervisor:
        injection = None
        if args.fail_host >= 0:
            injection = FailureInjection(host=args.fail_host,
                                         at_step=args.fail_at_step,
                                         kind=args.fail_kind)
        supervisor = ServeSupervisor(make_sched, ctx, injection=injection)
        engine = supervisor
    else:
        engine = make_sched(ctx, None)

    results, rounds = [], []
    t0 = time.perf_counter()
    for r in range(max(args.requests, 1)):
        reqs = [Request(rid=r * len(lens) + i, prompt=prompts[i],
                        max_new_tokens=args.gen,
                        frames=None if frames is None else frames[i])
                for i in range(len(lens))]
        before = dataclasses.replace(program_cache.stats)
        t_round = time.perf_counter()
        results = supervisor.serve(reqs) if supervisor is not None \
            else engine.run(reqs)
        after = program_cache.stats
        rounds.append({
            "start_s": t_round, "end_s": time.perf_counter(),
            "cache_hits": after.hits - before.hits,
            "cache_misses": after.misses - before.misses,
            "compile_s": after.compile_seconds - before.compile_seconds})
    wall = time.perf_counter() - t0

    n_requests = len(lens) * max(args.requests, 1)
    total_tokens = args.gen * n_requests
    stats = engine.stats(n_requests)
    # serving throughput excludes AOT compilation (the ProgramCache tracks
    # its own compile seconds); a cold first round is compile-dominated
    serve_wall = max(wall - program_cache.stats.compile_seconds, 1e-9)
    out = {
        "tokens": np.stack([r.tokens for r in results]),
        "schedule": args.schedule,
        "sampling": args.sampling,
        "wall_s": wall,
        "compile_s": program_cache.stats.compile_seconds,
        "tok_per_s": total_tokens / serve_wall,
        "cache_hits": program_cache.stats.hits,
        "cache_misses": program_cache.stats.misses,
        "rounds": rounds,
        "results": results,
        **stats,
    }
    if dispatcher is not None:
        out["routes"] = dict(Counter(
            (r.kernel, r.backend) for r in dispatcher.routes))
    prefix_note = ""
    if args.prefix_cache:
        pc = stats["prefix_cache"]
        prefix_note = (f" | prefix cache: {pc['hits']} hits / "
                       f"{pc['misses']} misses, {pc['hit_tokens']} prefill "
                       f"tokens skipped, {pc['evictions']} evictions")
    chunk_note = ""
    if args.prefill_chunk is not None:
        cp = stats["chunked_prefill"]
        chunk_note = (f" | chunked prefill C={cp['prefill_chunk']}: "
                      f"{cp['n_chunks']} chunks / {cp['chunk_tokens']} "
                      f"prompt tokens")
    mesh_note = ""
    if ctx.active:
        mesh_note = (f" | mesh {args.mesh_shape}: {stats['n_hosts']} hosts, "
                     f"fleet floor {stats['fleet_floor_s']*1e3:.2f} ms")
    if supervisor is not None:
        mesh_note += (f" | supervisor: {stats['restarts']} restarts, "
                      f"{len(stats['rescales'])} rescales, evacuated lanes "
                      f"{stats['evacuated_rids']}")
    slo_note = ""
    if args.schedule == "slo":
        slo_note = (f" | in-flight<= {stats['max_in_flight']}, "
                    f"{stats['deferred_admissions']} deferred admissions, "
                    f"pred p99 token "
                    f"{stats['predicted_token_latency_s']*1e3:.2f} ms")
    elif args.schedule == "spec":
        trained = "distilled" if stats.get("drafter_trained") else "random"
        slo_note = (f" | {args.draft} ({trained}) drafter depth "
                    f"{args.draft_depth} x{stats['draft_branches']} "
                    f"branches: {stats['n_windows']} windows, acceptance "
                    f"{stats['acceptance_rate']:.2f}, "
                    f"{stats['tokens_per_window_dispatch']:.2f} "
                    f"tok/window-dispatch")
    c = stats["counters"]
    work = c["lane_steps_generated"] + c["lane_steps_catchup"]
    lane_note = ""
    if work:
        lane_note = (f" | {c['decode_ticks']} decode ticks, lane occupancy "
                     f"{100 * work / (work + c['lane_steps_empty']):.1f}%, "
                     f"catch-up {100 * c['lane_steps_catchup'] / work:.1f}%")
    print(f"{args.schedule} x {args.sampling}: {n_requests} requests "
          f"(lens {lens}) gen {args.gen}: {wall*1e3:.1f} ms "
          f"({serve_wall*1e3:.1f} ms ex-compile, {out['tok_per_s']:.1f} "
          f"tok/s) | {stats['n_dispatches']} "
          f"dispatches, modeled floor/request "
          f"{stats['per_request_dispatch_overhead_s']*1e6:.1f} us | "
          f"program cache h{program_cache.stats.hits}/"
          f"m{program_cache.stats.misses}{lane_note}{mesh_note}{prefix_note}"
          f"{chunk_note}{slo_note}")
    return out


def _merge_prefill(model, caches, pf_caches, prompt_len: int):
    """Copy prefill cache contents into the (larger) decode buffers.

    Kept for callers of the historical serve-loop helper; the merge itself
    is `scheduler.merge_prefill_caches` — by named time axis, raising with
    the tree path on any rank/axis mismatch instead of silently returning
    the empty decode buffer."""
    del model, prompt_len                  # merge is shape-driven per leaf
    return merge_prefill_caches(caches, pf_caches)


if __name__ == "__main__":
    run()
