#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the serving path runs on a TPU.

    python chip_smoke.py                # one chip: phi4-mini-3.8b serving
    python chip_smoke.py --four-chips   # four chips: the EP mesh path

The automated check runs only the one-chip mode, with no arguments;
`--four-chips` is run by hand on a four-chip host.

One chip. Serves full-width `phi4-mini-3.8b` (32 layers, d_model 3072,
24/8 heads, vocab 200064, bf16, random weights from `--seed`) through
`repro.launch.serve.run` -> `build_scheduler`, under the continuous and the
slo schedules, greedy, 4 lanes of mixed prompt lengths, two identical
rounds. Checks:
  * every request returns `gen` tokens in [0, vocab);
  * every kernel route the dispatcher took is `pallas`, none `oracle`;
  * round 2 hits the ProgramCache and compiles nothing;
  * the greedy streams of the two schedules are identical;
  * for one prompt, the last-position prefill logits of the dispatched
    model match the undispatched model (`dispatcher=None`) run under
    `jax.default_matmul_precision("highest")` (tolerance below).

Four chips. `dbrx-132b` at published widths cut to 1 layer (every dbrx
layer is MoE, so one layer is a whole period) is served on a
("data", "model") = (1, 4) mesh with its expert banks over "model", and
compared with the same model on device 0, in the same process. Checks: the
EP route (`moe.ROUTE_COUNTS["ep"]`) ticks, and the prefill logits agree
(tolerances below).

Everything runs in this one process: a child started after JAX holds the
chip could not reach it. Weights come from the seed; nothing is read but
the repository's tracked files. Timings printed on the way are a smoke
readout, not a measurement. The last line of standard output is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}. A failed
check, or no TPU, exits nonzero without it.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Dispatched (Pallas kernels) vs undispatched (XLA dots, jnp attention)
# last-position prefill logits, as ||a - b||_2 / ||b||_2 over the vocab.
# Both run bf16 weights and activations with fp32 accumulation; they sum in
# different orders, so an fp32 partial that sits near a bf16 rounding
# boundary lands one bf16 ulp (2^-8 relative) apart, and 32 residual layers
# carry such flips to the logits. 5e-2 admits a few ulps of drift across
# the whole logit vector; a wrong kernel (mask, head mapping, block
# offsets, scale) moves the logits by O(1).
LOGITS_REL_TOL = 5e-2

# EP mesh vs device 0, max |a - b| over the prefill logits: the bound
# benchmarks/bench_sharded_serve.py holds the EP path to. It holds for both
# programs compiled with excess precision off (EXACT_COMPILE): XLA then
# rounds every bf16 value where the program says, so the mesh and device 0
# round at the same places, and EP with no drops computes what the dense
# path does.
EP_LOGITS_ABS_TOL = 1e-4
EXACT_COMPILE = {"xla_allow_excess_precision": False}
# The same pair compiled as served (XLA may keep fp32 across a bf16 cast
# inside a fusion, and the EP body fuses apart from the dense path), as
# ||a - b||_2 / ||b||_2 over every logit: one layer's worth of one-ulp bf16
# flips (2^-8 relative) stays below it (2.9e-3 on a TPU v5e), while one
# token routed to a wrong expert or dropped among the 128 moves it by about
# 128^-1/2.
EP_LOGITS_REL_TOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------


def tpu_device(want: int):
    """The TPU JAX runs on and its HAL target; anything else fails."""
    import jax

    from repro.core import hal
    from repro.kernels import compat

    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX runs on {dev.platform} ({dev.device_kind})")
    check(len(devs) >= want, f"want {want} chips, JAX sees {len(devs)}")
    target = hal.target_for_device_kind(dev.device_kind)
    check(compat.interpret_mode() is False,
          "Pallas would run in interpret mode on a TPU")
    return dev, target


def versions() -> str:
    import importlib.metadata as md

    import jax
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "absent"
    return f"jax {jax.__version__} jaxlib {md.version('jaxlib')} libtpu {libtpu}"


class CompileLog:
    """Backend compiles, stamped on the host clock (JAX monitoring)."""

    def __init__(self):
        self.stamps: list[float] = []

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.stamps.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.stamps)


# ---------------------------------------------------------------------------
# One chip: phi4-mini serving
# ---------------------------------------------------------------------------


def serve_phase(arch_args: list[str], schedule: str, *, target_name: str,
                prompt_lens: str, gen: int, seed: int, vocab: int,
                compiles: CompileLog) -> dict:
    """One `serve.run` call, checked. Returns its result dict."""
    from repro.launch import serve

    argv = [*arch_args, "--target", target_name, "--schedule", schedule,
            "--sampling", "greedy", "--batch", "4", "--prompt-lens",
            prompt_lens, "--gen", str(gen), "--requests", "2",
            "--seed", str(seed)]
    out = serve.run(argv)
    lanes = len(prompt_lens.split(","))
    check(len(out["results"]) == lanes and out["tokens"].shape == (lanes, gen),
          f"{schedule}: {len(out['results'])} results, tokens "
          f"{out['tokens'].shape}")
    for r in out["results"]:
        check(r.tokens.shape == (gen,), f"{schedule}: request {r.rid} "
              f"returned {r.tokens.shape[0]} tokens, want {gen}")
        check(bool(((r.tokens >= 0) & (r.tokens < vocab)).all()),
              f"{schedule}: request {r.rid} has tokens outside [0, {vocab})")
    oracle = {k: n for k, n in out["routes"].items() if k[1] != "pallas"}
    check(not oracle, f"{schedule}: routes off the Pallas path: {oracle}")
    r1, r2 = out["rounds"]
    check(r2["cache_misses"] == 0 and r2["cache_hits"] > 0,
          f"{schedule}: round 2 ProgramCache {r2}")
    n_compiles = compiles.between(r2["start_s"], r2["end_s"])
    check(n_compiles == 0,
          f"{schedule}: round 2 ran {n_compiles} backend compiles")
    print(f"[smoke, not a measurement] {schedule}: wall {out['wall_s']:.3f} s"
          f", compile {out['compile_s']:.3f} s, {out['tok_per_s']:.2f} tok/s"
          f" ex-compile; round 1 {r1['end_s'] - r1['start_s']:.3f} s "
          f"({r1['cache_misses']} programs compiled), round 2 "
          f"{r2['end_s'] - r2['start_s']:.3f} s (0 compiled); routes "
          f"{sorted(f'{k}:{b}' for k, b in out['routes'])}", flush=True)
    return out


def logits_phase(cfg, target, *, seed: int, prompt_len: int) -> float:
    """Dispatched vs undispatched (`dispatcher=None`, highest matmul
    precision) last-position prefill logits for one prompt, relative L2."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.dispatch import KernelDispatcher
    from repro.models.model import build_model

    model = build_model(cfg, dispatcher=KernelDispatcher(target))
    plain = build_model(cfg, dispatcher=None)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed + 1)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, size=(1, prompt_len)), jnp.int32)}
    # the served program as it runs; the reference's XLA dots at full fp32
    # precision (Mosaic refuses fp32 contract precision on bf16 operands,
    # so the dispatched trace stays at the default)
    _, got = jax.jit(model.prefill)(params, batch)
    with jax.default_matmul_precision("highest"):
        _, want = jax.jit(plain.prefill)(params, batch)
    got = np.asarray(got, np.float32)[0, -1, :cfg.vocab]
    want = np.asarray(want, np.float32)[0, -1, :cfg.vocab]
    check(bool(np.isfinite(got).all() and np.isfinite(want).all()),
          "non-finite prefill logits")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    print(f"prefill logits, dispatched vs dispatcher=None (highest "
          f"precision): rel L2 {rel:.3e} (tol {LOGITS_REL_TOL:.0e}), max abs "
          f"{float(np.abs(got - want).max()):.3e}, argmax "
          f"{int(got.argmax())} vs {int(want.argmax())}", flush=True)
    check(rel <= LOGITS_REL_TOL,
          f"prefill logits rel L2 {rel:.3e} > {LOGITS_REL_TOL:.0e}")
    return rel


def one_chip(arch: str, target, *, seed: int, prompt_lens: str, gen: int):
    import jax
    import numpy as np

    from repro import configs

    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    cfg = configs.get_config(arch)
    arch_args = ["--arch", arch]
    streams = {}
    for schedule in ("continuous", "slo"):
        out = serve_phase(arch_args, schedule, target_name=target.name,
                          prompt_lens=prompt_lens, gen=gen, seed=seed,
                          vocab=cfg.vocab, compiles=compiles)
        streams[schedule] = out["tokens"]
        del out
        gc.collect()
    check(np.array_equal(streams["continuous"], streams["slo"]),
          "greedy streams differ between the continuous and slo schedules")
    print("greedy streams identical across continuous and slo", flush=True)
    logits_phase(cfg, target, seed=seed,
                 prompt_len=int(prompt_lens.split(",")[0]))


# ---------------------------------------------------------------------------
# Four chips: dbrx EP mesh vs device 0
# ---------------------------------------------------------------------------


def four_chips(arch: str, target, *, seed: int, lanes: int = 4,
               prompt_len: int = 64, gen: int = 8):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.core.dispatch import ExecutionStream, KernelDispatcher
    from repro.launch.mesh import make_mesh
    from repro.launch.scheduler import Request, ServeConfig, build_scheduler
    from repro.models import moe
    from repro.models.model import build_model
    from repro.parallel.ctx import ParallelContext

    base = configs.get_config(arch)
    # one layer is a whole period (every dbrx layer is MoE); capacity factor
    # = the model-axis size, so every routed copy fits and EP drops nothing
    cfg = dataclasses.replace(base, n_layers=1, moe_capacity_factor=4.0)
    dispatcher = KernelDispatcher(target)
    ctx = ParallelContext(mesh=make_mesh((1, 4), ("data", "model"),
                                         devices=jax.devices()[:4]))
    meshed = build_model(cfg, ctx, dispatcher=dispatcher)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, size=(prompt_len,)).astype(np.int32)
               for _ in range(lanes)]
    batch = {"tokens": jnp.asarray(np.stack(prompts)[:, :32], jnp.int32)}

    moe.ROUTE_COUNTS["ep"] = 0
    sched = build_scheduler(
        ServeConfig(schedule="continuous", max_len=prompt_len + gen,
                    n_slots=lanes, sampling="greedy", seed=seed,
                    stream=ExecutionStream(target=target), ctx=ctx),
        meshed, meshed.init(jax.random.PRNGKey(seed)), cfg)
    gc.collect()            # the device-0 init copy: only the placed one stays
    t0 = time.perf_counter()
    results = sched.run([Request(rid=i, prompt=p, max_new_tokens=gen)
                         for i, p in enumerate(prompts)])
    wall = time.perf_counter() - t0
    for r in results:
        check(r.tokens.shape == (gen,) and bool(
            ((r.tokens >= 0) & (r.tokens < cfg.vocab)).all()),
            f"mesh request {r.rid}: tokens {r.tokens}")
    check(moe.ROUTE_COUNTS["ep"] > 0, "mesh serve never traced the EP path")
    print(f"[smoke, not a measurement] {cfg.name} 1 layer on a (1, 4) mesh: "
          f"{lanes} requests x {gen} tokens in {wall:.3f} s (compile "
          f"{sched.cache.stats.compile_seconds:.3f} s), EP traced "
          f"{moe.ROUTE_COUNTS['ep']}x", flush=True)
    def prefill_logits(model, params) -> dict:
        """Prefill logits compiled as served and with excess precision off."""
        return {name: np.asarray(jax.jit(model.prefill, compiler_options=opts)(
                    params, batch)[1], np.float32)
                for name, opts in (("served", None), ("exact", EXACT_COMPILE))}

    ep_before = moe.ROUTE_COUNTS["ep"]
    lg_mesh = prefill_logits(meshed, sched.params)
    check(moe.ROUTE_COUNTS["ep"] > ep_before, "mesh prefill skipped EP")
    del sched, results
    gc.collect()

    single = build_model(cfg, ParallelContext(mesh=None), dispatcher=dispatcher)
    lg_ref = prefill_logits(single, single.init(jax.random.PRNGKey(seed)))
    for name in ("served", "exact"):
        got, want = lg_mesh[name], lg_ref[name]
        check(bool(np.isfinite(got).all() and np.isfinite(want).all()),
              f"{name}: non-finite prefill logits")
        err = float(np.abs(got - want).max())
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        print(f"prefill logits ({name} compile), EP mesh vs device 0: max abs "
              f"{err:.3e}, rel L2 {rel:.3e}, max |logit| "
              f"{float(np.abs(want).max()):.3e}, argmax agrees at "
              f"{float((got.argmax(-1) == want.argmax(-1)).mean()):.4f} of "
              f"positions", flush=True)
        if name == "exact":
            check(err <= EP_LOGITS_ABS_TOL, f"EP prefill logits (exact compile)"
                  f" off by {err:.3e} > {EP_LOGITS_ABS_TOL:.0e}")
        else:
            check(rel <= EP_LOGITS_REL_TOL, f"EP prefill logits (served) rel "
                  f"L2 {rel:.3e} > {EP_LOGITS_REL_TOL:.0e}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the EP mesh path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    try:
        import jax

        from repro.core.dispatch import use_persistent_compile_cache
        dev, target = tpu_device(4 if args.four_chips else 1)
        cache_dir = use_persistent_compile_cache()
        print(f"device {dev.platform} {dev.device_kind!r} x{len(jax.devices())}"
              f" -> HAL target {target.name}; {versions()}; compile cache "
              f"{cache_dir}", flush=True)
        if args.four_chips:
            four_chips("dbrx-132b", target, seed=args.seed)
        else:
            one_chip("phi4-mini-3.8b", target, seed=args.seed,
                     prompt_lens="128,300,512,1000", gen=32)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    if peak is not None:
        print(f"device 0 peak bytes in use: {peak}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
