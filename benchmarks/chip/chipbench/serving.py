"""Drive the program under test: its normal serving path, open loop, timed.

The path is the one `repro.launch.serve.run` builds: `build_model(cfg,
dispatcher=KernelDispatcher(target))`, then `build_scheduler(ServeConfig(
...))`, then `.run(requests)`. The benchmark adds nothing inside it. It
observes the run from outside, through public surfaces only:

  * `scheduler.step_hook(sched, step)`, called at the top of every tick:
    stamps the tick, releases requests that have come due (a `Request`
    waits in the queue with an arrival step no tick reaches until the
    hook sets it to 0), notes which lane each request was admitted to and
    at which bucket (`scheduler.slots`), and ends the run at the close of
    the window by raising `WindowClosed`;
  * `scheduler.sampler`, wrapped: every generated token passes through it,
    so each token gets its host time;
  * the `ExecutionStream` the benchmark hands in: its `records` give every
    dispatch's batch and host times.

Nothing compiles inside the window: every prefill bucket the traffic can
reach is warmed up first, and a compile seen in the window fails the run.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from chipbench import BENCH_DIR, weights
from chipbench.check import load_reference
from chipbench.tracing import null_span

# a tick number no run reaches: a request waits with it until it is due
NEVER = 2 ** 62


class WindowClosed(Exception):
    """Raised from the step hook to end a run at the close of the window."""


@dataclasses.dataclass
class ReqObs:
    """What the host saw of one request."""

    rid: int
    prompt_len: int
    max_new: int
    due: float
    released: float | None = None
    admitted: float | None = None
    bucket: int | None = None
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return len(self.tokens) >= self.max_new


@dataclasses.dataclass
class Dispatch:
    key: str
    batch: int
    submit: float


@dataclasses.dataclass
class Window:
    """Observations of one measured run."""

    t0: float
    t_open: float
    t_close: float
    n_slots: int
    reqs: list
    ticks: list
    dispatches: list
    compiles_in_window: int
    compiles_in_fill: int
    seqs: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: float) -> bool:
        return self.t_open <= t < self.t_close


class CompileLog:
    """Backend compiles and persistent-cache loads, on the host clock."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.stamps: list[float] = []

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.stamps.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.stamps)


def program_params(model, module, m: dict, seed: int):
    """The benchmark's weights in the program's own parameter tree, made on
    the device in one jitted call: the reference ``module``'s
    ``program_leaf`` fills every leaf of the program's tree by its path,
    in the leaf's shape and dtype, or this fails naming the leaves it
    cannot fill."""
    import jax
    from repro.kernels import compat

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = compat.tree_flatten_with_path(shapes)
    paths = [compat.tree_path_str(p) for p, _ in flat]

    def build(key):
        leaves, unknown = [], []
        for path, (_, spec) in zip(paths, flat):
            try:
                w = module.program_leaf(key, m, path, spec.shape)
            except KeyError as e:
                unknown.append(f"{path} (KeyError {e})")
                continue
            if w.shape != spec.shape or w.dtype != spec.dtype:
                raise ValueError(f"{module.__name__} gives {path} as "
                                 f"{w.dtype}{list(w.shape)}; the program "
                                 f"holds {spec.dtype}{list(spec.shape)}")
            leaves.append(w)
        if unknown:
            raise ValueError(f"{module.__name__} has no weight for program "
                             f"parameter(s) {', '.join(unknown)}")
        return jax.tree.unflatten(treedef, leaves)

    # the key is an argument, not a constant: one program serves every seed
    return jax.jit(build)(weights.root_key(seed))


class Server:
    """The program, built once per process: model, weights, programs."""

    def __init__(self, conf: dict, seed: int, chips: int) -> None:
        if chips != 1:
            raise ValueError(f"the cell asks for {chips} chips; the harness "
                             f"builds no mesh and serves on one device")
        import jax
        from repro import configs
        from repro.core.dispatch import KernelDispatcher, ProgramCache
        from repro.launch.serve import device_target
        from repro.models.model import build_model

        self.conf = conf
        m = conf["model"]
        base = configs.get_config(conf["program_arch"])
        fields = {k: m[k] for k in m if hasattr(base, k)}
        self.mcfg = dataclasses.replace(base, **fields)
        self.target = device_target()
        self.model = build_model(self.mcfg,
                                 dispatcher=KernelDispatcher(self.target))
        self.program_cache = ProgramCache()
        self.params = program_params(
            self.model, load_reference(BENCH_DIR, conf["reference"]), m, seed)
        jax.block_until_ready(self.params)
        self.seed = seed
        self.sched = None
        self.stream = None

    def new_scheduler(self):
        from repro.core.dispatch import ExecutionStream
        from repro.launch.scheduler import ServeConfig, build_scheduler

        s = self.conf["serving"]
        self.sched = self.stream = None
        gc.collect()
        self.stream = ExecutionStream(self.program_cache, target=self.target)
        config = ServeConfig(schedule=s["schedule"], max_len=s["max_len"],
                             n_slots=s["n_slots"], sampling=s["sampling"],
                             seed=self.seed % 2 ** 31,
                             stream=self.stream)
        self.sched = build_scheduler(config, self.model, self.params,
                                     self.mcfg)
        return self.sched

    def warm_up(self, prompt_lens) -> list[int]:
        """Serve one short request per prefill bucket the prompt lengths
        reach (one token past the bucket, so catch-up runs too), which
        compiles or loads every program the window will dispatch."""
        from repro.launch.scheduler import Request, bucket_for

        sched = self.sched or self.new_scheduler()
        lens = sorted(set(int(x) for x in prompt_lens))
        lo, hi = lens[0], lens[-1]
        buckets = sorted({bucket_for(L, sched.buckets) for L in lens})
        warm = sorted({min(max(b + 1, lo), hi) for b in buckets})
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, prompt=rng.integers(0, self.mcfg.vocab, L),
                        max_new_tokens=2) for i, L in enumerate(warm)]
        sched.run(reqs)
        return warm

    def serve(self, specs, fill_s: float, seconds: float, tracer=None,
              compile_log: CompileLog | None = None) -> Window:
        """Serve ``specs`` open loop from now; measure ``seconds`` after
        ``fill_s``. Returns the observations; the run is cut at the close."""
        from repro.launch.scheduler import Request

        sched = self.sched or self.new_scheduler()
        stream = self.stream
        n0 = len(stream.records)
        t0 = time.perf_counter()
        t_open, t_close = t0 + fill_s, t0 + fill_s + seconds
        obs = [ReqObs(i, len(s.prompt), s.max_new, t0 + s.due_s)
               for i, s in enumerate(specs)]
        reqs = [Request(rid=i, prompt=s.prompt, max_new_tokens=s.max_new,
                        arrival=NEVER) for i, s in enumerate(specs)]
        state = {"next": 0, "done": 0, "open": False}
        ticks: list[float] = []

        inner = sched.sampler
        span = tracer.span if tracer is not None else null_span

        def sampler(row, rid, position):
            with span("bench.sample"):
                tok = inner(row, rid, position)
            o = obs[rid]
            o.times.append(time.perf_counter())
            o.tokens.append(int(tok))
            if len(o.tokens) == o.max_new:
                state["done"] += 1
            return tok

        def release(now: float) -> None:
            i = state["next"]
            while i < len(reqs) and obs[i].due <= now:
                reqs[i].arrival = 0
                obs[i].released = now
                i += 1
            state["next"] = i

        def hook(s, step):
            now = time.perf_counter()
            if ticks:
                for slot in s.slots:
                    r = slot.req
                    if r is not None and obs[r.rid].admitted is None:
                        obs[r.rid].admitted = ticks[-1]
                        obs[r.rid].bucket = int(slot.bucket)
            release(now)
            idle = state["next"] - state["done"] == 0
            if idle and state["next"] < len(reqs):
                wake = min(obs[state["next"]].due, t_close)
                if wake > now:
                    time.sleep(wake - now)
                    now = time.perf_counter()
                    release(now)
            if not state["open"] and now >= t_open:
                state["open"] = True
                if tracer is not None:
                    tracer.open_window()
            if now >= t_close:
                if tracer is not None:
                    tracer.close_window()
                raise WindowClosed
            ticks.append(now)
            if tracer is not None:
                tracer.tick()

        sched.sampler = sampler
        sched.step_hook = hook
        if tracer is not None:
            tracer.wrap(stream)
        try:
            sched.run(reqs)
            raise RuntimeError("the traffic ran out before the window closed")
        except WindowClosed:
            pass
        finally:
            sched.sampler = inner
            sched.step_hook = None
            if tracer is not None:
                tracer.unwrap(stream)
        recs = [Dispatch(r.key, r.batch, r.submit_ts)
                for r in stream.records[n0:]]
        cl = compile_log or CompileLog()
        win = Window(t0=t0, t_open=t_open, t_close=t_close,
                     n_slots=self.conf["serving"]["n_slots"], reqs=obs,
                     ticks=ticks, dispatches=recs,
                     compiles_in_window=cl.between(t_open, t_close),
                     compiles_in_fill=cl.between(t0, t_open))
        win.seqs = {o.rid: np.concatenate(
            [specs[o.rid].prompt, np.asarray(o.tokens, np.int32)])
            for o in obs if o.tokens}
        # the aborted run leaves its lanes mid-request: the next run needs a
        # scheduler of its own
        self.sched = self.stream = None
        return win

    def free(self) -> None:
        """Drop the program's device state (weights, caches)."""
        self.sched = self.stream = None
        self.params = None
        gc.collect()
