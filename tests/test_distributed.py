"""Multi-device tests: run in subprocesses with 8 forced host devices.

Covers: EP MoE vs dense-reference parity, sharded train step, GPipe pipeline
parity, compressed all-reduce, elastic restore onto a different mesh."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run8(body: str, timeout=600) -> str:
    script = ("import os\n"
              "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n"
              + textwrap.dedent(body))
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=timeout,
                       env={**os.environ, "PYTHONPATH": SRC})
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


@pytest.mark.slow
def test_moe_ep_matches_dense_reference():
    out = run8("""
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro import configs
        from repro.models import moe as moe_lib
        from repro.parallel.ctx import ParallelContext
        import dataclasses

        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = ParallelContext(mesh=mesh)
        cfg = dataclasses.replace(configs.get_smoke("dbrx-132b"),
                                  moe_capacity_factor=8.0)   # no drops
        p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(4, 16, cfg.d_model)), jnp.float32)

        dense, aux_d = moe_lib.moe_dense(cfg, p, x)
        ep_fn = jax.jit(lambda p, x: moe_lib.moe_ep(cfg, p, x, ctx))
        ep, aux_e = ep_fn(p, x)
        np.testing.assert_allclose(np.asarray(ep), np.asarray(dense),
                                   rtol=2e-4, atol=2e-4)
        # aux: EP averages the per-rank balance loss over token slices, the
        # dense path computes it globally — same signal, small relative gap
        assert abs(float(aux_d) - float(aux_e)) / max(float(aux_d), 1e-6) < 0.3
        print("EP==DENSE OK")
    """)
    assert "EP==DENSE OK" in out


@pytest.mark.slow
def test_moe_ep_capacity_drops_are_bounded():
    out = run8("""
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro import configs
        from repro.models import moe as moe_lib
        from repro.parallel.ctx import ParallelContext
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = ParallelContext(mesh=mesh)
        cfg = dataclasses.replace(configs.get_smoke("dbrx-132b"),
                                  moe_capacity_factor=1.0)
        p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(4, 16, cfg.d_model)), jnp.float32)
        dense, _ = moe_lib.moe_dense(cfg, p, x)
        ep, _ = jax.jit(lambda p, x: moe_lib.moe_ep(cfg, p, x, ctx))(p, x)
        # with capacity 1.0 some copies drop; outputs stay close in norm
        rel = float(jnp.linalg.norm(ep - dense) / jnp.linalg.norm(dense))
        assert rel < 0.5, rel
        print("EP-drops OK", rel)
    """)
    assert "EP-drops OK" in out


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_single_device():
    out = run8("""
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.models.model import build_model
        from repro.optim import adamw
        from repro.parallel.ctx import ParallelContext
        from repro.parallel import sharding as shard_lib
        from repro.launch.train import make_train_step

        cfg = configs.get_smoke("tinyllama-1.1b")
        batch = {"tokens": jnp.ones((8, 32), jnp.int32),
                 "targets": jnp.ones((8, 32), jnp.int32)}
        opt_cfg = adamw.AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10)

        def run(ctx):
            m = build_model(cfg, ctx)
            params = m.init(jax.random.PRNGKey(0))
            opt = adamw.init_state(opt_cfg, params)
            step = make_train_step(m, opt_cfg)
            if ctx.active:
                ps = shard_lib.param_specs(params, ctx)
                os_ = shard_lib.opt_state_specs(opt, ps, ctx)
                bs = shard_lib.batch_specs(batch, ctx)
                fn = jax.jit(step, in_shardings=(
                    jax.tree.map(lambda s: jax.sharding.NamedSharding(ctx.mesh, s), ps),
                    jax.tree.map(lambda s: jax.sharding.NamedSharding(ctx.mesh, s), os_),
                    jax.tree.map(lambda s: jax.sharding.NamedSharding(ctx.mesh, s), bs)))
            else:
                fn = jax.jit(step)
            p2, o2, metrics = fn(params, opt, batch)
            return float(metrics["loss"]), p2

        mesh = make_mesh((2, 4), ("data", "model"))
        loss_sharded, p_sh = run(ParallelContext(mesh=mesh))
        loss_single, p_si = run(ParallelContext(mesh=None))
        assert abs(loss_sharded - loss_single) < 2e-2, (loss_sharded, loss_single)
        # updated params agree across the two executions
        for a, b in zip(jax.tree.leaves(p_sh), jax.tree.leaves(p_si)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-2, atol=2e-2)
        print("SHARDED==SINGLE OK", loss_sharded)
    """)
    assert "SHARDED==SINGLE OK" in out


def test_gpipe_matches_sequential():
    out = run8("""
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import gpipe, split_stages, bubble_fraction
        mesh = make_mesh((4,), ("stage",))
        L, S, M, mb, d = 8, 4, 6, 2, 16
        key = jax.random.PRNGKey(0)
        Ws = jax.random.normal(key, (L, d, d)) * 0.3

        def layer(w, x):
            return jnp.tanh(x @ w)

        def stage_fn(slab, x):            # slab: (L/S, d, d)
            def body(x, w):
                return layer(w, x), None
            x, _ = jax.lax.scan(body, x, slab)
            return x

        xs = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
        pp = gpipe(stage_fn, mesh)
        got = jax.jit(pp)(split_stages(Ws, S), xs)

        ref = xs
        for i in range(L):
            ref = jax.vmap(lambda x: layer(Ws[i], x))(ref)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        assert abs(bubble_fraction(S, M) - 3/9) < 1e-9
        print("GPIPE OK")
    """)
    assert "GPIPE OK" in out


def test_compressed_psum_close_to_exact():
    out = run8("""
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compressed_psum
        mesh = make_mesh((8,), ("data",))
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 1024)),
                        jnp.float32)
        exact = shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                          in_specs=P("data"), out_specs=P("data"))(x)
        comp = shard_map(lambda v: compressed_psum(v, "data"), mesh=mesh,
                         in_specs=P("data"), out_specs=P("data"))(x)
        rel = float(jnp.linalg.norm(comp - exact) / jnp.linalg.norm(exact))
        assert rel < 0.02, rel
        print("COMPRESSED_PSUM OK", rel)
    """)
    assert "COMPRESSED_PSUM OK" in out


def test_elastic_restore_onto_new_mesh():
    out = run8("""
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.checkpoint.checkpoint import CheckpointManager
        from repro.runtime.elastic import plan_rescale, build_mesh, make_placer
        from jax.sharding import PartitionSpec as P

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            tree = {"w": jnp.arange(64.0).reshape(8, 8)}
            mgr.save(1, tree)
            plan = plan_rescale(8, 4, model_parallel=2)
            assert plan.new_mesh_shape == (2, 2)
            mesh = make_mesh((2, 2), ("data", "model"),
                                 devices=jax.devices()[:4])
            placer = make_placer(mesh, lambda path, shape: P(None, "model"))
            restored, step = mgr.restore(tree, placer=placer)
            assert step == 1
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.arange(64.0).reshape(8, 8))
            assert len(restored["w"].sharding.device_set) == 4
            print("ELASTIC OK")
    """)
    assert "ELASTIC OK" in out


def test_ring_attention_matches_reference():
    out = run8("""
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.ring_attention import ring_attention
        from repro.models.attention import chunked_attention
        mesh = make_mesh((8,), ("model",))
        rng = np.random.default_rng(0)
        b, s, h, kvh, d = 2, 64, 4, 2, 32
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, s, kvh, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, s, kvh, d)), jnp.float32)
        got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh))(q, k, v)
        ref = chunked_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)
        # non-causal too
        got2 = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh,
                                                      causal=False))(q, k, v)
        ref2 = chunked_attention(q, k, v, causal=False, q_chunk=16, kv_chunk=16)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(ref2),
                                   rtol=2e-3, atol=2e-3)
        print("RING OK")
    """)
    assert "RING OK" in out
