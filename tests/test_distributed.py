"""Distributed integration tests — run in a subprocess with 8 host devices
(XLA device count is locked at first jax init, so these cannot share the
main pytest process)."""

import os
import subprocess
import sys
import textwrap

import pytest

# each test spawns a fresh 8-device XLA process (~15-25s): the slowest
# parity sweeps in the repo — excluded from `make test-fast`, always part
# of the full `make test` tier-1 run
pytestmark = pytest.mark.slow

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_py(body: str, timeout: int = 420) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout,
                       env={**os.environ,
                            "PYTHONPATH": os.path.join(ROOT, "src")})
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    out = run_py("""
        from repro.configs import get_config, reduced
        from repro.models import transformer as T
        from repro.parallel.sharding import make_context, mesh_view
        from repro.launch.mesh import make_smoke_mesh
        from repro.launch.dryrun import sharded_param_specs
        from repro.train.optimizer import OptimizerConfig, adamw_init
        from repro.train.train_step import make_train_step
        from repro.configs.base import RunConfig

        # fp32 compute: under bf16, reduction-order differences flip the
        # sign of near-zero grads, and Adam's step-1 update is ±lr per
        # element — a distracting (expected) artefact, not a sharding bug
        cfg = reduced(get_config("tinyllama-1.1b"), num_layers=2,
                      num_heads=4, num_kv_heads=2, d_model=64, head_dim=16,
                      vocab_size=256, d_ff=128, dtype="float32")
        mesh = make_smoke_mesh((2, 4), ("data", "model"))
        ctx = make_context(mesh, cfg, RunConfig(remat="none"))
        params = T.init_lm(cfg, jax.random.PRNGKey(0))
        opt_cfg = OptimizerConfig(lr=1e-2, warmup_steps=0)
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, 256, (8, 33)), jnp.int32)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

        # distributed
        step_d = make_train_step(cfg, opt_cfg, ctx=ctx)
        pshard = sharded_param_specs(params, cfg, ctx.mesh)
        params_d = jax.device_put(params, pshard)
        opt_d = adamw_init(params_d, opt_cfg)
        p2d, _, _, md = jax.jit(step_d)(params_d, opt_d, None, batch)

        # single-device reference
        step_s = make_train_step(cfg, opt_cfg)
        p2s, _, _, ms = jax.jit(step_s)(params, adamw_init(params, opt_cfg),
                                        None, batch)
        print("loss_d", float(md["loss"]), "loss_s", float(ms["loss"]))
        assert abs(float(md["loss"]) - float(ms["loss"])) < 1e-4
        flips = 0
        total = 0
        for a, b in zip(jax.tree_util.tree_leaves(p2d),
                        jax.tree_util.tree_leaves(p2s)):
            flips += int(jnp.sum(jnp.abs(a - b) > 5e-3))
            total += a.size
        print("param sign-flip fraction", flips / total)
        assert flips / total < 0.01
        print("OK")
    """)
    assert "OK" in out


def test_moe_a2a_matches_dense():
    out = run_py("""
        from repro.configs import get_config, reduced
        from repro.models import transformer as T
        from repro.parallel.sharding import make_context
        from repro.launch.mesh import make_smoke_mesh
        from repro.configs.base import RunConfig

        # fp32 end-to-end: bf16 router inputs flip near-tie expert choices
        # between sharding layouts, which is expected but not what this
        # equivalence test measures
        cfg = reduced(get_config("deepseek-moe-16b"), num_layers=2,
                      num_heads=4, num_kv_heads=4, d_model=64, head_dim=16,
                      vocab_size=128, moe_num_experts=4, moe_top_k=2,
                      moe_d_ff=32, moe_first_dense=1,
                      moe_capacity_factor=8.0, dtype="float32")
        mesh = make_smoke_mesh((2, 4), ("data", "model"))
        ctx = make_context(mesh, cfg, RunConfig(remat="none"))
        assert ctx.ep_axis == "a"
        params = T.init_lm(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        toks = jnp.asarray(rng.integers(0, 128, (8, 16)), jnp.int32)
        with jax.set_mesh(ctx.mesh):
            l_d, aux_d = jax.jit(lambda p, t: T.forward(p, cfg, t, ctx=ctx))(params, toks)
        l_s, aux_s = T.forward(params, cfg, toks)
        err = float(jnp.abs(l_d - l_s).max())
        print("moe logits err", err, "aux", float(aux_d), float(aux_s))
        assert err < 2e-2
        print("OK")
    """)
    assert "OK" in out


def test_elastic_restore_different_mesh():
    out = run_py("""
        import tempfile
        from repro.configs import get_config, reduced
        from repro.models import transformer as T
        from repro.train import checkpoint as ckpt
        from repro.launch.mesh import make_smoke_mesh
        from repro.launch.dryrun import sharded_param_specs
        from repro.parallel.sharding import make_context
        from repro.configs.base import RunConfig

        cfg = reduced(get_config("olmo-1b"), num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=4, head_dim=16,
                      vocab_size=256, d_ff=128)
        params = T.init_lm(cfg, jax.random.PRNGKey(0))
        d = tempfile.mkdtemp()
        # save from an 8-device (2x4) mesh
        mesh8 = make_smoke_mesh((2, 4))
        ps8 = sharded_param_specs(params, cfg, make_context(mesh8, cfg, RunConfig()).mesh)
        params8 = jax.device_put(params, ps8)
        ckpt.save(d, 1, params8)
        # restore onto a 4-device (1x4) mesh — elastic downsize
        mesh4 = make_smoke_mesh((1, 4))
        ps4 = sharded_param_specs(params, cfg, make_context(mesh4, cfg, RunConfig()).mesh)
        p2, _, _ = ckpt.restore(d, 1, params, shardings=ps4)
        for a, b in zip(jax.tree_util.tree_leaves(params8),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        print("OK")
    """)
    assert "OK" in out


def test_small_mesh_dryrun_cell():
    """lower+compile works on a small mesh inside a test (the 512-device
    production sweep runs via launch.sweep)."""
    out = run_py("""
        from repro.configs import get_config, reduced, SHAPES
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.models import transformer as T
        from repro.parallel.sharding import (abstract_params, input_specs,
                                             input_shardings, make_context)
        from repro.launch.mesh import make_smoke_mesh
        from repro.launch.dryrun import sharded_param_specs
        from repro.train.optimizer import OptimizerConfig, adamw_init
        from repro.train.train_step import make_train_step
        from repro.train.optimizer import AdamWState
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = reduced(get_config("qwen1.5-32b"), num_layers=2)
        shape = ShapeConfig("t", 256, 8, "train")
        mesh = make_smoke_mesh((2, 4))
        ctx = make_context(mesh, cfg, RunConfig(remat="full"))
        view = ctx.mesh
        params_abs = abstract_params(cfg, dtype=jnp.bfloat16)
        pshard = sharded_param_specs(params_abs, cfg, view)
        opt_cfg = OptimizerConfig()
        step = make_train_step(cfg, opt_cfg, ctx=ctx, microbatches=2)
        opt_abs = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_abs)
        oshard = AdamWState(step=NamedSharding(view, P()), m=pshard, v=pshard)
        batch = input_specs(cfg, shape)
        bshard = input_shardings(cfg, shape, view)
        fn = jax.jit(step, in_shardings=(pshard, oshard, None, bshard),
                     out_shardings=(pshard, oshard, None, None),
                     donate_argnums=(0, 1))
        compiled = fn.lower(params_abs, opt_abs, None, batch).compile()
        ma = compiled.memory_analysis()
        print("temp bytes", ma.temp_size_in_bytes)
        print("OK")
    """)
    assert "OK" in out
