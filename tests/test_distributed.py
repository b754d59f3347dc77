"""Multi-device distributed-path tests. Each test runs in a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test
process keeps a single device."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str):
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=560,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_moe_expert_parallel_equals_dense_dispatch():
    out = run_in_subprocess(
        """
        from repro.configs import SMOKE_CONFIGS
        from repro.models import moe as M
        cfg = SMOKE_CONFIGS["qwen3-moe-30b-a3b"].replace(dtype="float32")
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.n_experts)/cfg.moe.top_k,
            dispatch_rank="sort"))
        p = M.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
        dense_out, _ = M.moe_ffn(cfg, p, x)
        M.EP_MESH = mesh
        with mesh:
            ep_out, _ = jax.jit(lambda p, x: M.moe_ffn(cfg, p, x))(p, x)
        err = float(np.abs(np.asarray(ep_out) - np.asarray(dense_out)).max())
        assert err < 1e-5, err
        print("EP_OK", err)
        """
    )
    assert "EP_OK" in out


@pytest.mark.slow
def test_sharded_train_step_runs_on_8_devices():
    out = run_in_subprocess(
        """
        from repro.configs import SMOKE_CONFIGS
        from repro.distributed import sharding as sh
        from repro.models import get_model
        from repro.training import optimizer as opt
        from repro.training.train_loop import make_train_step
        cfg = SMOKE_CONFIGS["llama3.2-1b"]
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        ocfg = opt.OptimizerConfig(total_steps=3)
        state = opt.init_state(params, ocfg)
        pspecs = sh.param_specs(cfg, params, mesh, enable_tp=True)
        ospecs = sh.opt_state_specs(cfg, state, mesh, enable_tp=True)
        bspec = sh.batch_spec(mesh, 4)
        step = jax.jit(make_train_step(model, ocfg), in_shardings=(
            sh.to_shardings(mesh, pspecs), sh.to_shardings(mesh, ospecs),
            {"tokens": sh.to_shardings(mesh, bspec),
             "labels": sh.to_shardings(mesh, bspec)}))
        batch = {"tokens": jnp.zeros((4, 32), jnp.int32),
                 "labels": jnp.zeros((4, 32), jnp.int32)}
        with mesh:
            p2, s2, metrics = step(params, state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        print("TRAIN_OK", float(metrics["loss"]))
        """
    )
    assert "TRAIN_OK" in out
