"""Training launcher: ``--arch <id>`` + mesh flags.

Builds a (data, model) mesh over the local devices
(``mesh.make_debug_mesh``) and jits train_step with the sharding rules from
distributed/sharding.py. examples/train_small.py is the smaller local run.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --steps 100 --batch 4 --seq 128 [--smoke] [--ckpt out.npz]
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, get_smoke_config
from repro.distributed import sharding as sh
from repro.launch.mesh import make_debug_mesh
from repro.models import get_model
from repro.training import data, optimizer as opt
from repro.training.train_loop import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = get_model(cfg)
    mesh = make_debug_mesh()
    print(f"[train] arch={cfg.name} params={cfg.n_params() / 1e6:.1f}M "
          f"mesh={dict(mesh.shape)} devices={len(jax.devices())}")

    opt_cfg = opt.OptimizerConfig(lr=args.lr, total_steps=args.steps)
    params = model.init(jax.random.PRNGKey(0))
    state = opt.init_state(params, opt_cfg)

    pspecs = sh.param_specs(cfg, params, mesh)
    ospecs = sh.opt_state_specs(cfg, state, mesh)
    bspec = sh.batch_spec(mesh, args.batch)
    step = jax.jit(
        make_train_step(model, opt_cfg),
        in_shardings=(
            sh.to_shardings(mesh, pspecs),
            sh.to_shardings(mesh, ospecs),
            {"tokens": sh.to_shardings(mesh, bspec),
             "labels": sh.to_shardings(mesh, bspec)},
        ),
        donate_argnums=(0, 1),
    )

    stream = data.token_stream(cfg, batch=args.batch, seq_len=args.seq)
    import time

    t0 = time.perf_counter()
    with mesh:
        for i in range(args.steps):
            batch = {k: jax.numpy.asarray(v) for k, v in next(stream).items()}
            params, state, metrics = step(params, state, batch)
            if i % args.log_every == 0 or i == args.steps - 1:
                tok_s = args.batch * args.seq * (i + 1) / (time.perf_counter() - t0)
                print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"({tok_s:.0f} tok/s)")
    if args.ckpt:
        from repro.training import checkpoint

        checkpoint.save(args.ckpt, params, state, step=args.steps)
        print(f"[train] checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
