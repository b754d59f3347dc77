"""AdamW with global-norm clipping and cosine LR schedule — implemented
directly in JAX (no optax dependency in this container)."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptimizerConfig:
    lr: float = 3e-4
    betas: Tuple[float, float] = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    # moment dtype: f32 default; bf16 halves optimizer HBM
    moment_dtype: str = "float32"


class AdamState(NamedTuple):
    step: jnp.ndarray  # scalar int32
    mu: Any  # first moments (tree like params)
    nu: Any  # second moments


def init_state(params: Any, cfg: OptimizerConfig) -> AdamState:
    dt = jnp.dtype(cfg.moment_dtype)
    zeros = lambda p: jnp.zeros(p.shape, dt)
    return AdamState(
        step=jnp.zeros((), jnp.int32),
        mu=jax.tree.map(zeros, params),
        nu=jax.tree.map(zeros, params),
    )


def lr_at(step: jnp.ndarray, cfg: OptimizerConfig) -> jnp.ndarray:
    """Linear warmup then cosine decay to min_lr_frac."""
    warm = cfg.lr * jnp.minimum(step + 1, cfg.warmup_steps) / cfg.warmup_steps
    prog = jnp.clip(
        (step - cfg.warmup_steps) / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
        0.0,
        1.0,
    )
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < cfg.warmup_steps, warm, cfg.lr * cos)


def global_norm(tree: Any) -> jnp.ndarray:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))
    )


def _is_decayed(path: Tuple) -> bool:
    """No weight decay on norms/biases/scalars (llama convention)."""
    keys = [getattr(p, "key", getattr(p, "idx", "")) for p in path]
    s = "/".join(str(k) for k in keys)
    return not any(t in s for t in ("norm", "scale", "/b", "bias", "A_log", "lam", "D"))


def apply_updates(
    params: Any,
    grads: Any,
    state: AdamState,
    cfg: OptimizerConfig,
) -> Tuple[Any, AdamState, dict]:
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
    b1, b2 = cfg.betas
    step = state.step + 1
    lr = lr_at(state.step, cfg)
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(path, p, g, mu, nu):
        g = g.astype(jnp.float32) * scale
        mu_n = b1 * mu.astype(jnp.float32) + (1 - b1) * g
        nu_n = b2 * nu.astype(jnp.float32) + (1 - b2) * g * g
        u = (mu_n / bc1) / (jnp.sqrt(nu_n / bc2) + cfg.eps)
        if _is_decayed(path):
            u = u + cfg.weight_decay * p.astype(jnp.float32)
        new_p = (p.astype(jnp.float32) - lr * u).astype(p.dtype)
        dt = jnp.dtype(cfg.moment_dtype)
        return new_p, mu_n.astype(dt), nu_n.astype(dt)

    p_flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    g_flat = jax.tree.leaves(grads)
    mu_flat = jax.tree.leaves(state.mu)
    nu_flat = jax.tree.leaves(state.nu)
    results = [
        upd(path, p, g, m, n)
        for (path, p), g, m, n in zip(p_flat, g_flat, mu_flat, nu_flat)
    ]
    unflatten = jax.tree_util.tree_unflatten
    new_params = unflatten(treedef, [r[0] for r in results])
    new_mu = unflatten(treedef, [r[1] for r in results])
    new_nu = unflatten(treedef, [r[2] for r in results])
    return (
        new_params,
        AdamState(step=step, mu=new_mu, nu=new_nu),
        {"grad_norm": gnorm, "lr": lr},
    )
