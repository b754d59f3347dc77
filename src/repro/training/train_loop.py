"""Training loop: loss, train_step, and a host-side loop with
checkpointing and metrics."""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.registry import Model, get_model
from repro.training import checkpoint as ckpt
from repro.training import optimizer as opt


def loss_fn(
    model: Model, params, batch: Dict[str, jnp.ndarray]
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    logits, _, aux = model.forward(params, batch, mode="train")
    mask = batch.get("loss_mask")
    ce = L.cross_entropy_loss(logits, batch["labels"], mask)
    total = ce + aux.get("aux_loss", 0.0)
    return total, {"ce": ce, "aux": aux.get("aux_loss", jnp.float32(0.0))}


def make_train_step(
    model: Model, opt_cfg: opt.OptimizerConfig
) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics). NOT jitted here — the launcher jits it with shardings."""

    def train_step(params, opt_state, batch):
        (loss, parts), grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch), has_aux=True
        )(params)
        params, opt_state, om = opt.apply_updates(params, grads, opt_state, opt_cfg)
        metrics = {"loss": loss, **parts, **om}
        return params, opt_state, metrics

    return train_step


@dataclass
class TrainResult:
    params: Any
    opt_state: Any
    metrics_history: list


def train(
    cfg: ModelConfig,
    *,
    data: Iterator[Dict],
    steps: int,
    opt_cfg: Optional[opt.OptimizerConfig] = None,
    seed: int = 0,
    log_every: int = 10,
    ckpt_path: Optional[str] = None,
    ckpt_every: int = 0,
    jit: bool = True,
) -> TrainResult:
    model = get_model(cfg)
    opt_cfg = opt_cfg or opt.OptimizerConfig(total_steps=steps)
    params = model.init(jax.random.PRNGKey(seed))
    opt_state = opt.init_state(params, opt_cfg)
    step_fn = make_train_step(model, opt_cfg)
    if jit:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    history = []
    t0 = time.perf_counter()
    for i in range(steps):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if i % log_every == 0 or i == steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            m["step"] = i
            m["wall_s"] = time.perf_counter() - t0
            history.append(m)
            print(
                f"step {i:5d} loss {m['loss']:.4f} ce {m['ce']:.4f} "
                f"gnorm {m['grad_norm']:.3f} lr {m['lr']:.2e} ({m['wall_s']:.1f}s)"
            )
        if ckpt_path and ckpt_every and (i + 1) % ckpt_every == 0:
            ckpt.save(ckpt_path, params, opt_state, step=i + 1)
    if ckpt_path:
        ckpt.save(ckpt_path, params, opt_state, step=steps)
    return TrainResult(params=params, opt_state=opt_state, metrics_history=history)
