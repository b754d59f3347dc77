"""Model registry: resolves a ModelConfig to its family module and wraps it
in a uniform `Model` handle used by the engine, launcher, and tests."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, hstu, hybrid, seamless, ssm, transformer, vlm

_FAMILIES = {
    "dense": transformer,
    "moe": transformer,
    "mla_moe": transformer,
    "vlm": vlm,
    "ssm": ssm,
    "hybrid": hybrid,
    "encdec": encdec,
    "seamless": seamless,
    "hstu": hstu,
}


@dataclass(frozen=True)
class Model:
    config: ModelConfig
    module: Any

    def init(self, key) -> Any:
        return self.module.init(self.config, key)

    def init_cache(self, batch: int, max_len: int) -> Any:
        return self.module.init_cache(self.config, batch, max_len)

    def forward(
        self,
        params,
        batch: Dict[str, jnp.ndarray],
        *,
        cache=None,
        mode: str = "train",
        impl: str = "auto",
    ) -> Tuple[jnp.ndarray, Optional[Any], Dict[str, jnp.ndarray]]:
        return self.module.forward(
            self.config, params, batch, cache=cache, mode=mode, impl=impl
        )

    def abstract_params(self):
        """ShapeDtypeStruct tree of params — no allocation."""
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def abstract_cache(self, batch: int, max_len: int):
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))


def get_model(cfg: ModelConfig) -> Model:
    if cfg.family not in _FAMILIES:
        raise KeyError(f"unknown family {cfg.family!r}")
    return Model(config=cfg, module=_FAMILIES[cfg.family])
