"""Device meshes for the launchers.

Defined as FUNCTIONS so importing this module never touches jax device
state.
"""
from __future__ import annotations

import jax


def make_debug_mesh(n_devices: int | None = None):
    """Small mesh over whatever devices exist (tests: host platform count)."""
    n = n_devices or len(jax.devices())
    if n >= 4:
        return jax.make_mesh((n // 2, 2), ("data", "model"))
    return jax.make_mesh((n, 1), ("data", "model"))
