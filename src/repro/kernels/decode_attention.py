"""Pallas TPU flash-decode: one new token per sequence against a long
static KV cache (the memory-bound phase, paper Obs #1/#3).

Decode attention is pure HBM streaming: arithmetic intensity ≈ 2 FLOPs per
cached byte, far below the v5e ridge — the kernel's job is to keep HBM→VMEM
transfers saturated, not the MXU. It is the ``Tq = 1`` case of the flash
kernel (kernels/flash_attention.py):

- grid (B, 1, S/block_k): the innermost KV-block dimension streams the
  cache once, all KV heads per tile, in the cache's own layout; the
  running online-softmax state for every q-head group sits in VMEM
  scratch;
- GQA: each KV head's [G, D] q-head group is one row block, so each KV
  tile is read exactly once (minimum possible traffic);
- per-sequence ``lengths`` mask validity (static cache, paper §4.1.2);
  tiles entirely past ``lengths`` are skipped via predication on
  scalar-prefetched tile bounds.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas


def decode_attention_pallas(
    q: jnp.ndarray,  # [B, Hq, D]
    k: jnp.ndarray,  # [B, S, Hkv, D]
    v: jnp.ndarray,  # [B, S, Hkv, Dv]
    lengths: jnp.ndarray,  # [B]
    *,
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    s = k.shape[1]
    lengths = lengths.astype(jnp.int32)
    kpos = jnp.arange(s, dtype=jnp.int32)[None]
    out = flash_attention_pallas(
        q[:, None], k, v,
        q_positions=(lengths - 1)[:, None],
        k_positions=kpos,
        causal=False,
        k_valid=kpos < lengths[:, None],
        scale=scale,
        block_q=1,
        block_k=block_k,
        interpret=interpret,
    )
    return out[:, 0]
