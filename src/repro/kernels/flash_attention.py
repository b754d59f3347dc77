"""Pallas TPU flash-attention kernel (the paper's SDPA lever, §4.1.1,
re-tiled for the TPU memory hierarchy).

TPU adaptation of FlashAttention-2:
- (block_q × block_k) tiles stream HBM→VMEM; the innermost grid dimension
  walks the KV blocks and the online-softmax running state (m, l, acc)
  lives in VMEM scratch across it (TPU grids run sequentially, which
  replaces the CUDA thread-block carry);
- GQA-native: one KV tile holds ALL KV heads. K and V are read as
  ``[B, Hkv, D, S]`` views, ``(1, Hkv, D, block_k)`` per tile: the TPU's
  default layout for a ``[B, S, Hkv, D]`` cache with ``D < 128`` already
  puts the sequence minor-most (so the 128 lanes are not padded), and the
  transpose compiles to a bitcast of the cache's own bytes, not a copy —
  the compiled decode step holds none. The queries
  are regrouped per KV head as ``[B, Hkv, Tq * G, D]`` rows, so each
  head's score and PV products are plain 2-D matmuls over its whole
  q-head group, and each KV tile is read once per query block;
- causal / sliding-window / validity masking from position tiles; tiles
  with no visible key are skipped with ``pl.when`` on per-tile position
  bounds that arrive as scalar prefetch (SMEM), so the skip test is
  scalar arithmetic, not a reduction over a mask tile.

Decode (one query per sequence against a long cache) is the ``Tq = 1``
case of this kernel: see kernels/decode_attention.py.

Validated in ``interpret=True`` mode against kernels/ref.py on CPU, and
compiled for a described TPU v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
_BIG = 2 ** 30  # position bound of a tile with no valid key
#: query rows (block_q * Hq) per block: the per-row softmax state and
#: accumulator of every head stay in scoped VMEM (16 MiB on a v5e)
_MAX_Q_ROWS = 4096
#: scores of one KV head's tile (block_q * G * block_k): its float32
#: score and probability tiles sit in the same scoped VMEM, so a model with
#: many query heads per KV head (G) takes fewer query positions a block
_MAX_TILE_SCORES = 512 * 512


def _flash_kernel(
    q_lo_ref, q_hi_ref, k_lo_ref, k_hi_ref,  # SMEM [B*nq] / [B*nk] bounds
    qpos_ref,  # [1, rows, 1] query position per row (rows = bq * G)
    kpos_ref, kval_ref,  # [1, 1, bk] key positions / validity (int32)
    q_ref,  # [1, Hkv, rows, D]
    k_ref, v_ref,  # [1, Hkv, D, bk] / [1, Hkv, Dv, bk]
    o_ref,  # [1, Hkv, rows, Dv]
    m_scr, l_scr, acc_scr,  # VMEM [Hkv, rows, 1] x2, [Hkv, rows, Dv]
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    n_q_blocks: int,
    n_k_blocks: int,
):
    ib, iq, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    hkv = q_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qi = ib * n_q_blocks + iq
    ki = ib * n_k_blocks + ik
    k_lo, k_hi = k_lo_ref[ki], k_hi_ref[ki]
    visible = k_lo <= k_hi  # the tile holds at least one valid key
    if causal:
        visible = jnp.logical_and(visible, k_lo <= q_hi_ref[qi])
    if window is not None:
        visible = jnp.logical_and(visible, k_hi > q_lo_ref[qi] - window)

    @pl.when(visible)
    def _compute():  # predicated block-skipping for masked tiles
        qpos = qpos_ref[0]  # [rows, 1]
        kpos = kpos_ref[0]  # [1, bk]
        ok = jnp.broadcast_to(kval_ref[0] != 0, (qpos.shape[0], kpos.shape[1]))
        if causal:
            ok = jnp.logical_and(ok, kpos <= qpos)
        if window is not None:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        for h in range(hkv):
            k = k_ref[0, h]  # [D, bk]
            v = v_ref[0, h]  # [Dv, bk]
            q = (q_ref[0, h].astype(jnp.float32) * scale).astype(k.dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rows, bk]
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            l_scr[h] = l_scr[h] * alpha + p.sum(axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rows, Dv]
            acc_scr[h] = acc_scr[h] * alpha + pv
            m_scr[h] = m_new

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        for h in range(hkv):
            l = jnp.maximum(l_scr[h], 1e-30)
            o_ref[0, h] = (acc_scr[h] / l).astype(o_ref.dtype)


def _tile_bounds(pos, valid, n_blocks, block):
    """Per-tile [lo, hi] of the VALID positions, flattened to [B * n]; a
    tile with no valid entry gets lo > hi."""
    b = pos.shape[0]
    pos = pos.reshape(b, n_blocks, block)
    valid = valid.reshape(b, n_blocks, block)
    lo = jnp.where(valid, pos, _BIG).min(axis=-1)
    hi = jnp.where(valid, pos, -_BIG).max(axis=-1)
    return lo.reshape(-1).astype(jnp.int32), hi.reshape(-1).astype(jnp.int32)


def flash_attention_pallas(
    q: jnp.ndarray,  # [B, Tq, Hq, D]
    k: jnp.ndarray,  # [B, Tk, Hkv, D]
    v: jnp.ndarray,  # [B, Tk, Hkv, Dv]
    *,
    q_positions: jnp.ndarray,  # [B, Tq]
    k_positions: jnp.ndarray,  # [B, Tk]
    causal: bool = True,
    window: Optional[int] = None,
    k_valid: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """On a TPU, ``block_k`` must be a multiple of 128 or cover ``Tk``, and
    ``block_q * G`` a multiple of 16 or cover ``Tq * G`` (Mosaic's tiling
    of the position and query blocks)."""
    from jax.experimental.pallas import tpu as pltpu

    b, tq, hq, d = q.shape
    tk, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    block_k = min(block_k, tk)
    block_q = min(block_q, tq, max(16, _MAX_Q_ROWS // hq // 16 * 16),
                  max(16, _MAX_TILE_SCORES // (g * block_k) // 16 * 16))

    pq = (-tq) % block_q
    pk = (-tk) % block_k
    if k_valid is None:
        k_valid = jnp.ones((b, tk), bool)
    else:
        k_valid = jnp.broadcast_to(k_valid, (b, tk))
    q_positions = jnp.broadcast_to(q_positions, (b, tq)).astype(jnp.int32)
    k_positions = jnp.broadcast_to(k_positions, (b, tk)).astype(jnp.int32)
    q_valid = jnp.ones((b, tq), bool)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pq)))
        q_valid = jnp.pad(q_valid, ((0, 0), (0, pq)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, ((0, 0), (0, pk)))
        k_valid = jnp.pad(k_valid, ((0, 0), (0, pk)))

    tq_p, tk_p = tq + pq, tk + pk
    n_q_blocks, n_k_blocks = tq_p // block_q, tk_p // block_k
    rows = block_q * g
    q_lo, q_hi = _tile_bounds(q_positions, q_valid, n_q_blocks, block_q)
    k_lo, k_hi = _tile_bounds(k_positions, k_valid, n_k_blocks, block_k)
    # queries regrouped per KV head: row t * G + j is q head h * G + j at t
    qg = q.reshape(b, tq_p, hkv, g, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, hkv, tq_p * g, d)
    qpos_rows = jnp.repeat(q_positions, g, axis=1)[:, :, None]  # [B, Tq*G, 1]
    kpos = k_positions[:, None, :]  # [B, 1, Tk]
    kval = k_valid.astype(jnp.int32)[:, None, :]
    # [B, Hkv, D, Tk]: the cache's HBM order (see the module docstring),
    # so this transpose relabels the same bytes instead of copying them
    kt = k.transpose(0, 2, 3, 1)
    vt = v.transpose(0, 2, 3, 1)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        n_q_blocks=n_q_blocks, n_k_blocks=n_k_blocks,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, n_q_blocks, n_k_blocks),
        in_specs=[
            pl.BlockSpec((1, rows, 1), lambda ib, iq, ik, *_: (ib, iq, 0)),
            pl.BlockSpec((1, 1, block_k), lambda ib, iq, ik, *_: (ib, 0, ik)),
            pl.BlockSpec((1, 1, block_k), lambda ib, iq, ik, *_: (ib, 0, ik)),
            pl.BlockSpec(
                (1, hkv, rows, d), lambda ib, iq, ik, *_: (ib, 0, iq, 0)
            ),
            pl.BlockSpec(
                (1, hkv, d, block_k), lambda ib, iq, ik, *_: (ib, 0, 0, ik)
            ),
            pl.BlockSpec(
                (1, hkv, dv, block_k), lambda ib, iq, ik, *_: (ib, 0, 0, ik)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, hkv, rows, dv), lambda ib, iq, ik, *_: (ib, 0, iq, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, tq_p * g, dv), q.dtype),
        interpret=interpret,
    )(q_lo, q_hi, k_lo, k_hi, qpos_rows, kpos, kval, qg, kt, vt)
    out = out.reshape(b, hkv, tq_p, g, dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, tq_p, hq, dv)[:, :tq]
