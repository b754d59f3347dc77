"""Jitted dispatch wrappers for every kernel.

Each op has up to three implementations:

- ``ref``    — the naive oracle in :mod:`repro.kernels.ref` (materializes).
- ``xla``    — a memory-efficient pure-XLA implementation (chunked online
               softmax / chunked SSD). This is what runs off the TPU
               (the CPU tests); it is the paper's "SDPA" lever expressed
               in XLA.
- ``pallas`` — the TPU Pallas kernel (VMEM-tiled). Validated on CPU with
               ``interpret=True``; selected on real TPU backends.

``impl="auto"`` picks ``pallas`` on TPU and ``xla`` elsewhere.
``xla_blockskip`` is the beyond-paper causal-block-skipping variant of the
xla path (§Perf lever: skips fully-masked KV blocks instead of masking).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as _ref


def _interpret() -> bool:
    """Pallas kernels compile through Mosaic on a TPU backend and run in
    the Pallas interpreter everywhere else."""
    return jax.default_backend() != "tpu"


def _default_impl() -> str:
    return "xla" if _interpret() else "pallas"


def _resolve(impl: str) -> str:
    return _default_impl() if impl == "auto" else impl


def _on_own_heads(kernel, q_head_axis: int, q, k, v, *rest):
    """Call a Pallas attention kernel on this device's heads.

    GSPMD cannot partition a Mosaic kernel. A step traced under a mesh with
    a ``"model"`` axis (the tensor-parallel pool shards heads over it, see
    distributed/tp_pool.py) runs the kernel under ``shard_map``: q, k, v
    and the output split on their head axis, ``rest`` (positions,
    validity, lengths) replicated. Where the KV heads do not divide the
    axis (the pool then shards the cache's sequence axis instead), every
    device runs the whole kernel on gathered inputs. Off such a mesh the
    kernel is called as is."""
    mesh = jax.sharding.get_abstract_mesh()
    n = 1 if mesh.empty else dict(mesh.shape).get("model", 1)
    if n == 1:
        return kernel(q, k, v, *rest)
    split = "model" if k.shape[2] % n == 0 else None

    def heads(ndim, axis):
        return P(*(split if i == axis else None for i in range(ndim)))

    q_spec, kv_spec = heads(q.ndim, q_head_axis), heads(k.ndim, 2)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec) + (P(),) * len(rest),
        out_specs=q_spec, check_vma=False,
    )(q, k, v, *rest)


NEG_INF = -1e30  # finite sentinel: keeps online softmax NaN-free


# --------------------------------------------------------------------------
# Flash attention (train / prefill)
# --------------------------------------------------------------------------

def flash_attention(
    q: jnp.ndarray,  # [B, Tq, Hq, D]
    k: jnp.ndarray,  # [B, Tk, Hkv, D]
    v: jnp.ndarray,  # [B, Tk, Hkv, D]
    *,
    q_positions: Optional[jnp.ndarray] = None,
    k_positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
    window: Optional[int] = None,
    k_valid: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    impl: str = "auto",
    block_k: int = 512,
    block_q: int = 512,
) -> jnp.ndarray:
    impl = _resolve(impl)
    b, tq, hq, d = q.shape
    tk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(tq)[None] + (tk - tq), (b, tq))
    if k_positions is None:
        k_positions = jnp.broadcast_to(jnp.arange(tk)[None], (b, tk))
    if impl == "ref":
        return _ref.attention_ref(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            causal=causal, window=window, k_valid=k_valid, scale=scale,
        )
    if impl == "pallas":
        from repro.kernels import flash_attention as _fa

        def kernel(q, k, v, q_positions, k_positions, k_valid):
            return _fa.flash_attention_pallas(
                q, k, v, q_positions=q_positions, k_positions=k_positions,
                causal=causal, window=window, k_valid=k_valid, scale=scale,
                block_q=block_q, block_k=block_k, interpret=_interpret(),
            )

        return _on_own_heads(
            kernel, 2, q, k, v, q_positions, k_positions, k_valid
        )
    if impl == "xla_blockskip":
        return _flash_xla_blockskip(
            q, k, v, q_positions, k_positions, causal, window, k_valid,
            scale, block_q, block_k,
        )
    return _flash_xla(
        q, k, v, q_positions, k_positions, causal, window, k_valid, scale, block_k
    )


def _mask_bias(
    qpos: jnp.ndarray,  # [B, T]
    kpos: jnp.ndarray,  # [B, S]
    causal: bool,
    window: Optional[int],
    k_valid: Optional[jnp.ndarray],
) -> jnp.ndarray:
    """[B, T, S] additive bias: 0 where attend, NEG_INF where masked."""
    qp = qpos[:, :, None]
    kp = kpos[:, None, :]
    ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), dtype=bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > qp - window
    if k_valid is not None:
        ok &= k_valid[:, None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _flash_xla(q, k, v, qpos, kpos, causal, window, k_valid, scale, block_k):
    """Chunked online-softmax attention: scans KV in blocks, never
    materializing the [Tq, Tk] score matrix. GQA-aware (KV loaded once per
    Q-head group)."""
    # Never pad KV beyond the actual sequence: with the defaults
    # (block_k=512/1024) a short cache (e.g. an 81-token smoke decode)
    # would be zero-padded up to a full block, materializing transients
    # ~12x the cache itself (caught by repro.analysis audit_no_growth).
    # Masked pad rows contribute exact zeros to the online softmax, so
    # clamping is bit-identical.
    block_k = min(block_k, max(k.shape[1], 1))
    b, tq, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    dv = v.shape[-1]

    pad = (-s) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)))
        kv_pad_valid = jnp.broadcast_to(jnp.arange(s + pad)[None, :] < s, (b, s + pad))
        k_valid = kv_pad_valid if k_valid is None else (
            jnp.pad(k_valid, ((0, 0), (0, pad))) & kv_pad_valid
        )
    n_blk = (s + pad) // block_k

    qf = (q.astype(jnp.float32) * scale).reshape(b, tq, hkv, g, d)
    k_blocks = k.reshape(b, n_blk, block_k, hkv, d).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, n_blk, block_k, hkv, dv).transpose(1, 0, 2, 3, 4)
    kpos_blocks = kpos.reshape(b, n_blk, block_k).transpose(1, 0, 2)
    kval_blocks = (
        None
        if k_valid is None
        else k_valid.reshape(b, n_blk, block_k).transpose(1, 0, 2)
    )

    m0 = jnp.full((b, tq, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, tq, hkv, g), jnp.float32)
    acc0 = jnp.zeros((b, tq, hkv, g, dv), jnp.float32)

    def step(carry, blk):
        m, l, acc = carry
        if kval_blocks is None:
            kb, vb, kpb = blk
            kvb = None
        else:
            kb, vb, kpb, kvb = blk
        scores = jnp.einsum(
            "bthgd,bshd->bthgs", qf, kb.astype(jnp.float32)
        )  # [B,T,Hkv,G,blk]
        bias = _mask_bias(qpos, kpb, causal, window, kvb)  # [B,T,blk]
        scores = scores + bias[:, :, None, None, :]
        new_m = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[..., None])
        new_l = l * alpha + p.sum(axis=-1)
        new_acc = acc * alpha[..., None] + jnp.einsum(
            "bthgs,bshd->bthgd", p, vb.astype(jnp.float32)
        )
        return (new_m, new_l, new_acc), None

    xs = (
        (k_blocks, v_blocks, kpos_blocks)
        if kval_blocks is None
        else (k_blocks, v_blocks, kpos_blocks, kval_blocks)
    )
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), xs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, tq, hq, dv).astype(q.dtype)


def _flash_xla_blockskip(
    q, k, v, qpos, kpos, causal, window, k_valid, scale, block_q, block_k
):
    """Causal-block-skipping variant (beyond-paper §Perf lever).

    Splits queries into static blocks (python-unrolled at trace time) and,
    for each, only scans KV blocks that can be visible under the causal /
    sliding-window mask. Requires position-aligned inputs (qpos/kpos equal
    across batch and contiguous) — callers fall back to ``xla`` otherwise.
    Cuts flash-attention FLOPs ~2x for causal training and to O(W·T) for
    sliding windows.
    """
    b, tq, hq, d = q.shape
    s = k.shape[1]
    q_lo = tq and int(s - tq)  # queries start at position s - tq (aligned)
    outs = []
    for qstart in range(0, tq, block_q):
        qlen = min(block_q, tq - qstart)
        q_blk = jax.lax.slice_in_dim(q, qstart, qstart + qlen, axis=1)
        qpos_blk = jax.lax.slice_in_dim(qpos, qstart, qstart + qlen, axis=1)
        # visible key range for this q block under causal+window
        hi = q_lo + qstart + qlen if causal else s
        lo = max(0, q_lo + qstart - (window - 1)) if window is not None else 0
        lo = (lo // block_k) * block_k
        hi = min(s, hi)
        k_blk = jax.lax.slice_in_dim(k, lo, hi, axis=1)
        v_blk = jax.lax.slice_in_dim(v, lo, hi, axis=1)
        kpos_blk = jax.lax.slice_in_dim(kpos, lo, hi, axis=1)
        kval_blk = (
            None if k_valid is None else jax.lax.slice_in_dim(k_valid, lo, hi, axis=1)
        )
        outs.append(
            _flash_xla(
                q_blk, k_blk, v_blk, qpos_blk, kpos_blk, causal, window,
                kval_blk, scale, min(block_k, max(k_blk.shape[1], 1)),
            )
        )
    return jnp.concatenate(outs, axis=1)


# --------------------------------------------------------------------------
# Decode attention (one token vs. a long KV cache)
# --------------------------------------------------------------------------

def decode_attention(
    q: jnp.ndarray,  # [B, Hq, D]
    k: jnp.ndarray,  # [B, S, Hkv, D]
    v: jnp.ndarray,  # [B, S, Hkv, Dv]
    lengths: jnp.ndarray,  # [B]
    *,
    scale: Optional[float] = None,
    impl: str = "auto",
    block_k: int = 1024,
) -> jnp.ndarray:
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.decode_attention_ref(q, k, v, lengths, scale=scale)
    if impl == "pallas":
        from repro.kernels import decode_attention as _da

        def kernel(q, k, v, lengths):
            return _da.decode_attention_pallas(
                q, k, v, lengths, scale=scale, block_k=block_k,
                interpret=_interpret(),
            )

        return _on_own_heads(kernel, 1, q, k, v, lengths)
    b, s, hkv, d = k.shape
    k_valid = jnp.arange(s)[None, :] < lengths[:, None]
    kpos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    qpos = lengths[:, None] - 1
    out = _flash_xla(
        q[:, None], k, v, qpos, kpos, False, None, k_valid,
        scale if scale is not None else q.shape[-1] ** -0.5, block_k,
    )
    return out[:, 0]


def decode_attention_partial(
    q: jnp.ndarray,  # [B, Hq, D]
    k: jnp.ndarray,  # [B, S_tile, Hkv, D] — a tile of the keys
    v: jnp.ndarray,
    k_valid: jnp.ndarray,  # [B, S_tile]
    *,
    scale: Optional[float] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Flash-decode partial: returns (acc [B,Hq,Dv], m [B,Hq], l [B,Hq])
    for LSE-combination across key tiles (the paged decode walk folds
    them with :func:`merge_partial_attention`)."""
    b, s, hkv, d = k.shape
    hq = q.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(b, hkv, g, d)
    scores = jnp.einsum("bhgd,bshd->bhgs", qf, k.astype(jnp.float32))
    scores = jnp.where(k_valid[:, None, None, :], scores, NEG_INF)
    m = scores.max(axis=-1)
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(k_valid[:, None, None, :], p, 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return (
        acc.reshape(b, hq, v.shape[-1]),
        m.reshape(b, hq),
        l.reshape(b, hq),
    )


def merge_partial_attention(a, b):
    """LSE-merge two flash-decode partials (acc, m, l) into one, still
    unnormalized: the running form of :func:`combine_partial_attention`
    (the paged decode walk folds each tile into its carry with it)."""
    acc_a, m_a, l_a = a
    acc_b, m_b, l_b = b
    m = jnp.maximum(m_a, m_b)
    sa, sb = jnp.exp(m_a - m), jnp.exp(m_b - m)
    return acc_a * sa[..., None] + acc_b * sb[..., None], m, l_a * sa + l_b * sb


def combine_partial_attention(accs, ms, ls):
    """LSE-combine flash-decode partials stacked on a leading shard axis."""
    m = ms.max(axis=0)
    alpha = jnp.exp(ms - m[None])
    l = (ls * alpha).sum(axis=0)
    acc = (accs * alpha[..., None]).sum(axis=0)
    return acc / jnp.maximum(l, 1e-30)[..., None]


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------

def rmsnorm(x, weight, eps: float = 1e-5, impl: str = "auto"):
    impl = _resolve(impl)
    if impl == "pallas":
        from repro.kernels import rmsnorm as _rn

        return _rn.rmsnorm_pallas(
            x, weight, eps=eps, interpret=_interpret()
        )
    return _ref.rmsnorm_ref(x, weight, eps)


# --------------------------------------------------------------------------
# Int8 matmul (AutoQuant substrate)
# --------------------------------------------------------------------------

def quantize_int8(w: jnp.ndarray, axis: int = 0):
    """Symmetric per-channel int8 quantization along ``axis`` (the
    contraction axis): returns (w_q int8, scale f32 over remaining dims)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    w_q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(
        jnp.int8
    )
    return w_q, scale.squeeze(axis)


def int8_matmul_weight_only(x, w_q, w_scale, impl: str = "auto"):
    """x @ dequant(w_q): memory-bound lever (paper §4.2 weight-only).

    XLA path applies the per-output-channel scale AFTER the GEMM —
    x @ (W_q·s) == (x @ W_q)·s — so the int8 weights feed the dot
    directly (int values ≤127 are exact in bf16) and no dequantized
    full-size weight is ever materialized in HBM. §Perf round 4: the
    dequant-first form added a f32 weight write+read per layer and made
    int8 SLOWER than bf16 on the memory term."""
    impl = _resolve(impl)
    if impl == "pallas":
        from repro.kernels import int8_matmul as _im

        return _im.int8_matmul_pallas(
            x, w_q, w_scale, interpret=_interpret()
        )
    if impl == "ref":
        return _ref.int8_matmul_ref(x, w_q, w_scale)
    acc = jnp.matmul(
        x, w_q.astype(x.dtype), preferred_element_type=jnp.float32
    )
    return (acc * w_scale[None, :].astype(jnp.float32)).astype(x.dtype)


def int8_matmul_dynamic(x, w_q, w_scale, impl: str = "auto"):
    """Dynamic activation quantization + int8×int8 GEMM: compute-bound
    lever (paper §4.2 dynamic). Activation scales computed per row."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    x_scale = jnp.maximum(amax, 1e-8) / 127.0
    x_q = jnp.clip(jnp.round(x.astype(jnp.float32) / x_scale), -127, 127).astype(
        jnp.int8
    )
    impl = _resolve(impl)
    if impl == "pallas":
        from repro.kernels import int8_matmul as _im

        return _im.int8_matmul_dynamic_pallas(
            x_q, w_q, w_scale, x_scale, interpret=_interpret()
        )
    out = _ref.int8_matmul_ref(x_q, w_q, w_scale, x_scale=x_scale)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Mamba-2 SSD (chunked state-space duality)
# --------------------------------------------------------------------------

def ssd_scan(
    x: jnp.ndarray,  # [B, T, H, P]
    dt: jnp.ndarray,  # [B, T, H] (already softplus'd, >0)
    A: jnp.ndarray,  # [H] (negative)
    B_: jnp.ndarray,  # [B, T, G, N]
    C: jnp.ndarray,  # [B, T, G, N]
    D: jnp.ndarray,  # [H]
    *,
    chunk: int = 256,
    initial_state: Optional[jnp.ndarray] = None,
    impl: str = "auto",
):
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.ssd_ref(x, dt, A, B_, C, D, initial_state=initial_state)
    if impl == "pallas":
        from repro.kernels import ssd_scan as _ss

        return _ss.ssd_scan_pallas(
            x, dt, A, B_, C, D, chunk=chunk, initial_state=initial_state,
            interpret=_interpret(),
        )
    return _ssd_chunked_xla(x, dt, A, B_, C, D, chunk, initial_state)


def _ssd_chunked_xla(x, dt, A, B_, C, D, chunk, initial_state):
    """Chunked SSD: quadratic attention-like intra-chunk + linear
    inter-chunk state recurrence (Mamba-2 Algorithm, arXiv:2405.21060)."""
    b, t, h, p = x.shape
    g, n = B_.shape[2], B_.shape[3]
    rep = h // g
    pad = (-t) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    tp = t + pad
    nc = tp // chunk

    xf = x.astype(jnp.float32).reshape(b, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, h)
    Bf = jnp.repeat(B_.astype(jnp.float32), rep, axis=2).reshape(b, nc, chunk, h, n)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=2).reshape(b, nc, chunk, h, n)
    Af = A.astype(jnp.float32)

    log_decay = dtf * Af[None, None, None, :]  # [B,nc,Q,H], <= 0
    cum = jnp.cumsum(log_decay, axis=2)  # inclusive cumulative log-decay
    cum_total = cum[:, :, -1]  # [B,nc,H]

    # ---- intra-chunk (quadratic within chunk) ----
    # scores[q, k] = (C_q . B_k) * exp(cum_q - cum_k) * dt_k  for k <= q
    cb = jnp.einsum("bcqhn,bckhn->bchqk", Cf, Bf)
    cum_h = cum.transpose(0, 1, 3, 2)  # [B,nc,H,Q]
    ldiff = cum_h[..., :, None] - cum_h[..., None, :]  # [B,nc,H,Q,Q]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    # clamp BEFORE exp: the masked (upper) triangle has ldiff > 0 and can
    # overflow to inf; where(mask, inf, 0) is fine forward but its VJP is
    # 0 * inf = NaN (bit us in mamba2 training — see test_smoke_archs)
    ldiff = jnp.where(causal[None, None, None], ldiff, 0.0)
    decay_mat = jnp.where(causal[None, None, None], jnp.exp(ldiff), 0.0)
    w = cb * decay_mat * dtf.transpose(0, 1, 3, 2)[..., None, :]
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", w, xf)

    # ---- chunk states and inter-chunk recurrence ----
    # state contribution of chunk c: sum_k exp(cum_total - cum_k) dt_k B_k x_k^T
    state_w = jnp.exp(cum_total[:, :, None] - cum) * dtf  # [B,nc,Q,H]
    chunk_states = jnp.einsum("bckh,bckhn,bckhp->bchpn", state_w, Bf, xf)

    decay_chunk = jnp.exp(cum_total)  # [B,nc,H]
    h0 = (
        initial_state.astype(jnp.float32)
        if initial_state is not None
        else jnp.zeros((b, h, p, n), jnp.float32)
    )

    def inter(hprev, inp):
        dchunk, cstate = inp  # [B,H], [B,H,P,N]
        hnew = dchunk[:, :, None, None] * hprev + cstate
        return hnew, hprev

    (hfinal, h_prevs) = jax.lax.scan(
        inter,
        h0,
        (decay_chunk.transpose(1, 0, 2), chunk_states.transpose(1, 0, 2, 3, 4)),
    )
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)  # [B,nc,H,P,N] state before chunk

    y_inter = jnp.einsum(
        "bcqhn,bchpn,bcqh->bcqhp", Cf, h_prevs, jnp.exp(cum)
    )
    y = (y_intra + y_inter).reshape(b, tp, h, p)[:, :t]
    y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32).reshape(
        b, tp, h, p
    )[:, :t]
    return y.astype(x.dtype), hfinal


def ssd_decode_step(
    x: jnp.ndarray,  # [B, H, P] one token
    dt: jnp.ndarray,  # [B, H]
    A: jnp.ndarray,  # [H]
    B_: jnp.ndarray,  # [B, G, N]
    C: jnp.ndarray,  # [B, G, N]
    D: jnp.ndarray,  # [H]
    state: jnp.ndarray,  # [B, H, P, N]
):
    """Single-token SSD recurrence (decode): O(H·P·N) per token."""
    h = x.shape[1]
    g = B_.shape[1]
    rep = h // g
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bf = jnp.repeat(B_.astype(jnp.float32), rep, axis=1)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=1)
    decay = jnp.exp(dtf * A.astype(jnp.float32)[None])
    upd = jnp.einsum("bh,bhp,bhn->bhpn", dtf, xf, Bf)
    new_state = decay[:, :, None, None] * state.astype(jnp.float32) + upd
    y = jnp.einsum("bhn,bhpn->bhp", Cf, new_state)
    y = y + D.astype(jnp.float32)[None, :, None] * xf
    return y.astype(x.dtype), new_state


# --------------------------------------------------------------------------
# HSTU pointwise attention
# --------------------------------------------------------------------------

def hstu_attention(
    q, k, v, rel_bias, *, max_attn_len=None, lengths=None, impl: str = "auto"
):
    impl = _resolve(impl)
    if impl == "pallas":
        from repro.kernels import hstu_attention as _ha

        return _ha.hstu_attention_pallas(
            q, k, v, rel_bias, max_attn_len=max_attn_len, lengths=lengths,
            interpret=_interpret(),
        )
    return _ref.hstu_attention_ref(
        q, k, v, rel_bias, max_attn_len=max_attn_len, lengths=lengths
    )
