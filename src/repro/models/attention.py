"""Unified attention block: GQA (optionally windowed / qk-norm / bias) and
DeepSeek-V2 MLA, with static-KV-cache integration.

Cache discipline (the paper's CUDA-Graph enabler, §4.1.2, adapted to JAX):
buffers are allocated once at a static max length; per-slot ``lengths``
counters select the write position; decode attends under a validity mask.
A sliding-window config turns the buffer into a ring (size = window).

Modes:
- ``train``:   no cache; full causal flash attention.
- ``prefill``: writes the prompt's K/V into the cache (slot-aligned) and
               attends causally over the in-flight K/V.
- ``decode``:  one token per slot; vmapped dynamic_update_slice write at
               ``lengths % cache_len``; decode attention over the cache.
- ``mixed``:   paged caches only (chunked prefill), on PACKED lanes
               (:class:`MixedPack`): every row's lane 0 (a decode token, a
               chunk's first token or an idle row) plus the chunk rows'
               further lanes, ``B + C`` lanes in all. Each lane's K/V is
               written straight into its slot's blocks; lane 0 attends
               through the paged decode walk, the chunk lanes through
               chunk-query flash attention over their slots' prompt blocks
               (intra-chunk causal via the query positions).
- ``verify``:  speculative verification window: a per-slot ``t_new``
               window over ``[B, C]`` lanes, written into the blocks (or,
               on contiguous pools, by a masked window scatter), then
               window-query flash attention over each slot's gathered view;
               the caller keeps EVERY lane's logits — one full-model
               forward scores a whole drafted window per slot.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis.hotpath import hot_path
from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers as L

#: When set (by the launcher/dry-run --decode-sp), the decode path runs
#: sequence-parallel under shard_map: the KV cache shards its SEQUENCE
#: axis over 'model', each device computes flash-decode partials on its
#: shard, and an LSE-combine merges them — instead of GSPMD all-gathering
#: the whole cache (the §Perf-measured 270GB/step pathology on 405B).
SP_MESH: Optional[Mesh] = None


# --------------------------------------------------------------------------
# cache write helpers
# --------------------------------------------------------------------------

def write_prefill(buf: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
    """Write [B, T, ...] into [B, S, ...] at offset 0 (ring-aware: if
    T > S, keeps the last S entries at their ring slots)."""
    s, t = buf.shape[1], new.shape[1]
    if t <= s:
        pad = [(0, 0), (0, s - t)] + [(0, 0)] * (buf.ndim - 2)
        return jnp.pad(new, pad) if t < s else new
    # ring: keep last S tokens; token t sits at slot t % S
    tail = new[:, t - s:]
    slots = (jnp.arange(t - s, t)) % s
    return buf.at[:, slots].set(tail)


def write_decode(buf: jnp.ndarray, new: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Write one entry per batch row: buf [B,S,...], new [B,...], idx [B]."""

    def one(b, n, i):
        return jax.lax.dynamic_update_slice(b, n[None], (i,) + (0,) * (b.ndim - 1))

    return jax.vmap(one)(buf, new, idx)


def write_extend(buf: jnp.ndarray, new: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Write a [B, T, ...] block at per-batch offsets idx [B] (contiguous,
    non-ring; used by speculative/LayerSkip verification windows)."""

    def one(b, n, i):
        return jax.lax.dynamic_update_slice(b, n, (i,) + (0,) * (b.ndim - 1))

    return jax.vmap(one)(buf, new, idx)


def write_window(buf: jnp.ndarray, new: jnp.ndarray, lengths: jnp.ndarray,
                 t_new: jnp.ndarray) -> jnp.ndarray:
    """Masked multi-token scatter for verify windows on contiguous
    caches: new [B, C, ...] lands at per-slot offsets ``lengths`` [B],
    lane ``j`` written iff ``j < t_new[b]``. Invalid lanes are parked at
    position ``S`` and dropped by the scatter (mode="drop"), so — unlike
    :func:`write_extend`'s ``dynamic_update_slice`` — an overhanging
    window can never clamp-shift its start onto committed entries, and
    no two lanes ever target the same position."""
    s, c = buf.shape[1], new.shape[1]
    pos = lengths[:, None] + jnp.arange(c)[None]  # [B, C]
    pos = jnp.where(jnp.arange(c)[None] < t_new[:, None], pos, s)

    def one(b, n, p):
        return b.at[p].set(n.astype(b.dtype), mode="drop")

    return jax.vmap(one)(buf, new, pos)


def write_slot_row(buf: jnp.ndarray, row: jnp.ndarray, slot: jnp.ndarray) -> jnp.ndarray:
    """Replace one sequence slot of a pooled buffer: buf [B, ...] gets
    row [1, ...] at batch index ``slot`` (traced, so one executable serves
    every slot). This is the continuous-batching refill write: a freshly
    prefilled single-sequence cache row drops into the shared pool."""
    return jax.lax.dynamic_update_slice(
        buf, row.astype(buf.dtype), (slot,) + (0,) * (buf.ndim - 1)
    )


def valid_counts(lengths: jnp.ndarray, cache_len: int) -> jnp.ndarray:
    return jnp.minimum(lengths, cache_len)


# --------------------------------------------------------------------------
# paged (block-table) cache helpers
# --------------------------------------------------------------------------
# A paged layer cache carries K/V as physical blocks [num_blocks,
# block_size, ...] plus a per-slot block table ``bt`` [B, max_blocks]
# mapping logical block t // block_size -> physical block id (see
# core/kv_cache.py: "Block-table addressing"). Shapes stay static, so the
# decode step remains ONE compiled executable.

@hot_path
def paged_write_token(buf: jnp.ndarray, new: jnp.ndarray, bt: jnp.ndarray,
                      lengths: jnp.ndarray) -> jnp.ndarray:
    """Scatter one token per slot into a block pool: buf [NB, bs, ...],
    new [B, ...], at physical position (bt[b, lengths[b] // bs],
    lengths[b] % bs). Live slots own disjoint blocks, so their targets
    never collide; freed slots' tables are all-zero, so their (garbage)
    writes land in the reserved sink block 0."""
    bs = buf.shape[1]
    blk = jnp.clip(lengths // bs, 0, bt.shape[1] - 1)
    phys = jnp.take_along_axis(bt, blk[:, None], axis=1)[:, 0]  # [B]
    return buf.at[phys, lengths % bs].set(new.astype(buf.dtype))


def paged_gather(buf: jnp.ndarray, bt: jnp.ndarray) -> jnp.ndarray:
    """Materialize each slot's logical K/V view: buf [NB, bs, ...] gathered
    through bt [B, MB] -> [B, MB * bs, ...]. The gather is a transient
    activation (same read set the contiguous decode touches); the memory
    the pool *reserves* is only ``NB * bs`` tokens. Single-token decode
    gathers one tile of blocks at a time through it (see
    :func:`paged_decode_attention`); the mixed step gathers only its chunk
    rows' prompt blocks, and the verify window every slot's whole view."""
    b, mb = bt.shape
    g = buf[bt]  # [B, MB, bs, ...]
    return g.reshape((b, mb * buf.shape[1]) + buf.shape[2:])


@hot_path
def paged_write_chunk(buf: jnp.ndarray, new: jnp.ndarray, bt: jnp.ndarray,
                      lengths: jnp.ndarray, t_new: jnp.ndarray) -> jnp.ndarray:
    """Scatter one per-slot K/V chunk straight into the block pool (chunked
    prefill): buf [NB, bs, ...], new [B, C, ...]. Lane ``j`` of slot ``b``
    lands at logical position ``lengths[b] + j`` — physical block
    ``bt[b, pos // bs]``, offset ``pos % bs`` — iff ``j < t_new[b]``.
    Invalid lanes (a final partial chunk's padding, decode rows beyond lane
    0, idle rows with ``t_new == 0``) are routed to the reserved sink block
    0, the same rule that makes freed slots' decode writes harmless. Live
    slots own disjoint blocks and each slot's valid lanes hit distinct
    positions, so valid writes never collide. No dense ``pad_to`` row is
    ever materialized: the chunk goes from the layer's K/V projections
    directly into the slot's blocks."""
    bs = buf.shape[1]
    c = new.shape[1]
    pos = lengths[:, None] + jnp.arange(c)[None]  # [B, C] logical positions
    blk = jnp.clip(pos // bs, 0, bt.shape[1] - 1)
    phys = jnp.take_along_axis(bt, blk, axis=1)  # [B, C]
    valid = jnp.arange(c)[None] < t_new[:, None]
    phys = jnp.where(valid, phys, 0)
    return buf.at[phys, pos % bs].set(new.astype(buf.dtype))


#: Most keys a slot's tile holds in one iteration of the paged decode
#: walk: the key block of ``decode_attention_pallas``, 32 blocks of 16.
TILE_KEYS = 512


def walk_tile_blocks(block_size: int, max_blocks: int) -> int:
    """Blocks a slot's tile gathers in one iteration of the decode walk:
    the table cut into the fewest tiles of at most ``TILE_KEYS`` keys, as
    even as they come, so a full slot's last tile is not mostly sink."""
    n = -(-max_blocks // max(1, TILE_KEYS // block_size))
    return -(-max_blocks // n)


def walk_tiles(n_valid, live, block_size: int, max_blocks: int):
    """Tiles the paged decode walk runs: enough to cover the deepest LIVE
    row's keys ``n_valid`` [B]. ``live`` [B] is ``bt[:, 0] != 0``: the pool
    never hands out the sink block 0 and zeroes a freed row, whose device
    counter drifts upward every step until the slot is reassigned, so a
    free row never sets the bound. Plain arithmetic, so the scheduler
    applies the same rule to its host mirrors (numpy) as the step does on
    the device (jnp)."""
    blocks = (n_valid + block_size - 1) // block_size * live
    return -(-blocks.max() // walk_tile_blocks(block_size, max_blocks))


@hot_path
def paged_decode_attention(
    q: jnp.ndarray,  # [B, Hq, D]
    kbuf: jnp.ndarray,  # [NB, bs, Hkv, D] or [NB, bs, D] (shared-head latent)
    vbuf: Optional[jnp.ndarray],  # like kbuf, or None: V = K[..., :v_dim]
    bt: jnp.ndarray,  # [B, MB] block table
    n_valid: jnp.ndarray,  # [B] valid cached tokens per slot
    *,
    scale: Optional[float] = None,
    v_dim: Optional[int] = None,
) -> jnp.ndarray:
    """Flash-decode straight off the physical block pool, walking only the
    live part of the table: a loop over tiles of ``walk_tile_blocks``
    logical blocks, each gathered per slot as a [B, TILE_KEYS, ...]
    scratch and folded into a running online-softmax state (acc, m, l)
    by the LSE rule of ``ops.merge_partial_attention``. The trip count is
    traced (``walk_tiles``: the deepest live row), so one executable
    serves every depth. Free rows (``bt[:, 0] == 0``) are masked whole;
    their output is zero and never read. ``vbuf=None`` with ``v_dim``
    serves MLA's absorbed latent, where V is the leading slice of the
    cached K."""
    bs = kbuf.shape[1]
    b, mb = bt.shape
    g = walk_tile_blocks(bs, mb)
    # sink entries to a whole number of tiles: a clamped slice at the last
    # tile would shift its start back and count blocks twice
    bt = jnp.pad(bt, ((0, 0), (0, -(-mb // g) * g - mb)))
    live = bt[:, 0] != 0
    n_valid = jnp.where(live, n_valid, 0)
    keys = jnp.arange(g * bs)

    def body(j, carry):
        phys = jax.lax.dynamic_slice_in_dim(bt, j * g, g, axis=1)  # [B, G]
        kj = paged_gather(kbuf, phys)  # [B, G * bs, ...]
        vj = paged_gather(vbuf, phys) if vbuf is not None else kj[..., :v_dim]
        if kj.ndim == 3:  # shared-head latent: add the Hkv=1 axis
            kj, vj = kj[:, :, None, :], vj[:, :, None, :]
        k_valid = (j * g * bs + keys)[None, :] < n_valid[:, None]
        part = ops.decode_attention_partial(q, kj, vj, k_valid, scale=scale)
        return ops.merge_partial_attention(carry, part)

    hq = q.shape[1]
    dv = vbuf.shape[-1] if vbuf is not None else v_dim
    init = (jnp.zeros((b, hq, dv), jnp.float32),
            jnp.full((b, hq), ops.NEG_INF, jnp.float32),
            jnp.zeros((b, hq), jnp.float32))
    acc, _, l = jax.lax.fori_loop(0, walk_tiles(n_valid, live, bs, mb), body,
                                  init)
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


class MixedPack(NamedTuple):
    """Where the ``N = B + C`` computed lanes of a packed mixed step come
    from, and how their attention is laid out. Lane ``b < B`` is row
    ``b``'s lane 0: its decode token, its chunk's first token, or an idle
    row (``t_new = 0``). Lanes ``B ..`` hold the chunk rows' lanes
    ``1 .. t_new - 1``, row after row; past their total they are padding.
    Chunk attention runs on ``K`` rows, one per chunk row (``t_new >= 2``),
    each holding that chunk's queries in its first lanes and the first
    ``P`` blocks of the slot's table as its keys."""

    row: jnp.ndarray  # [N] source row of each lane
    col: jnp.ndarray  # [N] lane within that row
    pos: jnp.ndarray  # [N] logical position the lane writes and attends at
    phys: jnp.ndarray  # [N] physical block of its write (0 = the sink)
    last: jnp.ndarray  # [B] lane that holds each row's last valid token
    n_valid: jnp.ndarray  # [B] keys each lane 0 attends
    tables: jnp.ndarray  # [K, P] the chunk rows' prompt blocks
    q_lane: jnp.ndarray  # [K, C] lane of each chunk query
    q_pos: jnp.ndarray  # [K, C] its position (-1: padding, sees no key)
    k_valid: jnp.ndarray  # [K, P * bs] keys written by the end of the step
    out_at: jnp.ndarray  # [C] index into the [K * C] chunk outputs


def mixed_pack(t_new: jnp.ndarray, lengths: jnp.ndarray, bt: jnp.ndarray,
               *, width: int, block_size: int, chunk_rows: int,
               prompt_blocks: int) -> MixedPack:
    """The lane layout of one mixed step from its per-row widths ``t_new``
    [B] and write positions ``lengths`` [B]: ``width`` (C) is the step's
    prefill budget, which bounds the chunk lanes' total; ``chunk_rows``
    (K) bounds the rows with ``t_new >= 2`` and ``prompt_blocks`` (P) the
    blocks a chunk's keys can lie in (a chunk belongs to a prompt, and
    prompts fill a slot's first blocks). The packer
    (core/prefill.ChunkedPrefill.plan) guarantees all three."""
    b, c, bs = t_new.shape[0], width, block_size
    mb = bt.shape[1]
    i32 = jnp.int32
    extra = jnp.maximum(t_new - 1, 0)  # a row's lanes past lane 0
    ends = jnp.cumsum(extra)
    starts = ends - extra
    lane = jnp.arange(c, dtype=i32)
    # the row of chunk lane i: the first row whose lanes end past i
    src = jnp.minimum(jnp.sum(ends[None, :] <= lane[:, None], axis=1), b - 1)
    live = lane < ends[-1]
    ccol = jnp.where(live, lane - starts[src] + 1, 0)
    row = jnp.concatenate([jnp.arange(b, dtype=i32), src.astype(i32)])
    col = jnp.concatenate([jnp.zeros((b,), i32), ccol.astype(i32)])
    valid = jnp.concatenate([t_new > 0, live])
    pos = lengths[row] + col
    blk = jnp.clip(pos // bs, 0, mb - 1)
    phys = jnp.where(valid, bt[row, blk], 0)
    last = jnp.where(t_new > 1, b + ends - 1, jnp.arange(b)).astype(i32)
    is_chunk = t_new > 1
    crow = jnp.nonzero(is_chunk, size=chunk_rows, fill_value=0)[0]
    used = jnp.arange(chunk_rows) < jnp.sum(is_chunk)
    q_ok = used[:, None] & (lane[None, :] < extra[crow][:, None])
    q_lane = jnp.where(q_ok, b + starts[crow][:, None] + lane[None, :], 0)
    q_pos = jnp.where(q_ok, lengths[crow][:, None] + 1 + lane[None, :], -1)
    keys = jnp.arange(prompt_blocks * bs)
    k_valid = used[:, None] & (keys[None, :]
                               < (lengths + t_new)[crow][:, None])
    rank = jnp.cumsum(is_chunk) - 1  # a chunk row's attention row
    out_at = jnp.where(live, rank[src] * c + ccol - 1, 0)
    return MixedPack(
        row=row, col=col, pos=pos, phys=phys, last=last,
        n_valid=valid_counts(lengths + 1, mb * bs),
        tables=bt[crow, :prompt_blocks], q_lane=q_lane.astype(i32),
        q_pos=q_pos.astype(i32), k_valid=k_valid, out_at=out_at.astype(i32),
    )


@hot_path
def paged_write_lanes(buf: jnp.ndarray, new: jnp.ndarray,
                      pack: MixedPack) -> jnp.ndarray:
    """Scatter the packed lanes' K/V [N, ...] into the block pool at their
    physical positions; padding lanes and idle rows land in the sink
    block 0. Valid lanes are distinct (slot, position) pairs in blocks
    their slots own, so they never collide."""
    return buf.at[pack.phys, pack.pos % buf.shape[1]].set(new.astype(buf.dtype))


@hot_path
def paged_mixed_attention(
    q: jnp.ndarray,  # [N, Hq, D] packed lanes
    kbuf: jnp.ndarray,  # [NB, bs, Hkv, D] or [NB, bs, D] (shared-head latent)
    vbuf: Optional[jnp.ndarray],  # like kbuf, or None: V = K[..., :v_dim]
    bt: jnp.ndarray,  # [B, MB] block table
    pack: MixedPack,
    *,
    scale: Optional[float] = None,
    v_dim: Optional[int] = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Attention of a packed mixed step, after the step's writes. Lane 0
    of each row is one query at ``lengths`` over ``lengths + 1`` keys: the
    paged decode walk. The chunk lanes attend their slot's prompt blocks
    only, one flash-attention row per chunk row. Returns [N, Hq, Dv]."""
    b = bt.shape[0]
    head = paged_decode_attention(q[:b], kbuf, vbuf, bt, pack.n_valid,
                                  scale=scale, v_dim=v_dim)
    kc = paged_gather(kbuf, pack.tables)  # [K, P * bs, ...]
    vc = paged_gather(vbuf, pack.tables) if vbuf is not None \
        else kc[..., :v_dim]
    if kc.ndim == 3:  # shared-head latent: add the Hkv=1 axis
        kc, vc = kc[:, :, None, :], vc[:, :, None, :]
    k_rows, s = pack.k_valid.shape
    out = ops.flash_attention(
        q[pack.q_lane], kc, vc, q_positions=pack.q_pos,
        k_positions=jnp.broadcast_to(jnp.arange(s)[None], (k_rows, s)),
        causal=True, k_valid=pack.k_valid, scale=scale, impl=impl,
    )  # [K, C, Hq, Dv]
    tail = out.reshape((-1,) + out.shape[2:])[pack.out_at]
    return jnp.concatenate([head, tail.astype(head.dtype)])


def _sp_decode(cache, k_new, v_new, q, lengths):
    """Sequence-parallel flash decode under shard_map.

    Cache K/V [B, S, Hkv, D] shard the S axis over 'model'; each device:
    (1) writes the new token's K/V iff it owns slot ``lengths``,
    (2) computes flash-decode partials (acc, m, l) over its local shard,
    (3) all-gathers the tiny per-head partials and LSE-combines.
    Collective cost per layer: 3 × [B, Hq, (D+2)] floats instead of the
    GSPMD baseline's full-cache all-gather.
    """
    mesh = SP_MESH
    msize = mesh.shape["model"]
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = daxes if daxes else None
    kv_spec = P(bspec, "model", None, None)
    vec_spec = P(bspec, None, None)

    def local(ck, cv, kn, vn, qv, ln):
        s_loc = ck.shape[1]
        shard = jax.lax.axis_index("model")
        local_idx = ln - shard * s_loc
        in_range = (local_idx >= 0) & (local_idx < s_loc)
        wi = jnp.clip(local_idx, 0, s_loc - 1)
        # conditional single-row write: non-owners rewrite the row they
        # already hold (a full-buffer where() would triple cache traffic —
        # §Perf round 2 measured it at ~3x the live bytes)
        def row_at(buf, i):
            return jax.vmap(
                lambda b, j: jax.lax.dynamic_slice(
                    b, (j,) + (0,) * (b.ndim - 1), (1,) + b.shape[1:]
                )[0]
            )(buf, i)

        sel = in_range[:, None, None]
        ck2 = write_decode(ck, jnp.where(sel, kn, row_at(ck, wi)), wi)
        cv2 = write_decode(cv, jnp.where(sel, vn, row_at(cv, wi)), wi)

        base = shard * s_loc
        n_valid = ln + 1
        k_valid = (base + jnp.arange(s_loc))[None, :] < n_valid[:, None]
        acc, m, l = ops.decode_attention_partial(qv, ck2, cv2, k_valid)
        accs = jax.lax.all_gather(acc, "model")  # [msize, B, Hq, D]
        ms = jax.lax.all_gather(m, "model")
        ls = jax.lax.all_gather(l, "model")
        out = ops.combine_partial_attention(accs, ms, ls)
        return out.astype(qv.dtype), ck2, cv2

    out, ck2, cv2 = jax.shard_map(
        local, mesh=mesh,
        in_specs=(kv_spec, kv_spec, vec_spec, vec_spec, vec_spec, P(bspec)),
        out_specs=(vec_spec, kv_spec, kv_spec),
        check_vma=False,
    )(cache["k"], cache["v"], k_new, v_new, q, lengths)
    return out, {"k": ck2, "v": cv2}


# --------------------------------------------------------------------------
# standard GQA attention
# --------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig):
    dt = L.param_dtype(cfg)
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d, hq * dh, dt, bias=cfg.qkv_bias),
        "wk": L.dense_init(ks[1], d, hkv * dh, dt, bias=cfg.qkv_bias),
        "wv": L.dense_init(ks[2], d, hkv * dh, dt, bias=cfg.qkv_bias),
        "wo": L.dense_init(ks[3], hq * dh, d, dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.rmsnorm_init(dh, dt)
        p["k_norm"] = L.rmsnorm_init(dh, dt)
    return p


def init_attention_cache(cfg: ModelConfig, batch: int, max_len: int, window=None):
    dt = L.param_dtype(cfg)
    s = min(max_len, window) if window else max_len
    shape = (batch, s, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def attention(
    cfg: ModelConfig,
    p,
    x: jnp.ndarray,  # [B, T, d]
    *,
    positions: jnp.ndarray,  # [B, T]
    lengths: Optional[jnp.ndarray],  # [B] context size BEFORE this call
    cache: Optional[dict],
    mode: str,
    window: Optional[int] = None,
    impl: str = "auto",
    bidirectional: bool = False,
    t_new: Optional[jnp.ndarray] = None,  # [B] window widths (verify mode)
    pack: Optional[MixedPack] = None,  # lane layout (mixed mode)
) -> Tuple[jnp.ndarray, Optional[dict]]:
    b, t, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    q = L.dense(p["wq"], x).reshape(b, t, hq, dh)
    k = L.dense(p["wk"], x).reshape(b, t, hkv, dh)
    v = L.dense(p["wv"], x).reshape(b, t, hkv, dh)
    if cfg.qk_norm:
        q = L.rmsnorm(p["q_norm"], q, cfg.rmsnorm_eps)
        k = L.rmsnorm(p["k_norm"], k, cfg.rmsnorm_eps)
    if not bidirectional:  # encoder stacks skip RoPE (whisper uses sinusoid)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)

    if mode == "train" or (mode == "prefill" and cache is None):
        out = ops.flash_attention(
            q, k, v, q_positions=positions, k_positions=positions,
            causal=not bidirectional, window=window, impl=impl,
        )
        new_cache = None
    elif mode == "prefill":
        new_cache = {
            "k": write_prefill(cache["k"], k),
            "v": write_prefill(cache["v"], v),
        }
        out = ops.flash_attention(
            q, k, v, q_positions=positions, k_positions=positions,
            causal=not bidirectional, window=window, impl=impl,
        )
    elif mode in ("decode", "mixed", "verify") and "bt" in cache:
        if window is not None:
            raise NotImplementedError("paged cache unsupported on ring/window")
        if SP_MESH is not None:
            raise NotImplementedError(
                "paged decode unsupported under sequence-parallel shard_map"
            )
        bt = cache["bt"]  # [B, max_blocks] int32
        bs = cache["k"].shape[1]
        if mode == "decode":
            new_cache = {
                "k": paged_write_token(cache["k"], k[:, 0], bt, lengths),
                "v": paged_write_token(cache["v"], v[:, 0], bt, lengths),
                "bt": bt,
            }
            n_valid = valid_counts(lengths + 1, bt.shape[1] * bs)
            out = paged_decode_attention(
                q[:, 0], new_cache["k"], new_cache["v"], bt, n_valid,
            )[:, None]
        elif mode == "mixed":
            # packed lanes [N, 1]: every lane's K/V lands before any lane
            # attends, so a chunk sees its own earlier tokens
            new_cache = {
                "k": paged_write_lanes(cache["k"], k[:, 0], pack),
                "v": paged_write_lanes(cache["v"], v[:, 0], pack),
                "bt": bt,
            }
            out = paged_mixed_attention(
                q[:, 0], new_cache["k"], new_cache["v"], bt, pack, impl=impl,
            )[:, None]
        else:
            # verify window: write each slot's window into its blocks, then
            # window-query flash attention over the slot's gathered logical
            # view — prior blocks AND the window just written, intra-window
            # causality via the query positions.
            new_cache = {
                "k": paged_write_chunk(cache["k"], k, bt, lengths, t_new),
                "v": paged_write_chunk(cache["v"], v, bt, lengths, t_new),
                "bt": bt,
            }
            s_log = bt.shape[1] * bs
            kpos = jnp.broadcast_to(jnp.arange(s_log)[None], (b, s_log))
            k_valid = jnp.arange(s_log)[None] < (lengths + t_new)[:, None]
            out = ops.flash_attention(
                q, paged_gather(new_cache["k"], bt),
                paged_gather(new_cache["v"], bt),
                q_positions=positions, k_positions=kpos, causal=True,
                k_valid=k_valid, impl=impl,
            )
    elif mode == "mixed":
        raise ValueError("mixed mode requires a paged (block-table) cache")
    elif mode == "verify":
        # contiguous verify window: masked multi-token write at the slot
        # offsets, then window-query flash over the cache — intra-window
        # causality via the query positions, per-slot width via t_new.
        if window is not None:
            raise NotImplementedError("verify unsupported on ring/window caches")
        if SP_MESH is not None:
            raise NotImplementedError(
                "verify unsupported under sequence-parallel shard_map"
            )
        s = cache["k"].shape[1]
        new_cache = {
            "k": write_window(cache["k"], k, lengths, t_new),
            "v": write_window(cache["v"], v, lengths, t_new),
        }
        kpos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        k_valid = jnp.arange(s)[None] < (lengths + t_new)[:, None]
        out = ops.flash_attention(
            q, new_cache["k"], new_cache["v"], q_positions=positions,
            k_positions=kpos, causal=True, k_valid=k_valid, impl=impl,
        )
    elif mode == "decode":
        if SP_MESH is not None and window is None:
            out, new_cache = _sp_decode(cache, k[:, 0], v[:, 0], q[:, 0], lengths)
            out = out[:, None]
        else:
            s = cache["k"].shape[1]
            idx = lengths % s
            new_cache = {
                "k": write_decode(cache["k"], k[:, 0], idx),
                "v": write_decode(cache["v"], v[:, 0], idx),
            }
            n_valid = valid_counts(lengths + 1, s)
            out = ops.decode_attention(
                q[:, 0], new_cache["k"], new_cache["v"], n_valid, impl=impl
            )[:, None]
    elif mode == "extend":
        if "bt" in cache:
            raise NotImplementedError("extend unsupported on paged caches")
        s = cache["k"].shape[1]
        if window is not None:
            # extend over a ring buffer would need wraparound scatter;
            # speculative windows are short — engines exclude ring archs.
            raise NotImplementedError("extend unsupported on ring/window caches")
        new_cache = {
            "k": write_extend(cache["k"], k, lengths),
            "v": write_extend(cache["v"], v, lengths),
        }
        kpos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        k_valid = jnp.arange(s)[None] < (lengths + t)[:, None]
        out = ops.flash_attention(
            q, new_cache["k"], new_cache["v"], q_positions=positions,
            k_positions=kpos, causal=not bidirectional, window=window,
            k_valid=k_valid, impl=impl,
        )
    else:
        raise ValueError(f"unknown mode {mode!r}")

    return L.dense(p["wo"], out.reshape(b, t, hq * dh)), new_cache


# --------------------------------------------------------------------------
# DeepSeek-V2 Multi-head Latent Attention
# --------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig):
    m = cfg.mla
    dt = L.param_dtype(cfg)
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)
    p = {
        "kv_down": L.dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_dim, dt),
        "kv_norm": L.rmsnorm_init(m.kv_lora_rank, dt),
        "kv_up": L.dense_init(
            ks[3], m.kv_lora_rank, h * (m.qk_nope_dim + m.v_head_dim), dt
        ),
        "wo": L.dense_init(ks[4], h * m.v_head_dim, d, dt),
    }
    if m.q_lora_rank > 0:
        p["q_down"] = L.dense_init(ks[0], d, m.q_lora_rank, dt)
        p["q_norm"] = L.rmsnorm_init(m.q_lora_rank, dt)
        p["q_up"] = L.dense_init(
            ks[1], m.q_lora_rank, h * (m.qk_nope_dim + m.qk_rope_dim), dt
        )
    else:
        p["q_up"] = L.dense_init(ks[1], d, h * (m.qk_nope_dim + m.qk_rope_dim), dt)
    return p


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Single fused latent buffer [B, S, kv_lora + rope]: the absorbed
    decode's K is the whole buffer and its V is the [:kv_lora] slice — a
    separate (c_kv, k_rope) pair forced a full-cache concat every decode
    step (§Perf P4)."""
    m = cfg.mla
    dt = L.param_dtype(cfg)
    return {
        "latent": jnp.zeros((batch, max_len, m.kv_lora_rank + m.qk_rope_dim), dt),
    }


def _mla_qkv(cfg, p, x, positions):
    """Shared query path + latent K/V computation."""
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.n_heads
    if "q_down" in p:
        cq = L.rmsnorm(p["q_norm"], L.dense(p["q_down"], x), cfg.rmsnorm_eps)
    else:
        cq = x
    qall = L.dense(p["q_up"], cq).reshape(b, t, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = jnp.split(qall, [m.qk_nope_dim], axis=-1)
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)

    kv = L.dense(p["kv_down"], x)
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = L.rmsnorm(p["kv_norm"], c_kv, cfg.rmsnorm_eps)
    k_rope = L.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def _mla_expand_kv(cfg, p, c_kv):
    """Up-project latents to per-head K_nope / V (prefill/train path)."""
    m = cfg.mla
    b, s, _ = c_kv.shape
    h = cfg.n_heads
    kv = L.dense(p["kv_up"], c_kv).reshape(b, s, h, m.qk_nope_dim + m.v_head_dim)
    return jnp.split(kv, [m.qk_nope_dim], axis=-1)  # k_nope, v


def mla_attention(
    cfg: ModelConfig,
    p,
    x: jnp.ndarray,
    *,
    positions: jnp.ndarray,
    lengths: Optional[jnp.ndarray],
    cache: Optional[dict],
    mode: str,
    impl: str = "auto",
    t_new: Optional[jnp.ndarray] = None,  # [B] window widths (verify mode)
    pack: Optional[MixedPack] = None,  # lane layout (mixed mode)
) -> Tuple[jnp.ndarray, Optional[dict]]:
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.n_heads
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(cfg, p, x, positions)

    if mode in ("train", "prefill"):
        k_nope, v = _mla_expand_kv(cfg, p, c_kv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None], (b, t, h, m.qk_rope_dim))],
            axis=-1,
        )
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = ops.flash_attention(
            q, k, v, q_positions=positions, k_positions=positions,
            causal=True, scale=scale, impl=impl,
        )
        new_cache = None
        if mode == "prefill" and cache is not None:
            new_cache = {
                "latent": write_prefill(
                    cache["latent"], jnp.concatenate([c_kv, k_rope], axis=-1)
                ),
            }
    elif mode in ("decode", "extend", "mixed", "verify"):
        paged = "bt" in cache
        if paged and mode == "extend":
            raise NotImplementedError("extend unsupported on paged caches")
        if mode == "mixed" and not paged:
            raise ValueError("mixed mode requires a paged (block-table) cache")
        latent_new = jnp.concatenate([c_kv, k_rope], axis=-1)  # tiny: [B,T,r+rope]
        lat = None
        if paged and mode == "decode":
            bt = cache["bt"]
            new_cache = {
                "latent": paged_write_token(
                    cache["latent"], latent_new[:, 0], bt, lengths
                ),
                "bt": bt,
            }
            s = bt.shape[1] * cache["latent"].shape[1]  # logical view length
        elif paged and mode == "mixed":  # packed lanes into the blocks
            bt = cache["bt"]
            new_cache = {
                "latent": paged_write_lanes(
                    cache["latent"], latent_new[:, 0], pack
                ),
                "bt": bt,
            }
        elif paged:  # verify: per-slot latent window into the blocks
            bt = cache["bt"]
            new_cache = {
                "latent": paged_write_chunk(
                    cache["latent"], latent_new, bt, lengths, t_new
                ),
                "bt": bt,
            }
            lat = paged_gather(new_cache["latent"], bt)
            s = lat.shape[1]
        elif mode == "decode":
            s = cache["latent"].shape[1]
            idx = lengths % s
            new_cache = {
                "latent": write_decode(cache["latent"], latent_new[:, 0], idx),
            }
            lat = new_cache["latent"]
        elif mode == "verify":  # contiguous verify: masked window write
            s = cache["latent"].shape[1]
            new_cache = {
                "latent": write_window(cache["latent"], latent_new, lengths, t_new),
            }
            lat = new_cache["latent"]
        else:
            s = cache["latent"].shape[1]
            new_cache = {
                "latent": write_extend(cache["latent"], latent_new, lengths),
            }
            lat = new_cache["latent"]
        # Absorbed attention (DeepSeek-V2 §2.1): fold kv_up's K-half into
        # the query so attention runs directly against the latent cache —
        # scores = [q_nope W_uk ; q_rope] . [c_kv ; k_rope]. The latent
        # plays the role of a single shared KV "head" (Hkv=1 GQA).
        w_up = p["kv_up"]["w"].reshape(m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim)
        w_uk = w_up[:, :, : m.qk_nope_dim]  # [r, H, nope]
        w_uv = w_up[:, :, m.qk_nope_dim:]  # [r, H, v]
        q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, w_uk)  # [B,T,H,r]
        q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)  # [B,T,H,r+rope]
        if mode == "decode" and paged:
            # blockwise flash-decode off the latent block pool (no gathered
            # [B, MB*bs, r+rope] transient); V is the latent's leading slice
            n_valid = valid_counts(lengths + 1, s)
            ctx_lat = paged_decode_attention(
                q_eff[:, 0], new_cache["latent"], None, bt, n_valid,
                scale=scale, v_dim=m.kv_lora_rank,
            )[:, None]  # [B,1,H,r]
        elif mode == "mixed":
            ctx_lat = paged_mixed_attention(
                q_eff[:, 0], new_cache["latent"], None, bt, pack,
                scale=scale, v_dim=m.kv_lora_rank, impl=impl,
            )[:, None]  # [N,1,H,r]
        elif mode == "decode":
            k_eff = lat  # K = whole latent buffer (no copy)
            v_eff = lat[:, :, : m.kv_lora_rank]  # V = slice
            n_valid = valid_counts(lengths + 1, s)
            ctx_lat = ops.decode_attention(
                q_eff[:, 0], k_eff[:, :, None, :], v_eff[:, :, None, :],
                n_valid, scale=scale, impl=impl,
            )[:, None]  # [B,1,H,r]
        else:  # extend / verify: window-query flash over the logical view
            k_eff = lat  # paged verify: gathered view (amortized over window)
            v_eff = lat[:, :, : m.kv_lora_rank]  # V = slice
            ext = t_new if mode == "verify" else t  # per-slot or uniform
            kpos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            k_valid = jnp.arange(s)[None] < (lengths + ext)[:, None]
            ctx_lat = ops.flash_attention(
                q_eff, k_eff[:, :, None, :], v_eff[:, :, None, :],
                q_positions=positions, k_positions=kpos, causal=True,
                k_valid=k_valid, scale=scale, impl=impl,
            )  # [B,T,H,r]
        out = jnp.einsum(
            "bthr,rhv->bthv", ctx_lat.astype(jnp.float32), w_uv.astype(jnp.float32)
        ).astype(x.dtype)
    else:
        raise ValueError(f"unknown mode {mode!r}")

    return L.dense(p["wo"], out.reshape(b, t, h * m.v_head_dim)), new_cache
