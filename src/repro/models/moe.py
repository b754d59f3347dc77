"""Mixture-of-experts FFN with capacity-based sort-free dispatch.

FLOPs-honest sparse dispatch (no dense one-hot einsum over [N, E, C]):
tokens are scattered into a per-expert slot buffer of static capacity,
experts run as one batched einsum over [E, C, d], and outputs scatter-add
back with router weights. Expert-parallel: the leading E axis of all expert
weights and activations shards over the ``model`` mesh axis; the
gather/scatter between data-sharded tokens and expert-sharded buffers is
where XLA inserts the all-to-all (the paper's "communication requirement"
axis, Fig 1 — and this repo's designated collective-bound §Perf target).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L

#: When set (only tests set it), MoE layers route through the
#: shard_map expert-parallel path with explicit all-to-all dispatch
#: (moe_ffn_ep) instead of the GSPMD dense-dispatch baseline. The baseline
#: lets XLA all-gather every token to every expert shard AND replicates
#: expert compute across the data axis — the §Perf-measured pathology this
#: path removes.
EP_MESH: Optional[Mesh] = None

def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    dt = L.param_dtype(cfg)
    d, e, f = cfg.d_model, m.n_experts, m.d_ff_expert
    ks = jax.random.split(key, 5)
    scale = d ** -0.5
    p = {
        "router": {
            "w": (jax.random.normal(ks[0], (d, e), jnp.float32) * scale).astype(
                jnp.float32  # router kept in f32 (loss-bearing, tiny)
            )
        },
        "w1": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale).astype(dt),
        "w3": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale).astype(dt),
        "w2": (jax.random.normal(ks[3], (e, f, d), jnp.float32) * f ** -0.5).astype(dt),
    }
    if m.n_shared_experts:
        p["shared"] = L.ffn_init(ks[4], d, m.n_shared_experts * f, dt)
    return p


def _position_in_expert(flat_e: jnp.ndarray, e: int, method: str) -> jnp.ndarray:
    """Rank of each (token, k) assignment within its expert's queue.

    "cumsum": one-hot cumulative count — simple, but XLA lowers the cumsum
    over the token axis to a quadratic reduce-window (measured: costs more
    FLOPs than every expert GEMM combined at 32k-token scale).
    "sort": stable argsort groups assignments by expert; the rank is the
    index within the group (O(N log N)) — the §Perf-optimized path.
    """
    nk = flat_e.shape[0]
    if method == "cumsum":
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # [N*K, E]
        pos = jnp.cumsum(onehot, axis=0) - 1
        return jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    assert method == "sort", method
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = jnp.take(flat_e, order)
    starts = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    pos_sorted = jnp.arange(nk, dtype=jnp.int32) - jnp.take(
        starts, sorted_e
    ).astype(jnp.int32)
    return jnp.zeros((nk,), jnp.int32).at[order].set(pos_sorted)


def expert_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(m.capacity_factor * n_tokens * m.top_k / m.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8, floor 8


def moe_ffn(cfg: ModelConfig, p, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, T, d] -> (out [B, T, d], aux_loss scalar)."""
    if EP_MESH is not None:
        return moe_ffn_ep(cfg, p, x)
    m = cfg.moe
    b, t, d = x.shape
    n = b * t
    e, k = m.n_experts, m.top_k
    xf = x.reshape(n, d)

    logits = xf.astype(jnp.float32) @ p["router"]["w"]  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, k)  # [N, K]
    if m.normalize_router_weights:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance auxiliary loss.
    me = probs.mean(axis=0)  # mean router prob per expert
    one_hot_top1 = jax.nn.one_hot(top_ids[:, 0], e, dtype=jnp.float32)
    ce = one_hot_top1.mean(axis=0)  # fraction of tokens routed (top-1)
    aux = e * jnp.sum(me * ce) * m.router_aux_weight

    cap = expert_capacity(n, cfg)
    flat_e = top_ids.reshape(n * k)  # expert of each (token, k) slot
    flat_w = top_w.reshape(n * k)
    flat_tok = jnp.repeat(jnp.arange(n), k)
    pos_in_e = _position_in_expert(flat_e, e, cfg.moe.dispatch_rank)
    keep = pos_in_e < cap
    slot = jnp.where(keep, flat_e * cap + pos_in_e, e * cap)  # overflow -> dummy

    tok_of_slot = jnp.zeros((e * cap + 1,), jnp.int32).at[slot].set(flat_tok)
    w_of_slot = jnp.zeros((e * cap + 1,), jnp.float32).at[slot].set(flat_w)
    used = jnp.zeros((e * cap + 1,), jnp.float32).at[slot].set(1.0)
    tok_of_slot, w_of_slot, used = (
        tok_of_slot[: e * cap],
        w_of_slot[: e * cap],
        used[: e * cap],
    )

    xe = jnp.take(xf, tok_of_slot, axis=0).reshape(e, cap, d)
    xe = xe * used.reshape(e, cap, 1).astype(xe.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w1"])) * jnp.einsum(
        "ecd,edf->ecf", xe, p["w3"]
    )
    ye = jnp.einsum("ecf,efd->ecd", h, p["w2"]).reshape(e * cap, d)

    out = jnp.zeros((n, d), jnp.float32).at[tok_of_slot].add(
        ye.astype(jnp.float32) * (w_of_slot * used)[:, None]
    )
    out = out.astype(x.dtype)
    if "shared" in p:
        out = out + L.ffn(p["shared"], xf)
    return out.reshape(b, t, d), aux


# --------------------------------------------------------------------------
# shard_map expert parallelism (beyond-paper §Perf path)
# --------------------------------------------------------------------------

def _round8(x: int) -> int:
    return max(8, -(-x // 8) * 8)


def moe_ffn_ep(cfg: ModelConfig, p, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """GShard-style expert parallelism under shard_map.

    Tokens stay data-sharded; each device routes its LOCAL tokens, packs
    per-destination-shard send buffers, exchanges them with ONE all-to-all
    over the 'model' axis, runs its local experts, and all-to-alls results
    back. Vs the GSPMD baseline this (a) removes the all-gather of every
    token to every expert shard, and (b) divides expert FLOPs by the data
    axis (the baseline replicates the global expert queues per data row).
    """
    mesh = EP_MESH
    assert mesh is not None
    m = cfg.moe
    e = m.n_experts
    msize = mesh.shape["model"]
    e_loc = e // msize
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    pspec = {
        "router": {"w": P()},
        "w1": P("model", None, None),
        "w3": P("model", None, None),
        "w2": P("model", None, None),
    }
    if "shared" in p:
        pspec["shared"] = jax.tree.map(lambda _: P(), p["shared"])
    xspec = P(daxes if daxes else None, None, None)

    def local_fn(pl, x_loc):
        b_loc, t, d = x_loc.shape
        n = b_loc * t
        k = m.top_k
        xf = x_loc.reshape(n, d)

        logits = xf.astype(jnp.float32) @ pl["router"]["w"]  # [n, E] (router replicated)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_ids = jax.lax.top_k(probs, k)
        if m.normalize_router_weights:
            top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(axis=0)
        ce = jax.nn.one_hot(top_ids[:, 0], e, dtype=jnp.float32).mean(axis=0)
        aux = e * jnp.sum(me * ce) * m.router_aux_weight
        aux = jax.lax.pmean(aux, axis_name=mesh.axis_names)

        flat_e = top_ids.reshape(n * k)
        flat_w = top_w.reshape(n * k)
        flat_tok = jnp.repeat(jnp.arange(n), k)

        # Single-stage dispatch (§Perf round 2 refinement): rank each
        # assignment within its (source-local) EXPERT queue and pack the
        # send buffer directly in expert-major order — after ONE tiled
        # all-to-all the rows land already grouped per local expert, so
        # no second sort/scatter and no eid/validity exchanges are needed
        # (the two-stage variant's extra traffic showed up as a 2x memory
        # term). Router weights & token ids never leave the source.
        cap_se = _round8(int(m.capacity_factor * n * k / e))  # per expert
        pos = _position_in_expert(flat_e, e, "sort")
        keep = pos < cap_se
        slot = jnp.where(keep, flat_e * cap_se + pos, e * cap_se)

        x_send = jnp.zeros((e * cap_se + 1, d), xf.dtype).at[slot].set(
            jnp.take(xf, flat_tok, axis=0)
        )[: e * cap_se]
        # [E*cap, d] is dest-shard-major (experts sorted by owner): a2a it
        x_recv = jax.lax.all_to_all(x_send, "model", 0, 0, tiled=True)
        # received rows: [src, e_loc, cap, d]; keep source-major layout and
        # let dot_general batch over e directly (an explicit expert-major
        # transpose costs 2 full-buffer copies per direction — §Perf r3)
        xe = x_recv.reshape(msize, e_loc, cap_se, d)
        h = jax.nn.silu(jnp.einsum("secd,edf->secf", xe, pl["w1"])) * jnp.einsum(
            "secd,edf->secf", xe, pl["w3"]
        )
        ye = jnp.einsum("secf,efd->secd", h, pl["w2"]).astype(x_loc.dtype)

        y_home = jax.lax.all_to_all(
            ye.reshape(msize * e_loc * cap_se, d), "model", 0, 0, tiled=True
        )  # [E*cap, d] back in source slot order

        gathered = jnp.take(
            jnp.concatenate([y_home, jnp.zeros((1, d), y_home.dtype)], 0),
            slot, axis=0,
        )  # [n*k, d]; dropped slots hit the zero row
        # combine in bf16 (k<=8 addends; f32 round-trips doubled traffic)
        out = jnp.zeros((n, d), x_loc.dtype).at[flat_tok].add(
            gathered * (flat_w * keep.astype(jnp.float32))[:, None].astype(
                gathered.dtype
            )
        )
        if "shared" in pl:
            out = out + L.ffn(pl["shared"], xf)
        return out.reshape(b_loc, t, d), aux

    out, aux = jax.shard_map(
        local_fn, mesh=mesh, in_specs=(pspec, xspec),
        out_specs=(xspec, P()), check_vma=False,
    )(p, x)
    return out, aux
