"""Decoder-only transformer stack: dense (llama/yi/qwen), MoE (qwen3-moe),
MLA+MoE (deepseek-v2) and early-fusion VLM (chameleon) all share this file —
the family only changes the attention/FFN blocks plugged into each layer.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M

#: PartitionSpec applied at layer boundaries when cfg.seq_parallel: MUST
#: pin the batch axes too (an unconstrained/None batch dim means
#: "replicated" to GSPMD — §Perf round 1 measured a 10x compute blow-up
#: from exactly that). The launcher overrides it with the mesh's axes.
SEQ_PARALLEL_SPEC = None  # set by launcher, e.g. P(("data",), "model", None)


def _layer_uses_moe(cfg: ModelConfig, layer: int) -> bool:
    return cfg.moe is not None and layer >= cfg.moe.first_dense_layers


def _dense_ff_width(cfg: ModelConfig) -> int:
    if cfg.moe is not None and cfg.moe.first_dense_layers > 0:
        return cfg.moe.d_ff_dense or cfg.d_ff
    return cfg.d_ff


def init_layer(key, cfg: ModelConfig, layer: int):
    ka, kf = jax.random.split(key)
    dt = L.param_dtype(cfg)
    p = {
        "attn_norm": L.rmsnorm_init(cfg.d_model, dt),
        "ffn_norm": L.rmsnorm_init(cfg.d_model, dt),
    }
    p["attn"] = A.init_mla(ka, cfg) if cfg.mla is not None else A.init_attention(ka, cfg)
    if _layer_uses_moe(cfg, layer):
        p["moe"] = M.init_moe(kf, cfg)
    else:
        p["ffn"] = L.ffn_init(kf, cfg.d_model, _dense_ff_width(cfg), dt)
    return p


def layer_forward(
    cfg: ModelConfig,
    p,
    x: jnp.ndarray,
    *,
    layer: int,
    positions: jnp.ndarray,
    lengths: Optional[jnp.ndarray],
    cache: Optional[dict],
    mode: str,
    impl: str = "auto",
    t_new: Optional[jnp.ndarray] = None,
    pack: Optional[A.MixedPack] = None,
) -> Tuple[jnp.ndarray, Optional[dict], jnp.ndarray]:
    h = L.rmsnorm(p["attn_norm"], x, cfg.rmsnorm_eps)
    kw = dict(positions=positions, lengths=lengths, cache=cache, mode=mode,
              impl=impl, t_new=t_new, pack=pack)
    if cfg.mla is not None:
        attn_out, new_cache = A.mla_attention(cfg, p["attn"], h, **kw)
    else:
        attn_out, new_cache = A.attention(
            cfg, p["attn"], h, window=cfg.sliding_window, **kw
        )
    x = x + attn_out

    h = L.rmsnorm(p["ffn_norm"], x, cfg.rmsnorm_eps)
    if "moe" in p:
        ffn_out, aux = M.moe_ffn(cfg, p["moe"], h)
    else:
        ffn_out, aux = L.ffn(p["ffn"], h), jnp.float32(0.0)
    return x + ffn_out, new_cache, aux


def _n_prefix_layers(cfg: ModelConfig) -> int:
    """Layers kept unrolled before the scanned homogeneous block."""
    if not cfg.scan_layers:
        return cfg.n_layers
    return cfg.moe.first_dense_layers if cfg.moe is not None else 0


def _layer_forward_remat(fn, cfg, lp, x, **kw):
    """Activation-checkpointed layer: recompute internals in the backward
    pass (the standard memory/compute trade for long-sequence training)."""
    return jax.checkpoint(lambda lp_, x_: fn(cfg, lp_, x_, **kw))(lp, x)


def init(cfg: ModelConfig, key):
    ks = jax.random.split(key, cfg.n_layers + 3)
    dt = L.param_dtype(cfg)
    npre = _n_prefix_layers(cfg)
    p = {
        "embed": L.embedding_init(ks[0], cfg.vocab_size, cfg.d_model, dt),
        "final_norm": L.rmsnorm_init(cfg.d_model, dt),
        "layers": [init_layer(ks[2 + i], cfg, i) for i in range(npre)],
    }
    if cfg.scan_layers:
        # stack the homogeneous block: every leaf gains a leading [L] axis
        p["scanned"] = jax.vmap(lambda k: init_layer(k, cfg, npre))(
            jnp.stack(ks[2 + npre : 2 + cfg.n_layers])
        )
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[1], cfg.d_model, cfg.vocab_size, dt)
    return p


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    if cfg.mla is not None:
        mk = lambda: A.init_mla_cache(cfg, batch, max_len)
    else:
        mk = lambda: A.init_attention_cache(
            cfg, batch, max_len, window=cfg.sliding_window
        )
    npre = _n_prefix_layers(cfg)
    cache = {
        "lengths": jnp.zeros((batch,), jnp.int32),
        "layers": [mk() for _ in range(npre)],
    }
    if cfg.scan_layers:
        nscan = cfg.n_layers - npre
        cache["scanned"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (nscan,) + x.shape), mk()
        )
    return cache


def forward(
    cfg: ModelConfig,
    params,
    batch: dict,
    *,
    cache: Optional[dict] = None,
    mode: str = "train",
    impl: str = "auto",
) -> Tuple[jnp.ndarray, Optional[dict], dict]:
    """Returns (logits [B,T,V] f32, new_cache, aux dict); a mixed step
    returns each row's last valid lane only, [B,1,V]."""
    tokens = batch["tokens"]
    b, t = tokens.shape
    if mode == "train":
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        lengths = None
    else:
        lengths = cache["lengths"]
        positions = lengths[:, None] + jnp.arange(t)[None]
    # mixed (chunked-prefill) / verify (speculative window) steps: per-slot
    # widths [B]; lanes beyond t_new[b] are padding (writes hit the sink
    # block / get dropped, outputs discarded)
    t_new = batch.get("t_new") if mode in ("mixed", "verify") else None
    pack = batch["pack"] if mode == "mixed" else None
    if pack is not None:
        # the step's real lanes only, as [N, 1] rows (attention.MixedPack)
        tokens = tokens[pack.row, pack.col][:, None]
        positions = pack.pos[:, None]

    x = L.embed(params["embed"], tokens)
    aux_total = jnp.float32(0.0)
    new_layers = []
    # paged serving: every layer shares ONE block table (stored once at the
    # cache's top level; injected as a per-layer view, stripped on return)
    bt = cache.get("block_tables") if cache is not None else None
    base_layer_fn = layer_forward
    if cfg.seq_parallel and SEQ_PARALLEL_SPEC is not None:
        sp_spec = SEQ_PARALLEL_SPEC

        def base_layer_fn(cfg_, lp_, x_, **kw):  # noqa: F811
            x_ = jax.lax.with_sharding_constraint(x_, sp_spec)
            out, nlc, aux = layer_forward(cfg_, lp_, x_, **kw)
            return jax.lax.with_sharding_constraint(out, sp_spec), nlc, aux

    layer_fn = base_layer_fn
    if cfg.remat:
        layer_fn = functools.partial(_layer_forward_remat, base_layer_fn)
    for i, lp in enumerate(params["layers"]):
        lc = cache["layers"][i] if cache is not None else None
        if bt is not None and lc is not None:
            lc = dict(lc, bt=bt)
        x, nlc, aux = layer_fn(
            cfg, lp, x, layer=i, positions=positions, lengths=lengths,
            cache=lc, mode=mode, impl=impl, t_new=t_new, pack=pack,
        )
        if bt is not None and nlc is not None:
            nlc = {k: v for k, v in nlc.items() if k != "bt"}
        new_layers.append(nlc)
        aux_total = aux_total + aux

    new_scanned = None
    if cfg.scan_layers:
        npre = len(params["layers"])

        def body(carry, xs):
            x, aux_acc = carry
            lp, lc = xs
            x, nlc, aux = layer_fn(
                cfg, lp, x, layer=npre, positions=positions, lengths=lengths,
                cache=lc, mode=mode, impl=impl,
            )
            return (x, aux_acc + aux), nlc

        scanned_cache = cache["scanned"] if cache is not None else None
        (x, aux_total), new_scanned = jax.lax.scan(
            body, (x, aux_total), (params["scanned"], scanned_cache)
        )

    if pack is not None:
        # only each slot's LAST valid lane is ever read (a decode slot's
        # next-token logits / a finishing prefill's first-token logits):
        # gather it before the unembed so the vocab projection runs on one
        # lane per slot
        x = x[pack.last]  # [B, 1, d]
    x = L.rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], x)
    else:
        logits = L.dense(params["lm_head"], x).astype(jnp.float32)

    new_cache = None
    if cache is not None:
        # prefill: count the whole prompt (or per-request lengths if given);
        # decode: one token per slot.
        if mode == "prefill":
            new_len = batch.get("prompt_lengths", jnp.full((b,), t, jnp.int32))
        elif mode in ("mixed", "verify"):  # per-slot widths (0 = idle row)
            new_len = cache["lengths"] + t_new
        else:  # decode / extend
            new_len = cache["lengths"] + t
        new_cache = {"lengths": new_len, "layers": new_layers}
        if bt is not None:
            new_cache["block_tables"] = bt
        if cfg.scan_layers:
            new_cache["scanned"] = new_scanned
    return logits, new_cache, {"aux_loss": aux_total}
