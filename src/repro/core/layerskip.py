"""LayerSkip self-speculative decoding (paper §4.3, Elhoushi et al. 2024).

Draft tokens are generated auto-regressively with only the first E
transformer layers + the shared LM head (early exit); the draft window is
then verified IN PARALLEL by one full-model forward over the window
(``engine.verify_step``, "verify" mode). Greedy acceptance makes the
scheme lossless w.r.t. the full model under greedy decoding: every
committed token is exactly what the full model would have produced.

JAX adaptation notes (vs. the CUDA implementation the paper used):
- drafting writes THE pool cache, not a scratch copy: draft writes land
  only at positions >= the committed length, where the "stale tail is
  masked by validity" discipline (models/attention.py) already makes
  garbage harmless — and the verify step then overwrites the whole
  window across every layer. That makes both executables linear in the
  cache (draft -> verify -> host rewind), so BOTH donate it; "rollback
  on rejection" is a host-side ``lengths`` rewind (contiguous) or a
  block-table truncation (paged), never a device copy;
- for layers < E the draft's K/V writes are exactly what the full model
  would write (the first E layers are the same computation), so the
  verify pass re-deriving them costs correctness nothing;
- applies to attention-cache families (dense/moe/mla_moe/vlm). SSM/hybrid
  recurrent state cannot be rolled back by masking; DESIGN.md §4 notes
  this (their decode is already state-bounded, which shrinks LayerSkip's
  win anyway).

:func:`draft_window` + ``engine.verify_step`` are also the serving pool's
speculative step (core/scheduler.py, ``SpeculativeProfile``): per-slot
``n_live`` widths let plain-sampling and speculative traffic ride the
same two executables, and the same per-(request, token-index) sampling
keys keep committed tokens bit-identical to plain decoding at any
temperature. :func:`layerskip_generate` below is the batch-at-a-time
engine on the same primitives (per-row commit, no batch-min barrier).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import kv_cache
from repro.models import layers as L
from repro.models import transformer
from repro.models.registry import Model


def early_exit_forward(
    cfg: ModelConfig,
    params,
    batch: Dict[str, jnp.ndarray],
    *,
    n_layers: int,
    cache=None,
    mode: str = "decode",
    advance: Optional[jnp.ndarray] = None,
):
    """Transformer forward through the first ``n_layers`` layers only, then
    final-norm + (shared) LM head — the LayerSkip draft model. Supports
    paged caches (the pool's shared block table is injected per layer,
    like the full forward). ``advance`` [B] overrides the per-slot length
    bump (default ``t``): the pool's draft loop freezes slots whose
    window is exhausted by advancing them 0."""
    tokens = batch["tokens"]
    b, t = tokens.shape
    if mode == "train" or cache is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        lengths = None
    else:
        lengths = cache["lengths"]
        positions = lengths[:, None] + jnp.arange(t)[None]

    bt = cache.get("block_tables") if cache is not None else None
    x = L.embed(params["embed"], tokens)
    new_layers = []
    for i, lp in enumerate(params["layers"]):
        if i >= n_layers:
            new_layers.append(cache["layers"][i] if cache is not None else None)
            continue
        lc = cache["layers"][i] if cache is not None else None
        if bt is not None and lc is not None:
            lc = dict(lc, bt=bt)
        x, nlc, _ = transformer.layer_forward(
            cfg, lp, x, layer=i, positions=positions, lengths=lengths,
            cache=lc, mode=mode,
        )
        if bt is not None and nlc is not None:
            nlc = {k: v for k, v in nlc.items() if k != "bt"}
        new_layers.append(nlc)

    x = L.rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], x)
    else:
        logits = L.dense(params["lm_head"], x).astype(jnp.float32)
    new_cache = None
    if cache is not None:
        bump = advance if advance is not None else t
        new_cache = {"lengths": cache["lengths"] + bump, "layers": new_layers}
        if bt is not None:
            new_cache["block_tables"] = bt
    return logits, new_cache


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(4,))
def draft_window(
    model: Model, exit_layer: int, n_draft: int, params, cache, token0,
    n_live, lengths,
):
    """Greedy-draft up to ``n_draft`` tokens per slot with the early-exit
    submodel, writing straight into the (donated) pool cache. ``token0``
    [B] is each slot's pending feed token; slot ``b`` drafts
    ``n_live[b]`` tokens (0 = plain-decode or idle slot: it stays frozen
    — its repeated write at the frozen position is masked garbage the
    verify step overwrites). ``lengths`` [B] is the authoritative host
    write position, pinned like ``mixed_step``. Returns ``(window
    [B, n_draft+1], cache)`` — lane 0 is ``token0``, lanes 1..n_draft the
    greedy drafts (frozen slots repeat their token past their width;
    those lanes are never verified or committed). ONE executable per
    (exit_layer, n_draft, B) signature."""
    cfg = model.config
    cache = {**cache, "lengths": lengths}

    def step(carry, i):
        token, cache = carry
        live = i < n_live  # [B]
        logits, cache = early_exit_forward(
            cfg, params, {"tokens": token[:, None]}, n_layers=exit_layer,
            cache=cache, mode="decode", advance=live.astype(jnp.int32),
        )
        nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        nxt = jnp.where(live, nxt, token)
        return (nxt, cache), nxt

    (_, cache), drafts = jax.lax.scan(
        step, (token0, cache), jnp.arange(n_draft)
    )
    window = jnp.concatenate([token0[:, None], drafts.T], axis=1)
    return window, cache


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("shardings",), donate_argnums=(4,))
def tp_draft_window(
    model: Model, exit_layer: int, n_draft: int, params, cache, token0,
    n_live, lengths, *, shardings,
):
    """``draft_window`` on a tensor-parallel mesh (distributed/tp_pool.py):
    traces through the single-device draft scan, then pins the donated
    pool cache back to its per-device shards and the token window
    replicated (the window is host state — the scheduler slices it).
    Donation is re-declared because the inlined inner jit's is ignored."""
    from repro.core import engine

    with engine._on_mesh(shardings):
        window, cache = draft_window(
            model, exit_layer, n_draft, params, cache, token0, n_live, lengths
        )
    return (engine._tp_replicated(window, shardings),
            engine._tp_constrain(cache, shardings))


def layerskip_generate(
    model: Model,
    params,
    prompt_tokens: jnp.ndarray,  # [B, Tp]
    *,
    exit_layer: int,
    n_draft: int = 4,
    max_new_tokens: int = 32,
) -> Dict[str, jnp.ndarray]:
    """Greedy LayerSkip generation. Returns tokens plus acceptance stats.

    Losslessness: committed tokens equal full-model greedy decoding.
    Commit is per-row (``lengths`` is per-row state): a row with a
    rejected draft no longer drags the whole batch down to its accepted
    count — finished rows idle with a zero-width window.
    """
    from repro.core import engine as E

    cfg = model.config
    assert cfg.family in ("dense", "moe", "mla_moe", "vlm"), (
        "LayerSkip needs rollback-able attention caches (DESIGN.md §4)"
    )
    b, tp = prompt_tokens.shape
    max_len = tp + max_new_tokens + n_draft + 2
    prompt_lengths = jnp.full((b,), tp, jnp.int32)
    logits, cache = E.prefill(
        model, params, prompt_tokens, prompt_lengths, max_len, None
    )
    token = np.array(jnp.argmax(logits, axis=-1), np.int32)  # [B] (writable)

    out = np.zeros((b, max_new_tokens), np.int32)
    out[:, 0] = token
    emitted = np.ones((b,), np.int64)
    kv_len = np.full((b,), tp, np.int64)  # next write position per row
    n_rounds = n_drafted = n_accepted = 0
    while (emitted < max_new_tokens).any():
        remaining = max_new_tokens - emitted
        w = np.where(remaining > 0,
                     np.minimum(n_draft + 1, remaining), 0).astype(np.int32)
        n_live = np.maximum(w - 1, 0).astype(np.int32)
        lengths = jnp.asarray(kv_len, jnp.int32)
        window, cache = draft_window(
            model, exit_layer, n_draft, params, cache,
            jnp.asarray(token), jnp.asarray(n_live), lengths,
        )
        logits, cache = E.verify_step(
            model, params, cache, window, jnp.asarray(w), lengths,
        )
        preds, win = jax.device_get(
            (jnp.argmax(logits, axis=-1).astype(jnp.int32), window)
        )
        for r in range(b):
            if w[r] == 0:
                continue
            commits = 0
            for j in range(int(w[r])):
                tok = int(preds[r, j])
                out[r, emitted[r]] = tok
                emitted[r] += 1
                commits += 1
                token[r] = tok
                # stop at the first draft the full model contradicts
                # (the committed ``tok`` is the full model's correction)
                if j + 1 >= int(w[r]) or tok != int(win[r, j + 1]):
                    break
            kv_len[r] += commits
            n_drafted += int(n_live[r])
            n_accepted += commits - 1
        # host-side rollback of every rejected suffix: one lengths rewind
        cache = kv_cache.rewind(cache, jnp.asarray(kv_len, jnp.int32))
        n_rounds += 1

    tokens = jnp.asarray(out)
    return {
        "tokens": tokens,
        "n_rounds": n_rounds,
        "acceptance": n_accepted / max(n_drafted, 1),
        # first token comes from the prefill, not a draft/verify round
        "tokens_per_round": (tokens.shape[1] - 1) / max(n_rounds, 1),
    }
