"""Generation engine: step primitives + ONE profile-driven decode loop.

This is the paper's end-to-end inference pipeline (§3.2): a single jitted
prefill program and a single jitted decode-step program with static shapes
(the §4.1.2 lever) — every decode step replays the same compiled
executable, the XLA analogue of CUDA-Graph replay.

Step primitives (shared by every engine AND the continuous-batching
scheduler in core/scheduler.py):
- ``prefill``     — one jitted prefill program: prompt -> fresh cache +
                    last-position logits. With batch=1 this is the
                    scheduler's single-slot refill prefill.
- ``decode_step`` — one jitted decode-step program (cache donated), the
                    executable replayed forever.
- ``mixed_step``  — one jitted token-budget mixed prefill/decode program
                    (paged caches): every slot advances by its own
                    ``t_new`` tokens in the same step — decode slots by 1,
                    a prefilling slot by a prompt chunk — so admission
                    work interleaves with decoding (chunked prefill).
- ``verify_step`` — one jitted multi-token verification program
                    (speculative decoding, both pool kinds): every slot
                    scores its drafted window in a single full-model
                    forward and returns per-lane logits, so a pool step
                    can commit a VARIABLE number of tokens per slot.

Decoding strategies are NOT separate loops any more: they are
``DecodingProfile`` specs (core/profiles.py) driven by ONE loop,
:func:`run_profile` — prefill the profile's expanded streams, then replay
the decode-step executable, letting the profile pick each stream's next
token, an optional intra-group cache permutation (beam's Obs #4 KV
reorder), and the finish condition. The public engines are thin wrappers
that build the profile and preserve their historical signatures:

- ``generate``            — ``SamplingProfile`` (Llama/Chameleon I-T).
                            ``tokens`` is always [B, max_new_tokens]: on
                            early EOS exit the tail is padded with
                            ``eos_id`` so callers can slice safely.
- ``generate_beam``       — ``BeamProfile`` (Seamless, Obs #4): per-step
                            KV reorder via the returned permutation,
                            donated by default.
- ``generate_contrastive``— ``ContrastiveProfile`` (Chameleon T-I): two
                            streams, 2 forwards/step (§2.1.2).

The same profile objects ride ``ServeRequest.profile`` through the
continuous-batching scheduler, where a request becomes a *slot group* of
``profile.n_streams`` pool slots — see core/scheduler.py. ``layerskip``
lives in core/layerskip.py and reuses this module's prefill/commit
plumbing directly.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.analysis.hotpath import hot_path
from repro.core import kv_cache, prefill as prefill_plan, profiles, sampling
from repro.models import attention
from repro.models.registry import Model


def _last_logits(logits: jnp.ndarray, prompt_lengths: jnp.ndarray) -> jnp.ndarray:
    """Gather the logits at each sequence's final prompt position."""
    idx = jnp.maximum(prompt_lengths - 1, 0)
    return jnp.take_along_axis(
        logits, idx[:, None, None], axis=1
    )[:, 0]


@functools.partial(jax.jit, static_argnums=(0, 4))
def prefill(model: Model, params, tokens, prompt_lengths, max_len, extra=None):
    """Prompt -> (last-position logits [B, V], fresh cache). One compiled
    program per (batch, prompt pad, max_len) signature; the scheduler calls
    it with batch=1 as the single-slot refill prefill."""
    cache = model.init_cache(tokens.shape[0], max_len)
    batch = {"tokens": tokens, "prompt_lengths": prompt_lengths}
    # repro-lint: disable=TB001 — branches on the PYTREE STRUCTURE of
    # ``extra`` (None/empty vs dict of arrays), which is part of the jit
    # cache key, never on traced values inside it
    if extra:
        batch.update(extra)
    logits, cache, _ = model.forward(params, batch, cache=cache, mode="prefill")
    return _last_logits(logits, prompt_lengths), cache


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def decode_step(model: Model, params, cache, token):
    """One decode step for every sequence slot: token [B] -> (logits [B, V],
    cache). The cache is donated, so the executable updates it in place and
    is replayed forever (§4.1.2 CUDA-Graph-analogue discipline)."""
    logits, cache, _ = model.forward(
        params, {"tokens": token[:, None]}, cache=cache, mode="decode"
    )
    return logits[:, 0], cache


@functools.partial(jax.jit, static_argnums=(0, 6),
                   static_argnames=("prompt_len",), donate_argnums=(2,))
def mixed_step(model: Model, params, cache, tokens, t_new, lengths,
               prompt_len: Optional[int] = None):
    """One token-budget mixed prefill/decode step over the whole pool
    (chunked prefill, paged caches only): tokens [B, C] carries each slot's
    lane payload — lane 0 holds a decoding slot's last token (t_new = 1), a
    prefilling slot's next prompt chunk fills lanes 0..t_new-1 (t_new up to
    C), and idle rows ride along with t_new = 0. ``lengths`` [B] is the
    AUTHORITATIVE per-slot write position from the scheduler's host state
    (a decoding slot's kv length, a prefilling slot's chunk cursor): the
    device counters are pinned to it inside this same executable, so the
    pool-wide decode step's every-row increment (which drifts free and
    mid-prefill rows) can never misplace a chunk — and no separate resync
    dispatch ever runs between steps. Returns the logits at each slot's
    LAST valid lane [B, V] (a decode slot's next-token logits; a slot
    finishing its prefill reads its first-token logits here) plus the
    donated cache. ONE compiled executable per (B, C, prompt_len)
    signature — admission rides the pool-wide step instead of stalling it.

    The step computes packed lanes, not ``B x C``: every row's lane 0 and
    the chunk rows' further lanes, ``B + C`` in all
    (``attention.mixed_pack``). ``prompt_len`` is the longest prompt the
    pool admits (its ``pad_to``): a chunk's keys lie in the slot's first
    ``ceil(prompt_len / block_size)`` blocks, and at most
    ``prefill.max_chunks(C, prompt_len)`` rows carry two or more lanes (the
    packer's bound). Unset, it is the whole block table."""
    b, c = tokens.shape
    bt = cache["block_tables"]
    bs = jax.tree.leaves(cache["layers"][0])[0].shape[1]
    mb = bt.shape[1]
    prompt_len = min(prompt_len or mb * bs, mb * bs)
    pack = attention.mixed_pack(
        t_new, lengths, bt, width=c, block_size=bs,
        chunk_rows=min(b, prefill_plan.max_chunks(c, prompt_len)),
        prompt_blocks=-(-prompt_len // bs),
    )
    cache = {**cache, "lengths": lengths}
    logits, cache, _ = model.forward(
        params, {"tokens": tokens, "t_new": t_new, "pack": pack},
        cache=cache, mode="mixed",
    )
    return logits[:, 0], cache


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def verify_step(model: Model, params, cache, tokens, t_new, lengths):
    """One speculative verification step over the whole pool: tokens [B, C]
    carries each slot's window — lane 0 the last committed token, lanes
    1..t_new-1 the drafted continuation (t_new = 1 is a plain decode lane,
    t_new = 0 an idle row). One full-model forward scores EVERY lane:
    returns per-lane next-token logits [B, C, V] (lane j's logits sample
    the token at position lengths+j+1) plus the donated cache. ``lengths``
    [B] is the authoritative per-slot write position from the scheduler's
    host state, pinned inside the executable exactly like ``mixed_step``.
    The device cache ends the step with the whole window written (accepted
    or not); rejected suffixes are rewound HOST-side — block-table
    truncation on paged pools, a lengths rewind on contiguous ones — so no
    device gather or cleanup program ever runs. ONE compiled executable
    per (B, C) signature: every draft-length geometry warms once and
    replays forever."""
    cache = {**cache, "lengths": lengths}
    logits, cache, _ = model.forward(
        params, {"tokens": tokens, "t_new": t_new}, cache=cache, mode="verify"
    )
    return logits, cache


# --------------------------------------------------------------------------
# tensor-parallel step family (distributed/tp_pool.py)
# --------------------------------------------------------------------------
# Same signatures + semantics as the single-device steps above, plus a
# static ``shardings`` pytree (hashable ``(flat NamedShardings, treedef)``
# form, see tp_pool._static) that pins the output cache back onto its
# per-device shards and gathers logits replicated. The inner call traces
# straight through the jitted single-device step (nested jit inlines);
# donation must be RE-declared here because an inlined jit's
# donate_argnums are ignored. GSPMD derives the head-sharded attention
# and column/row-sharded FFN partitioning from the committed param
# shardings + these cache constraints — one executable per geometry,
# findable in the same trace-audit registry as the rest. The inner step
# is traced under the mesh (``_on_mesh``) so that the Pallas attention
# kernels, which GSPMD cannot partition, run under shard_map on each
# device's own heads (kernels/ops.py ``_on_own_heads``).


def _on_mesh(shardings):
    """Context that traces a TP step under its shardings' mesh."""
    return jax.sharding.use_abstract_mesh(shardings[0][0].mesh.abstract_mesh)


def _tp_constrain(tree, shardings):
    """Pin every leaf of ``tree`` to the matching NamedSharding from the
    static ``(flat, treedef)`` pair (order = treedef flatten order)."""
    flat_s, treedef = shardings
    flat = treedef.flatten_up_to(tree)
    return jax.tree_util.tree_unflatten(treedef, [
        jax.lax.with_sharding_constraint(x, s)
        for x, s in zip(flat, flat_s)
    ])


def _tp_replicated(x, shardings):
    """Constrain ``x`` fully replicated on the shardings' mesh — the
    host-facing outputs (logits, draft windows) the scheduler samples."""
    mesh = shardings[0][0].mesh
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    )


@functools.partial(jax.jit, static_argnums=(0, 4),
                   static_argnames=("row_shardings",))
def tp_prefill(model: Model, params, tokens, prompt_lengths, max_len,
               extra=None, *, row_shardings):
    """``prefill`` on the mesh: the fresh row cache comes back committed
    to its TP shards (head axis), logits replicated for host-side
    sampling. No donation — prefill allocates its cache internally."""
    with _on_mesh(row_shardings):
        logits, cache = prefill(model, params, tokens, prompt_lengths,
                                max_len, extra)
    return (_tp_replicated(logits, row_shardings),
            _tp_constrain(cache, row_shardings))


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("shardings",), donate_argnums=(2,))
def tp_decode_step(model: Model, params, cache, token, *, shardings):
    """``decode_step`` on the mesh (cache donated shard-for-shard)."""
    with _on_mesh(shardings):
        logits, cache = decode_step(model, params, cache, token)
    return (_tp_replicated(logits, shardings),
            _tp_constrain(cache, shardings))


@functools.partial(jax.jit, static_argnums=(0, 6),
                   static_argnames=("shardings", "prompt_len"),
                   donate_argnums=(2,))
def tp_mixed_step(model: Model, params, cache, tokens, t_new, lengths,
                  prompt_len: Optional[int] = None, *, shardings):
    """``mixed_step`` on the mesh (cache donated shard-for-shard)."""
    with _on_mesh(shardings):
        logits, cache = mixed_step(model, params, cache, tokens, t_new,
                                   lengths, prompt_len=prompt_len)
    return (_tp_replicated(logits, shardings),
            _tp_constrain(cache, shardings))


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("shardings",), donate_argnums=(2,))
def tp_verify_step(model: Model, params, cache, tokens, t_new, lengths, *,
                   shardings):
    """``verify_step`` on the mesh (cache donated shard-for-shard)."""
    with _on_mesh(shardings):
        logits, cache = verify_step(model, params, cache, tokens, t_new,
                                    lengths)
    return (_tp_replicated(logits, shardings),
            _tp_constrain(cache, shardings))


# --------------------------------------------------------------------------
# the ONE profile-driven decode loop
# --------------------------------------------------------------------------

@hot_path
def run_profile(
    model: Model,
    params,
    profile: profiles.DecodingProfile,
    prompt_tokens: jnp.ndarray,  # [G, Tp] per-GROUP prompts (right-padded)
    *,
    prompt_lengths: Optional[jnp.ndarray] = None,
    max_new_tokens: int = 32,
    max_len: Optional[int] = None,
    key: Optional[jax.Array] = None,
    extra_inputs: Optional[Dict[str, jnp.ndarray]] = None,
    reorder=None,
) -> Dict[str, Any]:
    """Drive one decoding profile batch-at-a-time: expand the G group
    prompts to the [G * n_streams] stream layout, prefill once, then
    replay the decode-step executable, with the profile choosing each
    stream's next token, the optional cache permutation (applied via
    ``reorder``, default the donated Obs #4 gather), and the finish
    condition. Returns the profile's ``finalize`` output plus ``cache``
    and ``n_steps`` (decode-loop iterations actually run)."""
    g, tp = prompt_tokens.shape
    if prompt_lengths is None:
        prompt_lengths = jnp.full((g,), tp, jnp.int32)
    if max_len is None:
        max_len = tp + max_new_tokens + 1
    key = key if key is not None else jax.random.PRNGKey(0)
    reorder = reorder if reorder is not None else kv_cache.reorder_donated

    toks_s, lens_s, extra_s = profile.expand_prompts(
        prompt_tokens, prompt_lengths, extra_inputs
    )
    logits, cache = prefill(model, params, toks_s, lens_s, max_len, extra_s)
    state = profile.init(g, max_new_tokens)
    n_steps, halt, feed = 0, False, None
    for i in range(max_new_tokens):
        if i > 0:
            if halt:
                break
            logits, cache = decode_step(model, params, cache, feed)
        key, sub = jax.random.split(key)
        out = profile.step(state, logits, sub)
        state, feed = out.state, out.feed
        if out.perm is not None:  # Obs #4: the KV_Cache_Reorder op
            cache = reorder(cache, out.perm)
        n_steps += 1
        # the loop's one deliberate host sync: a single scalar transfer
        # (device_get), not a stray bool() on the device array
        halt = out.done is not None and bool(jax.device_get(out.done.all()))
    result = profile.finalize(state)
    result.update(cache=cache, n_steps=n_steps)
    return result


# --------------------------------------------------------------------------
# thin engine wrappers (historical signatures preserved)
# --------------------------------------------------------------------------

def generate(
    model: Model,
    params,
    prompt_tokens: jnp.ndarray,  # [B, Tp] right-padded
    *,
    prompt_lengths: Optional[jnp.ndarray] = None,
    max_new_tokens: int = 32,
    sampler: sampling.Sampler = sampling.greedy,
    key: Optional[jax.Array] = None,
    eos_id: Optional[int] = None,
    extra_inputs: Optional[Dict[str, jnp.ndarray]] = None,
    live: Optional[jnp.ndarray] = None,
) -> Dict[str, jnp.ndarray]:
    """Python-loop generation (serving style): a ``SamplingProfile`` run
    through the one profile loop.

    ``live`` [B] marks which batch rows carry real requests; dead rows
    (fixed-slot padding) are treated as already finished: they emit only
    the fill token (``eos_id`` when set, else 0) and never block the
    all-done early exit. Without ``eos_id`` there is no early exit (live
    rows can never finish early), but dead-row outputs are still masked.

    Output contract: ``tokens`` is ALWAYS [B, max_new_tokens]. When every
    live row hits EOS early, the remaining columns are padded with the
    fill token (``n_steps`` reports the real decode-step count)."""
    prof = profiles.SamplingProfile(eos_id=eos_id, sampler=sampler, live=live)
    out = run_profile(
        model, params, prof, prompt_tokens,
        prompt_lengths=prompt_lengths, max_new_tokens=max_new_tokens,
        key=key, extra_inputs=extra_inputs,
    )
    return {"tokens": out["tokens"], "cache": out["cache"],
            "n_steps": out["n_steps"]}


def generate_scanned(
    model: Model,
    params,
    prompt_tokens: jnp.ndarray,
    *,
    max_new_tokens: int = 32,
    sampler: sampling.Sampler = sampling.greedy,
    key: Optional[jax.Array] = None,
    extra_inputs: Optional[Dict[str, jnp.ndarray]] = None,
) -> jnp.ndarray:
    """Whole-generation-as-one-program variant: prefill + lax.scan decode.
    The fully static pipeline: no host round trip between tokens
    (profiles' host-side control flow excludes them from this path)."""
    b, tp = prompt_tokens.shape
    prompt_lengths = jnp.full((b,), tp, jnp.int32)
    key = key if key is not None else jax.random.PRNGKey(0)
    max_len = tp + max_new_tokens + 1

    logits, cache = prefill(
        model, params, prompt_tokens, prompt_lengths, max_len, extra_inputs
    )
    token0 = sampler(logits, key)

    def step(carry, sub):
        token, cache = carry
        logits, cache = decode_step(model, params, cache, token)
        nxt = sampler(logits, sub)
        return (nxt, cache), nxt

    keys = jax.random.split(key, max_new_tokens - 1)
    (_, _), rest = jax.lax.scan(step, (token0, cache), keys)
    return jnp.concatenate([token0[None], rest], axis=0).T  # [B, max_new]


def generate_beam(
    model: Model,
    params,
    *,
    batch: Optional[int] = None,
    n_beams: int,
    bos_id: Optional[int] = None,
    eos_id: int,
    max_new_tokens: int,
    prompt_tokens: Optional[jnp.ndarray] = None,  # [B, Tp]; default [bos]
    prompt_lengths: Optional[jnp.ndarray] = None,
    extra_inputs: Optional[Dict[str, jnp.ndarray]] = None,
    length_penalty: float = 1.0,
    donate_reorder: bool = True,
) -> Dict[str, jnp.ndarray]:
    """Beam search with per-step KV reorder, as a ``BeamProfile``. Inputs
    (e.g. encoder frames) are tiled across beams; each step gathers the
    cache along the batch axis by the surviving-parent permutation (paper
    Obs #4) — donated by default (the optimized `copy_` form), or
    reallocating when ``donate_reorder=False`` (the paper's baseline
    `index_select`). ``prompt_tokens`` generalizes the historical
    BOS-only prompt (every beam prefills the same prompt)."""
    if prompt_tokens is None:
        if batch is None or bos_id is None:
            raise ValueError("need prompt_tokens, or batch + bos_id")
        prompt_tokens = jnp.full((batch, 1), bos_id, jnp.int32)
    prof = profiles.BeamProfile(
        n_beams=n_beams, eos_id=eos_id, length_penalty=length_penalty
    )
    out = run_profile(
        model, params, prof, prompt_tokens,
        prompt_lengths=prompt_lengths, max_new_tokens=max_new_tokens,
        extra_inputs=extra_inputs,
        reorder=(
            kv_cache.reorder_donated if donate_reorder
            else kv_cache.reorder_realloc
        ),
    )
    return {"tokens": out["tokens"], "scores": out["scores"],
            "n_steps": out["n_steps"]}


def generate_contrastive(
    model: Model,
    params,
    prompt_tokens: jnp.ndarray,  # [B, Tp] conditional (text) prompt
    *,
    uncond_token: int,
    n_image_tokens: int,
    guidance: float = 3.0,
    sampler: sampling.Sampler = sampling.greedy,
    key: Optional[jax.Array] = None,
) -> Dict[str, jnp.ndarray]:
    """Chameleon T-I as a ``ContrastiveProfile``: the conditional stream
    sees the prompt, the unconditional stream a null prompt; each step
    runs BOTH (the paper's "decodes twice at each time step"), combines
    logits contrastively, and feeds the same sampled image token to both
    streams. On VLM configs sampling is restricted to the image-token
    range; other families run plain classifier-free guidance."""
    cfg = model.config
    mask_offset = None
    if getattr(cfg, "vlm", None) is not None:
        from repro.models import vlm

        mask_offset = vlm.image_token_offset(cfg)
    prof = profiles.ContrastiveProfile(
        uncond_token=uncond_token, guidance=guidance,
        mask_offset=mask_offset, sampler=sampler,
    )
    tp = prompt_tokens.shape[1]
    out = run_profile(
        model, params, prof, prompt_tokens,
        max_new_tokens=n_image_tokens, max_len=tp + n_image_tokens + 1,
        key=key,
    )
    return {"tokens": out["tokens"], "n_steps": out["n_steps"]}
