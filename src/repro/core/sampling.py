"""Decoding strategies (paper Obs #4): greedy, temperature, top-k, top-p
(Llama/Chameleon default), beam search (Seamless default, with the KV
reorder hook), and the contrastive combine used by Chameleon T-I.

All samplers share the signature ``sample(logits [B, V], key) -> [B]`` so
the engine can treat them uniformly; beam search is stateful and exposes a
step function instead.

Continuous-batching serving needs *per-slot* decoding state: each slot in
the pool belongs to a different request with its own temperature / top-p
and its own RNG stream. ``sample_slots`` is the vectorized per-slot
sampler (temperature 0 selects greedy for that slot), and
``request_key`` / ``slot_step_keys`` derive a key per (request, token
index) — so a request's random stream is independent of which slot it
lands in and of what else shares the batch.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

Sampler = Callable[[jnp.ndarray, jax.Array], jnp.ndarray]


def greedy(logits: jnp.ndarray, key=None) -> jnp.ndarray:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def temperature(temp: float = 1.0) -> Sampler:
    def sample(logits, key):
        return jax.random.categorical(key, logits / max(temp, 1e-6)).astype(jnp.int32)

    return sample


def top_k(k: int, temp: float = 1.0) -> Sampler:
    def sample(logits, key):
        vals, idx = jax.lax.top_k(logits, k)
        choice = jax.random.categorical(key, vals / max(temp, 1e-6))
        return jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)

    return sample


def top_p(p: float = 0.9, temp: float = 1.0) -> Sampler:
    """Nucleus sampling (paper: Llama & Chameleon's strategy)."""

    def sample(logits, key):
        logits = logits / max(temp, 1e-6)
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(sorted_probs, axis=-1)
        # keep the smallest prefix with mass >= p (always keep top-1)
        cutoff_mask = cum - sorted_probs < p
        threshold = jnp.min(
            jnp.where(cutoff_mask, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        filtered = jnp.where(logits >= threshold, logits, -jnp.inf)
        return jax.random.categorical(key, filtered).astype(jnp.int32)

    return sample


# --------------------------------------------------------------------------
# Per-slot sampling (continuous-batching scheduler)
# --------------------------------------------------------------------------

def request_key(base_key: jax.Array, rid, stream=0) -> jax.Array:
    """Per-(request, stream) RNG key: independent of slot placement and
    batch mates. ``stream`` separates the streams of one multi-stream
    request (an n-beam / contrastive slot group): folding in only ``rid``
    would hand every stream of the group the SAME random stream."""
    return jax.random.fold_in(jax.random.fold_in(base_key, rid), stream)


@jax.jit
def slot_step_keys(
    base_key: jax.Array,
    rids: jnp.ndarray,
    steps: jnp.ndarray,
    streams: Optional[jnp.ndarray] = None,
) -> jax.Array:
    """Key per slot for its next token: fold (request id, stream index,
    token index) into the serve-level base key. [B] rids, [B] steps,
    optional [B] streams (default all-0: single-stream requests) -> [B]
    keys. Streams of one slot group share a rid but never a key."""
    req_keys = jax.vmap(lambda r: jax.random.fold_in(base_key, r))(rids)
    if streams is None:
        streams = jnp.zeros_like(rids)
    req_keys = jax.vmap(jax.random.fold_in)(req_keys, streams)
    return jax.vmap(jax.random.fold_in)(req_keys, steps)


@jax.jit
def sample_slots(
    logits: jnp.ndarray,  # [B, V]
    keys: jax.Array,  # [B] per-slot keys (stacked)
    temperature: jnp.ndarray,  # [B]; 0 => greedy for that slot
    top_p: jnp.ndarray,  # [B]; 1 => no nucleus filtering
) -> jnp.ndarray:
    """Vectorized per-slot sampler: each pool slot decodes with its own
    request's (temperature, top_p) and its own RNG stream."""
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    keep = cum - sorted_probs < top_p[:, None]
    threshold = jnp.min(
        jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    filtered = jnp.where(scaled >= threshold, scaled, -jnp.inf)
    sampled = jax.vmap(jax.random.categorical)(keys, filtered).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy_tok, sampled)


@jax.jit
def window_step_keys(
    base_key: jax.Array,
    rids: jnp.ndarray,  # [B]
    steps: jnp.ndarray,  # [B, C] per-lane token indices
    streams: Optional[jnp.ndarray] = None,  # [B]
) -> jax.Array:
    """Key per (slot, window lane): the speculative generalization of
    :func:`slot_step_keys`. Lane ``j`` of slot ``b`` gets the key for
    token index ``steps[b, j]`` of request ``rids[b]`` — the SAME key
    that slot would use for that token under plain one-token-per-step
    decoding, so committed tokens are bit-identical to the
    non-speculative engine regardless of where window boundaries fall."""
    req_keys = jax.vmap(lambda r: jax.random.fold_in(base_key, r))(rids)
    if streams is None:
        streams = jnp.zeros_like(rids)
    req_keys = jax.vmap(jax.random.fold_in)(req_keys, streams)
    return jax.vmap(jax.vmap(jax.random.fold_in, in_axes=(None, 0)))(
        req_keys, steps
    )


@jax.jit
def sample_window(
    logits: jnp.ndarray,  # [B, C, V]
    keys: jax.Array,  # [B, C] per-lane keys (stacked)
    temperature: jnp.ndarray,  # [B]; 0 => greedy for that slot
    top_p: jnp.ndarray,  # [B]; 1 => no nucleus filtering
) -> jnp.ndarray:
    """Per-lane :func:`sample_slots` over a verification window: every
    lane of a slot samples with the request's (temperature, top_p) under
    its own per-token key. Returns [B, C] sampled tokens."""
    return jax.vmap(sample_slots, in_axes=(1, 1, None, None), out_axes=1)(
        logits, keys, temperature, top_p
    )


# --------------------------------------------------------------------------
# Beam search (Seamless profile, Obs #4)
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass
class BeamState:
    tokens: jnp.ndarray  # [B*K, L] generated so far (right-padded)
    scores: jnp.ndarray  # [B*K] cumulative log-prob
    finished: jnp.ndarray  # [B*K] bool
    step: jnp.ndarray  # int32 scalar: tokens generated so far


def beam_init(batch: int, n_beams: int, max_len: int) -> BeamState:
    scores = jnp.tile(
        jnp.concatenate([jnp.zeros((1,)), jnp.full((n_beams - 1,), -1e9)]), (batch,)
    )
    return BeamState(
        tokens=jnp.zeros((batch * n_beams, max_len), jnp.int32),
        scores=scores,
        finished=jnp.zeros((batch * n_beams,), bool),
        step=jnp.zeros((), jnp.int32),
    )


def beam_step(
    state: BeamState,
    logits: jnp.ndarray,  # [B*K, V] next-token logits for every live beam
    n_beams: int,
    eos_id: int,
    length_penalty: float = 1.0,
) -> Tuple[BeamState, jnp.ndarray]:
    """One beam-search step. Returns (new_state, beam_idx [B*K]) where
    ``beam_idx`` is the KV-cache reorder permutation (paper Obs #4: every
    step re-binds each slot to its surviving parent's cache)."""
    bk, v = logits.shape
    b = bk // n_beams
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    # finished beams only extend with EOS at no cost
    eos_only = jnp.full((v,), -jnp.inf).at[eos_id].set(0.0)
    logp = jnp.where(state.finished[:, None], eos_only[None], logp)

    cand = state.scores[:, None] + logp  # [B*K, V]
    cand = cand.reshape(b, n_beams * v)
    top_scores, top_idx = jax.lax.top_k(cand, n_beams)  # [B, K]
    parent = top_idx // v  # beam index within the batch
    token = (top_idx % v).astype(jnp.int32)

    beam_idx = (parent + jnp.arange(b)[:, None] * n_beams).reshape(bk)
    new_tokens = jnp.take(state.tokens, beam_idx, axis=0)
    new_tokens = new_tokens.at[:, state.step].set(token.reshape(bk))
    new_finished = jnp.take(state.finished, beam_idx, axis=0) | (
        token.reshape(bk) == eos_id
    )
    new_state = BeamState(
        tokens=new_tokens,
        scores=top_scores.reshape(bk),
        finished=new_finished,
        step=state.step + 1,
    )
    return new_state, beam_idx


def beam_finalize(state: BeamState, n_beams: int, length_penalty: float = 1.0):
    """Pick the best beam per batch element (normalized by length^alpha)."""
    bk = state.scores.shape[0]
    b = bk // n_beams
    lengths = jnp.argmax(
        jnp.concatenate(
            [state.tokens == 0, jnp.ones((bk, 1), bool)], axis=1
        ).astype(jnp.int32),
        axis=1,
    )
    norm = state.scores / jnp.maximum(lengths, 1) ** length_penalty
    best = jnp.argmax(norm.reshape(b, n_beams), axis=1)
    idx = best + jnp.arange(b) * n_beams
    return jnp.take(state.tokens, idx, axis=0), jnp.take(norm, idx)
