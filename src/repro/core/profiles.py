"""DecodingProfile: ONE request-level decoding API for every strategy.

The paper characterizes workloads whose *decoding strategies* differ as
much as their architectures: Llama/Chameleon I-T sample token-by-token,
Seamless runs beam search with a per-step KV reorder (Obs #4), and
Chameleon T-I decodes two streams per request and combines them
contrastively every step (§2.1.2). Before this module each strategy was
its own engine loop AND its own serving path — plain sampling went
through the continuous-batching pool, beam/contrastive ran batch-at-a-
time, so the scheduler's occupancy/TTFT levers never applied to exactly
the workloads the paper measures.

A ``DecodingProfile`` is a per-request spec of *how to decode*, reduced
to five hooks the pool (core/scheduler.py) and the batch engines
(core/engine.py) both drive:

- ``n_streams``      — KV streams the request occupies (1 for sampling,
                       ``n_beams`` for beam, 2 for contrastive). The
                       scheduler admits a request as a *slot group* of
                       this many slots, all-or-nothing.
- ``stream_prompts`` / ``expand_prompts`` — what each stream prefills
                       (beam: the same prompt per beam; contrastive: the
                       conditional prompt + a null prompt). Streams with
                       identical prompts set ``prefix_shared`` so the
                       paged pool can admit one prefill and SHARE its
                       blocks copy-on-write instead of copying rows.
- ``init``           — fresh per-request decoding state. Pure: preemption
                       replay re-inits and replays token-identically.
- ``step``           — consume the group's per-stream logits, produce the
                       next token to feed each stream, an OPTIONAL
                       intra-group cache permutation (beam's surviving-
                       parent reorder), and per-group done flags.
- ``finalize``       — collapse the state into the request's output
                       (beam: best hypothesis + score).

Hooks are vectorized over G independent groups laid out group-
contiguously: row ``g * n_streams + s`` is group ``g``'s stream ``s``.
The batch engines call them with G = batch. The scheduler keeps one
*lane bank* per distinct profile value: the states of all its resident
groups stacked on a leading lane axis, each lane a G = 1 state.
``lane_step`` advances every lane in one jitted program (the unchanged
``step`` under ``vmap``, each lane's logits rows gathered from the
pool-wide step); ``put_lane`` writes a newly admitted group's state into
its lane, and ``lane_finalize`` finalizes every lane at once. The
programs take the profile as a static argument, so profiles are frozen
dataclasses, hashed by value: equal specs from different requests (or
schedulers) share one bank and one compiled program.

The permutation returned by ``step`` is expressed in flat row indices
(``perm[i]`` = the row whose cache stream ``i`` continues from). How it
is APPLIED is the caller's policy: the batch engines and the contiguous
slot-pool gather cache rows (``kv_cache.reorder_donated``, the paper's
optimized Obs #4 op); the paged pool rewrites host block tables and
shares common-prefix blocks copy-on-write — no device KV gather at all
(vLLM's insight: beam reorder is index manipulation, not data movement).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sampling


@dataclass
class StepOut:
    """One profile step's result for G groups of S streams."""

    state: Any
    feed: jnp.ndarray  # [G*S] next token fed to each stream
    perm: Optional[jnp.ndarray]  # [G*S] flat cache permutation, or None
    done: Optional[jnp.ndarray]  # [G] bool, or None (no early finish)


class DecodingProfile:
    """Base request-level decoding spec. Subclasses override the hooks;
    instances must stay immutable specs — all mutable decoding state lives
    in the object returned by ``init`` (so a preempted request re-inits
    and replays)."""

    #: streams with identical prompts (lets the paged pool share the
    #: prefilled prompt blocks across the group instead of copying them)
    prefix_shared: bool = True

    @property
    def n_streams(self) -> int:
        return 1

    # ---- prompt expansion -------------------------------------------------
    def stream_prompts(self, prompt: np.ndarray) -> List[np.ndarray]:
        """Serving-side: the prompt token ids each stream prefills with.
        All returned prompts must share one length (streams advance in
        lockstep through the pool)."""
        return [np.asarray(prompt, np.int32)] * self.n_streams

    def expand_prompts(
        self,
        prompt_tokens: jnp.ndarray,  # [G, Tp]
        prompt_lengths: jnp.ndarray,  # [G]
        extra_inputs: Optional[Dict[str, jnp.ndarray]],
    ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
        """Batch-engine side: expand [G, Tp] prompts (and per-group extra
        inputs such as encoder frames) to the [G*S, ...] stream layout."""
        s = self.n_streams
        if s == 1:
            return prompt_tokens, prompt_lengths, extra_inputs
        toks = jnp.repeat(prompt_tokens, s, axis=0)
        lens = jnp.repeat(prompt_lengths, s, axis=0)
        extra = None
        if extra_inputs:
            extra = {k: jnp.repeat(v, s, axis=0) for k, v in extra_inputs.items()}
        return toks, lens, extra

    # ---- decode hooks -----------------------------------------------------
    def init(self, n_groups: int, max_new: int) -> Any:
        raise NotImplementedError

    def step(self, state: Any, logits: jnp.ndarray, key: jax.Array) -> StepOut:
        raise NotImplementedError

    def finalize(self, state: Any) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError


# --------------------------------------------------------------------------
# plain sampling (greedy / top-p) — the Llama & Chameleon I-T strategy
# --------------------------------------------------------------------------

@dataclass
class SamplingProfile(DecodingProfile):
    """Single-stream token sampling: greedy at ``temperature <= 0``, else
    nucleus sampling. ``sampler`` overrides the derived sampler with an
    arbitrary callable (the ``engine.generate`` escape hatch); ``live``
    masks dead batch rows exactly as ``engine.generate`` documents."""

    temperature: float = 0.0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    sampler: Optional[sampling.Sampler] = None
    live: Optional[jnp.ndarray] = None  # [G] bool (batch engines only)

    prefix_shared = True

    @property
    def n_streams(self) -> int:
        return 1

    def _sampler(self) -> sampling.Sampler:
        if self.sampler is not None:
            return self.sampler
        if self.temperature <= 0.0:
            return sampling.greedy
        return sampling.top_p(self.top_p, self.temperature)

    @property
    def _fill(self) -> int:
        # finished/dead rows emit only the fill token: EOS when defined,
        # else 0 — the live mask masks garbage even without an EOS id
        return self.eos_id if self.eos_id is not None else 0

    def init(self, n_groups: int, max_new: int) -> Dict[str, Any]:
        done = None
        if self.eos_id is not None or self.live is not None:
            done = (
                jnp.zeros((n_groups,), bool) if self.live is None else ~self.live
            )
        return {
            # pre-filled with the fill token => early exit pads for free
            "tokens": jnp.full((n_groups, max_new), self._fill, jnp.int32),
            "done": done,
            "i": 0,
        }

    def step(self, state, logits, key) -> StepOut:
        token = self._sampler()(logits, key)
        done = state["done"]
        if done is not None:
            if self.eos_id is not None:
                done = done | (token == self.eos_id)  # 1st token may stop a row
            token = jnp.where(done, self._fill, token)
        new_state = {
            "tokens": state["tokens"].at[:, state["i"]].set(token),
            "done": done,
            "i": state["i"] + 1,
        }
        return StepOut(state=new_state, feed=token, perm=None, done=done)

    def finalize(self, state) -> Dict[str, jnp.ndarray]:
        return {"tokens": state["tokens"]}


# --------------------------------------------------------------------------
# speculative (LayerSkip draft/verify) — multi-token pool steps
# --------------------------------------------------------------------------

@dataclass
class SpeculativeProfile(SamplingProfile):
    """Single-stream sampling whose pool steps commit a VARIABLE number
    of tokens: each speculative step greedily drafts up to ``n_draft``
    tokens with the first ``exit_layer`` layers (LayerSkip early exit,
    ``core/layerskip.draft_window``), scores the whole window in ONE
    full-model forward (``engine.verify_step``), commits the accepted
    prefix plus the full model's correction token, and rewinds the
    rejected KV suffix host-side (block-table truncation / a lengths
    rewind — never a device program).

    Every committed token is sampled from FULL-model logits under the
    same per-(request, stream, token-index) key plain pool decoding
    uses, so outputs are bit-identical to a non-speculative run at any
    temperature — ``exit_layer``/``n_draft`` only move the
    acceptance-rate/throughput trade-off, never the tokens. The serving
    scheduler detects this subclass and routes the slot through its
    draft/verify step (core/scheduler.py ``_step_speculative``); the
    batch engines treat it as its ``SamplingProfile`` base. This is the
    seam multi-head drafters (Medusa/EAGLE-style) plug into later: only
    the draft executable changes."""

    exit_layer: int = 1
    n_draft: int = 4


# --------------------------------------------------------------------------
# beam search — the Seamless S-T/T-T strategy (paper Obs #4)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class BeamProfile(DecodingProfile):
    """Beam search over ``n_beams`` streams: every step rescores the
    ``n_beams * V`` candidates, keeps the top ``n_beams``, and re-binds
    each stream to its surviving parent's cache via the returned
    permutation — the paper's KV_Cache_Reorder op, which the paged pool
    turns into a pure host-side block-table permutation."""

    n_beams: int
    eos_id: int
    length_penalty: float = 1.0

    prefix_shared = True  # every beam prefills the same prompt

    @property
    def n_streams(self) -> int:
        return self.n_beams

    def init(self, n_groups: int, max_new: int) -> sampling.BeamState:
        return sampling.beam_init(n_groups, self.n_beams, max_new)

    def step(self, state, logits, key) -> StepOut:
        state, beam_idx = sampling.beam_step(
            state, logits, self.n_beams, self.eos_id, self.length_penalty
        )
        done = state.finished.reshape(-1, self.n_beams).all(axis=1)
        return StepOut(
            state=state,
            feed=state.tokens[:, state.step - 1],
            perm=beam_idx,
            done=done,
        )

    def finalize(self, state) -> Dict[str, jnp.ndarray]:
        tokens, scores = sampling.beam_finalize(
            state, self.n_beams, self.length_penalty
        )
        return {"tokens": tokens, "scores": scores}


# --------------------------------------------------------------------------
# contrastive / classifier-free guidance — the Chameleon T-I strategy
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ContrastiveProfile(DecodingProfile):
    """Two streams per request — conditional (sees the prompt) and
    unconditional (sees a null prompt) — each step runs BOTH (the paper's
    "decodes twice at each time step"), combines their logits as
    ``uncond + guidance * (cond - uncond)``, optionally restricts to the
    image-token range (``mask_offset``), samples ONE token, and feeds it
    to both streams. No cache permutation ever."""

    uncond_token: int
    guidance: float = 3.0
    mask_offset: Optional[int] = None  # restrict sampling to ids >= offset
    temperature: float = 0.0
    top_p: float = 1.0
    sampler: Optional[sampling.Sampler] = None

    prefix_shared = False  # cond and uncond prefill different prompts

    @property
    def n_streams(self) -> int:
        return 2

    def _sampler(self) -> sampling.Sampler:
        if self.sampler is not None:
            return self.sampler
        if self.temperature <= 0.0:
            return sampling.greedy
        return sampling.top_p(self.top_p, self.temperature)

    def stream_prompts(self, prompt: np.ndarray) -> List[np.ndarray]:
        p = np.asarray(prompt, np.int32)
        return [p, np.full_like(p, self.uncond_token)]

    def expand_prompts(self, prompt_tokens, prompt_lengths, extra_inputs):
        g, tp = prompt_tokens.shape
        uncond = jnp.full((g, tp), self.uncond_token, jnp.int32)
        # group-contiguous interleave: [c0, u0, c1, u1, ...]
        toks = jnp.stack([prompt_tokens, uncond], axis=1).reshape(2 * g, tp)
        lens = jnp.repeat(prompt_lengths, 2, axis=0)
        extra = None
        if extra_inputs:
            extra = {k: jnp.repeat(v, 2, axis=0) for k, v in extra_inputs.items()}
        return toks, lens, extra

    def init(self, n_groups: int, max_new: int) -> Dict[str, Any]:
        return {"tokens": jnp.zeros((n_groups, max_new), jnp.int32),
                "i": jnp.zeros((), jnp.int32)}

    def step(self, state, logits, key) -> StepOut:
        from repro.models import vlm  # the paper's T-I math lives there

        cond, uncond = logits[0::2], logits[1::2]  # [G, V] each
        mixed = vlm.contrastive_logits(cond, uncond, self.guidance)
        if self.mask_offset is not None:
            mixed = vlm.image_token_mask(self.mask_offset, mixed)
        token = self._sampler()(mixed, key)  # [G]
        new_state = {
            "tokens": state["tokens"].at[:, state["i"]].set(token),
            "i": state["i"] + 1,
        }
        # both streams advance on the same sampled token
        return StepOut(
            state=new_state, feed=jnp.repeat(token, 2), perm=None, done=None
        )

    def finalize(self, state) -> Dict[str, jnp.ndarray]:
        return {"tokens": state["tokens"]}


def n_streams_of(profile: Optional[DecodingProfile]) -> int:
    """Streams a request occupies (1 when it has no profile spec)."""
    return 1 if profile is None else profile.n_streams


# --------------------------------------------------------------------------
# lane banks — every resident group of one profile in one program
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def lane_step(profile: DecodingProfile, state, logits: jnp.ndarray,
              lanes: jnp.ndarray, base_key: jax.Array):
    """One ``profile.step`` for every lane of a bank. ``lanes`` [L, S + 3]
    int32 packs each lane's pool rows (its group's S slots), then its
    rid, token index and live flag. Lane ``l`` steps on ``logits[rows[l]]``
    under the key ``fold_in(request_key(base_key, rid), n_generated)`` —
    the key a group's own step would use — and a dead lane keeps its
    state. Returns (state, feed [L, S], perm [L, S] or None, done [L] or
    None)."""
    rows, rid, n_gen = lanes[:, :-3], lanes[:, -3], lanes[:, -2]
    live = lanes[:, -1] > 0

    def one(st, lane_rows, r, n):
        key = jax.random.fold_in(sampling.request_key(base_key, r), n)
        out = profile.step(st, logits[lane_rows], key)
        return out.state, out.feed, out.perm, out.done

    new, feed, perm, done = jax.vmap(one)(state, rows, rid, n_gen)
    state = jax.tree.map(
        lambda a, b: jnp.where(live.reshape((-1,) + (1,) * (a.ndim - 1)),
                               a, b),
        new, state)
    return state, feed, perm, None if done is None else done[:, 0]


@functools.partial(jax.jit, donate_argnums=0)
def put_lane(state, lane, lane_state):
    """Write one lane's G = 1 state into a bank (``lane`` is traced: one
    program for every lane)."""
    return jax.tree.map(lambda b, x: b.at[lane].set(x), state, lane_state)


@functools.partial(jax.jit, static_argnums=0)
def lane_finalize(profile: DecodingProfile, state) -> Dict[str, jnp.ndarray]:
    """``profile.finalize`` of every lane: each output gains a leading
    lane axis over its G = 1 shape."""
    return jax.vmap(profile.finalize)(state)
