"""Serving launcher: continuous-batching request serving over the KV
slot-pool (core/slot_pool.py + core/scheduler.py).

The serving stack is the paper's inference pipeline run as a persistent
engine: ONE compiled single-slot prefill executable admits requests into
free slots, ONE compiled pool-wide decode-step executable is replayed
forever, and the scheduler recycles slots the moment a request finishes
(per-slot EOS / max-new) — so the decode batch stays as full as the queue
allows (the Obs #2 idle-time lever). ``--policy fixed`` degrades the same
machinery to the seed's run-to-completion batcher for A/B comparison.
``--paged`` swaps the contiguous per-slot reservation for the vLLM-style
block-pool (core/slot_pool.BlockPool): same token streams, but the cache
only reserves ``num_blocks * block_size`` tokens instead of
``slots * (pad_to + max_new_cap)`` — the Fig 1 capacity lever.
``--chunked`` (with ``--paged``) turns admission itself into pool-wide
work: prompts stream into their slot's KV blocks ``--prefill-budget``
tokens per step inside the mixed-step executable (core/prefill.py), so a
new request never freezes resident decoding behind a full prefill.
``--profile-mix`` cycles per-request decoding profiles over the trace
(core/profiles.py): beam requests become ``n_beams``-slot groups with
the Obs #4 KV reorder done as a host-side block-table permutation under
``--paged``, contrastive requests 2-slot cond/uncond groups — the
paper's Seamless and Chameleon T-I decoding strategies served through
the SAME continuous-batching pool as plain sampling. A ``speculative``
kind in the mix serves those requests as LayerSkip draft/verify windows
(core/scheduler.py ``SpeculativeProfile``): up to ``--n-draft`` + 1
tokens commit per pool step, token-identical to plain decoding, with
acceptance-rate and tokens-per-step counters in the report.
``--prefix-cache`` (with ``--chunked``) turns shared-prompt traffic into
near-free prefill: a radix trie keyed by full-block spans of prompt
tokens (core/prefix_cache.py) lets each admission adopt every cached
block refcount-shared and prefill only the uncached suffix —
bit-identical tokens at any temperature, reported as
prefill-tokens-skipped / hit-rate / cached-block occupancy.
``--shared-prefix N`` generates the matching trace (N system prompts
reused Zipf-style under bursty Poisson arrivals), and ``--boost-after T``
ages waiting requests (+1 priority per T seconds unadmitted) so
low-priority requests cannot starve behind a hot high-priority queue.

Reported per request: TTFT (arrival -> first token), TPOT (mean inter-
token), e2e latency; aggregate: tokens/s, mean slot-occupancy (the
direct idle-time metric — fraction of decode-slot work that was real),
and the decode-stall-per-admission metric (chunked prefill's target):
for each admission that landed while residents were decoding, the
inter-step interval its work sat inside — i.e. the inter-token gap it
imposed on every resident (p50 gates, max shows the tail).

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --n-requests 8 --batch-slots 4 --max-new 16 --arrival-rate 16

The legacy fixed-slot batcher (``BatchServer``) is kept as the thin
``engine.generate`` front-end (and its live-mask test coverage); the A/B
benchmark's baseline arm is ``Scheduler(policy="fixed")``, NOT this class.
Partial batches now mask dead slots via ``live`` (garbage rows emit only
the fill token and never block the EOS early-exit).
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core import engine, profiles, sampling
from repro.core.scheduler import Scheduler, ServeRequest
from repro.launch import compile_cache
from repro.models import get_model
from repro.training import data as data_mod


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    t_submit: float = field(default_factory=time.perf_counter)
    t_done: Optional[float] = None
    output: Optional[np.ndarray] = None


class BatchServer:
    """Fixed-slot batcher (the paper's unoptimized baseline): pulls up to
    ``slots`` requests, right-pads the prompts, runs prefill + decode to
    completion. Partial batches mask their dead slots (``live``) so padding
    rows neither block the EOS early-exit nor leak garbage outputs."""

    def __init__(self, model, params, *, slots: int, sampler=None,
                 eos_id: Optional[int] = None):
        self.model = model
        self.params = params
        self.slots = slots
        self.sampler = sampler or sampling.top_p(0.9)
        self.eos_id = eos_id

    def serve(self, requests: List[Request], *, pad_to: int, max_new: int):
        done: List[Request] = []
        queue = list(requests)
        while queue:
            batch = queue[: self.slots]
            queue = queue[self.slots:]
            prompts = np.zeros((self.slots, pad_to), np.int32)
            lengths = np.ones((self.slots,), np.int32)
            live = np.zeros((self.slots,), bool)
            for i, r in enumerate(batch):
                p = r.prompt[:pad_to]
                prompts[i, : len(p)] = p
                lengths[i] = len(p)
                live[i] = True
            out = engine.generate(
                self.model, self.params, jnp.asarray(prompts),
                prompt_lengths=jnp.asarray(lengths),
                max_new_tokens=max_new, sampler=self.sampler,
                key=jax.random.PRNGKey(len(done)),
                eos_id=self.eos_id, live=jnp.asarray(live),
            )
            toks = np.asarray(out["tokens"])  # always [slots, max_new]
            for i, r in enumerate(batch):
                r.output = toks[i, : r.max_new]
                r.t_done = time.perf_counter()
                done.append(r)
        return done


# --------------------------------------------------------------------------
# trace + metrics
# --------------------------------------------------------------------------

def poisson_trace(
    profile: data_mod.LengthProfile,
    n_requests: int,
    *,
    pad_to: int,
    max_new_cap: int,
    vocab_size: int,
    arrival_rate: float,
    seed: int = 0,
    temperature: float = 0.0,
    top_p: float = 1.0,
) -> List[ServeRequest]:
    """Requests with paper-profile lengths and Poisson (exponential
    inter-arrival) arrival offsets; rate <= 0 means all arrive at t=0."""
    rng = np.random.default_rng(seed)
    ins, outs = data_mod.sample_lengths(profile, n_requests, seed=seed + 1)
    t = 0.0
    reqs = []
    for i in range(n_requests):
        if arrival_rate > 0:
            t += rng.exponential(1.0 / arrival_rate)
        reqs.append(
            ServeRequest(
                rid=i,
                prompt=rng.integers(0, vocab_size, size=min(int(ins[i]), pad_to)),
                max_new=max(1, min(int(outs[i]), max_new_cap)),
                t_arrival=t if arrival_rate > 0 else 0.0,
                temperature=temperature,
                top_p=top_p,
            )
        )
    return reqs


def shared_prefix_trace(
    n_requests: int,
    *,
    n_prefixes: int,
    prefix_len: int,
    pad_to: int,
    max_new_cap: int,
    vocab_size: int,
    arrival_rate: float,
    zipf_a: float = 1.1,
    burst_size: int = 4,
    seed: int = 0,
    temperature: float = 0.0,
    top_p: float = 1.0,
) -> List[ServeRequest]:
    """Shared-system-prompt trace — the dominant production chat shape
    and the workload the cross-request prefix cache (--prefix-cache)
    exists for. ``n_prefixes`` distinct system prompts of ``prefix_len``
    tokens are reused Zipf-style (prompt rank ``r`` drawn with
    probability ``r**-zipf_a``, normalized — a few prompts dominate, a
    long tail stays cold), each followed by a fresh random suffix of
    1..(pad_to - prefix_len) tokens. Arrivals are bursty Poisson:
    exponential gaps between bursts of 1..``burst_size`` requests that
    land effectively simultaneously (1 ms apart), with the gap mean
    scaled so the long-run rate stays ``arrival_rate``; rate <= 0 means
    all arrive at t=0. Also meaningful under ``--replicas``: each
    replica keeps its own independent trie, so fleet hit-rate depends on
    placement locality, not just the trace."""
    if not 0 < prefix_len < pad_to:
        raise ValueError("need 0 < prefix_len < pad_to")
    if n_prefixes < 1 or n_requests < 1:
        raise ValueError("need n_prefixes >= 1 and n_requests >= 1")
    rng = np.random.default_rng(seed)
    prefixes = rng.integers(0, vocab_size, size=(n_prefixes, prefix_len))
    ranks = np.arange(1, n_prefixes + 1, dtype=np.float64)
    pmf = ranks ** -zipf_a
    pmf /= pmf.sum()
    max_suffix = pad_to - prefix_len
    t, burst_left = 0.0, 0
    reqs: List[ServeRequest] = []
    for i in range(n_requests):
        if arrival_rate > 0:
            if burst_left == 0:
                t += rng.exponential(burst_size / arrival_rate)
                burst_left = int(rng.integers(1, burst_size + 1))
            else:
                t += 1e-3
            burst_left -= 1
        j = int(rng.choice(n_prefixes, p=pmf))
        suffix = rng.integers(
            0, vocab_size, size=int(rng.integers(1, max_suffix + 1))
        )
        reqs.append(
            ServeRequest(
                rid=i,
                prompt=np.concatenate([prefixes[j], suffix]),
                max_new=int(rng.integers(1, max_new_cap + 1)),
                t_arrival=t if arrival_rate > 0 else 0.0,
                temperature=temperature,
                top_p=top_p,
            )
        )
    return reqs


def apply_profile_mix(
    requests: List[ServeRequest],
    mix: str,
    *,
    n_beams: int = 2,
    beam_eos_id: int = 2,
    guidance: float = 2.0,
    uncond_token: int = 0,
    mask_offset: Optional[int] = None,
    exit_layer: int = 1,
    n_draft: int = 4,
) -> List[ServeRequest]:
    """Cycle decoding profiles over a trace: ``mix`` is a comma list of
    kinds (``greedy`` | ``beam`` | ``contrastive`` | ``speculative``)
    assigned round-robin by request order — deterministic, so A/B arms
    see identical work. ``greedy`` leaves the request on the per-slot
    sampling path; ``speculative`` keeps the request's own
    (temperature, top_p) — draft/verify windows are bit-identical to
    plain decoding at any temperature, so the mix only changes HOW MANY
    tokens each pool step commits."""
    kinds = [k.strip() for k in mix.split(",") if k.strip()]
    for i, r in enumerate(requests):
        kind = kinds[i % len(kinds)]
        if kind == "greedy":
            r.profile = None
        elif kind == "beam":
            r.profile = profiles.BeamProfile(n_beams=n_beams, eos_id=beam_eos_id)
        elif kind == "contrastive":
            r.profile = profiles.ContrastiveProfile(
                uncond_token=uncond_token, guidance=guidance,
                mask_offset=mask_offset,
            )
        elif kind == "speculative":
            r.profile = profiles.SpeculativeProfile(
                temperature=r.temperature, top_p=r.top_p,
                eos_id=r.eos_id, exit_layer=exit_layer, n_draft=n_draft,
            )
        else:
            raise ValueError(f"unknown profile kind {kind!r}")
    return requests


def mix_class_trace(
    profile: data_mod.LengthProfile,
    n_requests: int,
    *,
    pad_to: int,
    max_new_cap: int,
    vocab_size: int,
    arrival_rate: float,
    classes: Sequence[str] = (
        "greedy", "sampling", "beam", "cfg", "speculative"
    ),
    burst_size: int = 4,
    seed: int = 0,
    temperature: float = 0.8,
    top_p: float = 0.9,
    n_beams: int = 2,
    beam_eos_id: int = 2,
    guidance: float = 2.0,
    uncond_token: int = 0,
    mask_offset: Optional[int] = None,
    exit_layer: int = 1,
    n_draft: int = 4,
) -> List[ServeRequest]:
    """Heterogeneous production-shaped trace: every request draws an SLA
    class at random (seeded) from ``classes`` — ``greedy`` (temp 0),
    ``sampling`` (the given temperature/top_p), ``beam`` (a BeamProfile
    slot group), ``cfg`` (classifier-free guidance, a ContrastiveProfile
    pair; ``contrastive`` is accepted as an alias), ``speculative``
    (draft/verify windows) — with BURSTY Poisson arrivals (exponential
    gaps between bursts of 1..``burst_size`` requests landing ~1 ms
    apart, long-run rate = ``arrival_rate``; rate <= 0 means all at
    t=0). Unlike :func:`apply_profile_mix`'s round-robin (built for A/B
    arms that need identical work), this is the workload the per-class
    p50/p99 TTFT/TPOT breakdown in :func:`serve_metrics` exists to
    measure: interleaved classes contending for the same pool slots."""
    known = {"greedy", "sampling", "beam", "cfg", "contrastive",
             "speculative"}
    classes = [c.strip() for c in classes if c.strip()]
    for c in classes:
        if c not in known:
            raise ValueError(f"unknown request class {c!r}")
    if not classes:
        raise ValueError("need at least one request class")
    rng = np.random.default_rng(seed)
    ins, outs = data_mod.sample_lengths(profile, n_requests, seed=seed + 1)
    t, burst_left = 0.0, 0
    reqs: List[ServeRequest] = []
    for i in range(n_requests):
        if arrival_rate > 0:
            if burst_left == 0:
                t += rng.exponential(burst_size / arrival_rate)
                burst_left = int(rng.integers(1, burst_size + 1))
            else:
                t += 1e-3
            burst_left -= 1
        cls = classes[int(rng.integers(0, len(classes)))]
        r = ServeRequest(
            rid=i,
            prompt=rng.integers(0, vocab_size, size=min(int(ins[i]), pad_to)),
            max_new=max(1, min(int(outs[i]), max_new_cap)),
            t_arrival=t if arrival_rate > 0 else 0.0,
            temperature=temperature if cls == "sampling" else 0.0,
            top_p=top_p if cls == "sampling" else 1.0,
        )
        if cls == "beam":
            r.profile = profiles.BeamProfile(
                n_beams=n_beams, eos_id=beam_eos_id
            )
        elif cls in ("cfg", "contrastive"):
            r.profile = profiles.ContrastiveProfile(
                uncond_token=uncond_token, guidance=guidance,
                mask_offset=mask_offset,
            )
        elif cls == "speculative":
            r.profile = profiles.SpeculativeProfile(
                temperature=r.temperature, top_p=r.top_p,
                exit_layer=exit_layer, n_draft=n_draft,
            )
        reqs.append(r)
    return reqs


def request_class(r: ServeRequest) -> str:
    """SLA class of one request for the per-class latency breakdown:
    ``beam`` / ``contrastive`` (multi-stream slot groups), ``speculative``
    (draft/verify windows), else ``greedy`` or ``sampling`` by
    temperature. Classes, not rids, are what production SLOs quote."""
    p = r.profile
    if isinstance(p, profiles.SpeculativeProfile):
        return "speculative"
    if p is not None and profiles.n_streams_of(p) > 1:
        return type(p).__name__.replace("Profile", "").lower()
    return "greedy" if r.temperature <= 0.0 else "sampling"


def serve_metrics(done: List[ServeRequest], wall: float) -> Dict[str, object]:
    total_tok = sum(len(r.tokens) for r in done)
    ttft = [r.ttft for r in done]
    tpot = [r.tpot for r in done if len(r.tokens) > 1]
    e2e = [r.e2e for r in done]
    per_class: Dict[str, Dict[str, float]] = {}
    for cls in sorted({request_class(r) for r in done}):
        rs = [r for r in done if request_class(r) == cls]
        c_ttft = [r.ttft for r in rs]
        c_tpot = [r.tpot for r in rs if len(r.tokens) > 1]
        per_class[cls] = {
            "n_requests": len(rs),
            "ttft_p50_ms": float(np.percentile(c_ttft, 50)) * 1e3,
            "ttft_p99_ms": float(np.percentile(c_ttft, 99)) * 1e3,
            "tpot_p50_ms": (
                float(np.percentile(c_tpot, 50)) * 1e3 if c_tpot else 0.0
            ),
            "tpot_p99_ms": (
                float(np.percentile(c_tpot, 99)) * 1e3 if c_tpot else 0.0
            ),
        }
    return {
        "n_requests": len(done),
        "total_tokens": total_tok,
        "tokens_per_s": total_tok / max(wall, 1e-9),
        "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
        "ttft_p99_ms": float(np.percentile(ttft, 99)) * 1e3,
        "tpot_p50_ms": (float(np.percentile(tpot, 50)) * 1e3) if tpot else 0.0,
        "e2e_p50_s": float(np.percentile(e2e, 50)),
        "e2e_p99_s": float(np.percentile(e2e, 99)),
        "per_class": per_class,
    }


def run_scheduler(
    model, params, requests: List[ServeRequest], *,
    slots: int, pad_to: int, max_new_cap: int,
    eos_id: Optional[int] = None, policy: str = "continuous",
    paged: bool = False, block_size: int = 16,
    num_blocks: Optional[int] = None, chunked: bool = False,
    prefill_budget: Optional[int] = None,
    prefix_cache: bool = False,
    priority_boost_after: Optional[float] = None, seed: int = 0,
    replicas: Optional[int] = None, devices="auto",
    tp: Optional[int] = None,
    return_requests: bool = False,
):
    """Serve one trace; returns metrics (plus the scheduler's counters).
    Paged mode reports the block-level memory picture: bytes the pool
    keeps RESERVED vs the bytes its peak block working set actually USED
    (the reserved-but-unused gap is what paging reclaims, Fig 1).
    ``replicas=N`` routes the trace through a ReplicaRouter — N
    data-parallel pools of THIS geometry behind one shared queue — and
    merges in the fleet metrics (spills, requeues, per-replica report,
    and the busy-time aggregate service rate). ``replicas=1`` is a
    one-replica router (the symmetric-accounting baseline the scaling
    bench compares against); ``None`` (default) is the plain scheduler.
    ``tp=N`` shards the pool's executables + KV cache over an N-device
    ("model",) mesh (distributed/tp_pool.py) — composable with
    ``replicas`` (DP x TP: each replica serves on its own disjoint
    submesh); tokens are identical to single-device serving."""
    if replicas is not None:
        return _run_router(
            model, params, requests, replicas=replicas, devices=devices,
            slots=slots, pad_to=pad_to, max_new_cap=max_new_cap,
            eos_id=eos_id, policy=policy, paged=paged, block_size=block_size,
            num_blocks=num_blocks, chunked=chunked,
            prefill_budget=prefill_budget, prefix_cache=prefix_cache,
            priority_boost_after=priority_boost_after, seed=seed, tp=tp,
            return_requests=return_requests,
        )
    tp_mesh = None
    if tp is not None and tp > 1:
        from repro.distributed import tp_pool

        tp_mesh = tp_pool.make_tp_mesh(tp)
    sched = Scheduler(
        model, params, slots=slots, pad_to=pad_to, max_new_cap=max_new_cap,
        eos_id=eos_id, policy=policy, paged=paged, block_size=block_size,
        num_blocks=num_blocks, chunked=chunked, prefill_budget=prefill_budget,
        prefix_cache=prefix_cache, priority_boost_after=priority_boost_after,
        base_key=jax.random.PRNGKey(seed), tp_mesh=tp_mesh,
    )
    t0 = time.perf_counter()
    done = sched.run(requests)
    wall = time.perf_counter() - t0
    m = serve_metrics(done, wall)
    # decode-stall-per-admission, measured directly by the scheduler: the
    # inter-step (= resident inter-token) interval each admission's work
    # sat inside. The p50 is the noise-robust gate statistic — EVERY
    # unchunked admission pays a full prefill inside its gap, so the
    # median separates chunked/unchunked structurally; the max is
    # reported for tail visibility but is wall-clock-noise dominated.
    stalls = np.asarray(sched.admission_stalls, np.float64)
    m.update(
        wall_s=wall,
        decode_steps=sched.n_decode_steps,
        prefills=sched.n_prefills,
        mean_slot_occupancy=sched.mean_occupancy,
        kv_reserved_bytes=sched.pool.reserved_bytes,
        n_admission_stalls=len(stalls),
        admission_stall_p50_ms=(
            float(np.percentile(stalls, 50)) * 1e3 if len(stalls) else 0.0
        ),
        admission_stall_max_ms=(
            float(stalls.max()) * 1e3 if len(stalls) else 0.0
        ),
    )
    if tp_mesh is not None:
        from repro.distributed import tp_pool

        m.update(
            tp=tp,
            # physical per-device footprint: ~1/TP of the logical pool
            # plus the tiny replicated lengths/block-table leaves
            kv_reserved_per_device_bytes=tp_pool.max_per_device_bytes(
                sched.pool.cache
            ),
        )
    if sched.n_group_admissions:
        m.update(
            group_admissions=sched.n_group_admissions,
            cache_reorders=sched.n_cache_reorders,  # contiguous beam fallback
            block_permutes=sched.n_block_permutes,  # paged beam reorders
        )
    if sched.n_spec_steps:
        m.update(
            spec_steps=sched.n_spec_steps,
            spec_acceptance=(
                sched.n_spec_accepted / max(sched.n_spec_drafted, 1)
            ),
            # mean tokens committed per speculative slot-step (> 1 means
            # the draft/verify pair beat one-token-at-a-time stepping)
            spec_tokens_per_step=(
                sched.n_spec_committed / max(sched.n_spec_slot_steps, 1)
            ),
            spec_commit_hist={
                str(k): v for k, v in sorted(sched.spec_commit_hist.items())
            },
        )
    if paged:
        token_bytes = sched.pool.reserved_bytes / max(
            sched.pool.num_blocks * sched.pool.block_size, 1
        )
        m.update(
            n_preemptions=sched.n_preemptions,
            mean_block_occupancy=sched.mean_block_occupancy,
            kv_used_peak_bytes=int(
                sched.peak_used_blocks * sched.pool.block_size * token_bytes
            ),
        )
        if sched.n_group_admissions:
            m.update(cow_copies=sched.pool.n_cow_copies)
    if chunked:
        m.update(
            mixed_steps=sched.n_mixed_steps,
            prefill_chunks=sched.n_chunks,
            prefill_chunk_tokens=sched.n_chunk_tokens,
            # must stay 0 under chunking, except slot-group admissions
            # (multi-stream profiles take the dense prefill path)
            full_prefills=sched.n_prefills,
        )
    if prefix_cache:
        m.update(
            prefix_lookups=sched.n_prefix_lookups,
            prefix_hits=sched.n_prefix_hits,
            prefix_hit_rate=sched.prefix_hit_rate,
            # prompt tokens served straight out of cached KV blocks — the
            # prefill compute (and TTFT latency) the cache removed
            prefill_tokens_skipped=sched.n_prefix_tokens_skipped,
            prefix_blocks_reclaimed=sched.n_prefix_reclaimed,
            mean_cached_blocks=sched.mean_cached_blocks,
        )
    if priority_boost_after is not None:
        m.update(priority_boosts=sched.n_priority_boosts)
    if return_requests:
        return m, done
    return m


def _run_router(
    model, params, requests: List[ServeRequest], *,
    replicas: int, devices, slots: int, pad_to: int, max_new_cap: int,
    eos_id: Optional[int], policy: str, paged: bool, block_size: int,
    num_blocks: Optional[int], chunked: bool,
    prefill_budget: Optional[int], prefix_cache: bool,
    priority_boost_after: Optional[float], seed: int,
    tp: Optional[int] = None, return_requests: bool = False,
):
    """Replica-routed arm of ``run_scheduler``: one shared queue over N
    data-parallel pools (core/router.py). ``tokens_per_s`` stays the real
    wall-clock rate (replicas time-share a single-device host);
    ``aggregate_tokens_per_s`` is the fleet service rate — total tokens
    over the SLOWEST replica's device-busy seconds, i.e. the wall a real
    one-device-per-replica deployment would take — which is what the
    near-linear-scaling bench gate measures."""
    from repro.core.router import ReplicaRouter

    if policy != "continuous":
        raise ValueError("replica routing requires policy='continuous'")
    router = ReplicaRouter(
        model, params, replicas=replicas, devices=devices, slots=slots,
        pad_to=pad_to, max_new_cap=max_new_cap, eos_id=eos_id, paged=paged,
        block_size=block_size, num_blocks=num_blocks, chunked=chunked,
        prefill_budget=prefill_budget, prefix_cache=prefix_cache,
        priority_boost_after=priority_boost_after,
        base_key=jax.random.PRNGKey(seed), tp=tp,
    )
    t0 = time.perf_counter()
    done = router.run(requests)
    wall = time.perf_counter() - t0
    m = serve_metrics(done, wall)
    stalls = np.asarray(router.admission_stalls, np.float64)
    m.update(
        wall_s=wall,
        replicas=replicas,
        decode_steps=router.n_decode_steps,
        steps_max=router.steps_max,
        prefills=router.n_prefills,
        mean_slot_occupancy=router.mean_occupancy,
        kv_reserved_bytes=router.reserved_bytes,
        n_admission_stalls=len(stalls),
        admission_stall_p50_ms=(
            float(np.percentile(stalls, 50)) * 1e3 if len(stalls) else 0.0
        ),
        admission_stall_max_ms=(
            float(stalls.max()) * 1e3 if len(stalls) else 0.0
        ),
        spills=router.n_spills,
        requeues=router.n_requeues,
        busy_max_s=router.busy_max_s,
        aggregate_tokens_per_s=(
            m["total_tokens"] / max(router.busy_max_s, 1e-9)
        ),
        per_replica=router.replica_report(done),
    )
    if router.tp is not None:
        from repro.distributed import tp_pool

        m.update(
            tp=router.tp,
            kv_reserved_per_device_bytes=max(
                tp_pool.max_per_device_bytes(s.pool.cache)
                for s in router.replicas
            ),
        )
    if paged:
        bo = [s.mean_block_occupancy for s in router.replicas
              if s.block_occupancy_trace]
        pool0 = router.replicas[0].pool
        token_bytes = pool0.reserved_bytes / max(
            pool0.num_blocks * pool0.block_size, 1
        )
        m.update(
            n_preemptions=router.n_preemptions,
            mean_block_occupancy=float(sum(bo) / len(bo)) if bo else 0.0,
            kv_used_peak_bytes=int(sum(
                s.peak_used_blocks * s.pool.block_size * token_bytes
                for s in router.replicas
            )),
        )
    if chunked:
        m.update(
            mixed_steps=router.n_mixed_steps,
            prefill_chunks=sum(s.n_chunks for s in router.replicas),
            prefill_chunk_tokens=sum(
                s.n_chunk_tokens for s in router.replicas
            ),
            full_prefills=router.n_prefills,
        )
    if prefix_cache:
        m.update(
            prefix_lookups=router.n_prefix_lookups,
            prefix_hits=router.n_prefix_hits,
            prefix_hit_rate=router.prefix_hit_rate,
            prefill_tokens_skipped=router.n_prefix_tokens_skipped,
            prefix_blocks_reclaimed=router.n_prefix_reclaimed,
            mean_cached_blocks=router.mean_cached_blocks,
        )
    if priority_boost_after is not None:
        m.update(priority_boosts=router.n_priority_boosts)
    if return_requests:
        return m, done
    return m


def warmup(model, params, *, slots: int, pad_to: int, max_new_cap: int,
           paged: bool = False, block_size: int = 16,
           num_blocks: Optional[int] = None, chunked: bool = False,
           prefill_budget: Optional[int] = None,
           prefix_cache: bool = False,
           profile_mix: bool = False, n_beams: int = 2,
           speculative: bool = False, exit_layer: int = 1,
           n_draft: int = 4, tp: Optional[int] = None,
           replicas: Optional[int] = None) -> None:
    """Compile the serving executables (single-slot prefill, pool decode
    step, slot scatter — plus block copy/length scatter when paged, plus
    the mixed step when chunked) before any timed run. ``profile_mix``
    additionally warms the slot-group path: a beam group (beam-step top_k,
    CoW block copy / contiguous reorder) and a contrastive pair.
    ``speculative`` warms the draft/verify pair at the given
    (exit_layer, n_draft) geometry. ``prefix_cache`` warms block
    adoption (``kv_cache.set_slot_length`` at the adopt signature) by
    serving a prompt twice — the replay hits the trie. ``tp`` warms the
    sharded TP step family on its own mesh instead. ``replicas`` warms
    every replica of the router ``run_scheduler(replicas=...)`` builds:
    an executable is compiled per device or submesh, so each replica's
    placement needs its own warm run."""
    if replicas is not None:
        from repro.core.router import ReplicaRouter

        scheds = ReplicaRouter(
            model, params, replicas=replicas, slots=slots, pad_to=pad_to,
            max_new_cap=max_new_cap, paged=paged, block_size=block_size,
            num_blocks=num_blocks, chunked=chunked,
            prefill_budget=prefill_budget, prefix_cache=prefix_cache, tp=tp,
        ).replicas
    else:
        tp_mesh = None
        if tp is not None and tp > 1:
            from repro.distributed import tp_pool

            tp_mesh = tp_pool.make_tp_mesh(tp)
        scheds = [Scheduler(
            model, params, slots=slots, pad_to=pad_to,
            max_new_cap=max_new_cap, paged=paged, block_size=block_size,
            num_blocks=num_blocks, chunked=chunked,
            prefill_budget=prefill_budget, prefix_cache=prefix_cache,
            tp_mesh=tp_mesh,
        )]
    for sched in scheds:
        rng = np.random.default_rng(0)
        full_prompt = rng.integers(0, 8, size=pad_to)
        reqs = [
            ServeRequest(rid=0, prompt=full_prompt, max_new=2),
            ServeRequest(rid=1, prompt=rng.integers(0, 8, size=3), max_new=2),
        ]
        if profile_mix and slots >= max(n_beams, 2):
            reqs.append(ServeRequest(
                rid=2, prompt=rng.integers(0, 8, size=3), max_new=2,
                profile=profiles.BeamProfile(n_beams=n_beams, eos_id=2),
            ))
            reqs.append(ServeRequest(
                rid=3, prompt=rng.integers(0, 8, size=3), max_new=2,
                profile=profiles.ContrastiveProfile(uncond_token=0),
            ))
        if speculative:
            # max_new > n_draft + 1 so the warm run takes at least one full
            # draft+verify step at the serving window geometry
            reqs.append(ServeRequest(
                rid=4, prompt=rng.integers(0, 8, size=3),
                max_new=min(n_draft + 2, max_new_cap),
                profile=profiles.SpeculativeProfile(
                    exit_layer=exit_layer, n_draft=n_draft,
                ),
            ))
        sched.run(reqs)
        if prefix_cache:
            # rid 0's prompt blocks are in the trie now; its twin ADOPTS them
            sched.run([ServeRequest(rid=5, prompt=full_prompt, max_new=2)])


def main(argv=None, *, return_requests: bool = False):
    """Serve one generated trace and print its metrics; returns the metrics
    dict (with ``return_requests``, also the finished requests, as
    :func:`run_scheduler` does)."""
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--policy", choices=["continuous", "fixed"],
                    default="continuous")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV block-pool instead of per-slot rows")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="physical KV blocks incl. the sink block; default "
                         "= full per-slot parity (no memory saving)")
    ap.add_argument("--chunked", action="store_true",
                    help="chunked prefill (requires --paged): admission "
                         "streams prompts into KV blocks inside the "
                         "pool-wide mixed step instead of stalling it")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="prefill tokens per mixed step; default = "
                         "--block-size")
    ap.add_argument("--profile-mix", default=None,
                    help="comma list of decoding profiles cycled over the "
                         "trace (greedy | beam | contrastive | "
                         "speculative), e.g. 'greedy,beam,contrastive' — "
                         "beam/contrastive requests serve as slot GROUPS, "
                         "speculative ones decode draft/verify windows")
    ap.add_argument("--n-beams", type=int, default=2,
                    help="beams per beam-profile request (--profile-mix)")
    ap.add_argument("--guidance", type=float, default=2.0,
                    help="contrastive guidance scale (--profile-mix)")
    ap.add_argument("--exit-layer", type=int, default=1,
                    help="early-exit draft depth for speculative requests")
    ap.add_argument("--n-draft", type=int, default=4,
                    help="draft tokens per speculative window")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request radix prefix cache over KV blocks "
                         "(requires --chunked): cached full prompt blocks "
                         "are adopted refcount-shared at admission and "
                         "only the uncached suffix is prefilled — tokens "
                         "stay bit-identical to cold serving")
    ap.add_argument("--shared-prefix", type=int, default=None, metavar="N",
                    help="shared-system-prompt trace: N distinct prefixes "
                         "reused Zipf(--zipf-a) across requests with "
                         "bursty Poisson arrivals (default: i.i.d. "
                         "paper-profile prompts)")
    ap.add_argument("--prefix-len", type=int, default=64,
                    help="system-prompt tokens per shared prefix "
                         "(--shared-prefix)")
    ap.add_argument("--zipf-a", type=float, default=1.1,
                    help="Zipf exponent for prefix reuse: prefix rank r "
                         "drawn with p ~ r**-a (--shared-prefix)")
    ap.add_argument("--burst-size", type=int, default=4,
                    help="max requests per arrival burst (--shared-prefix)")
    ap.add_argument("--boost-after", type=float, default=None,
                    help="SLA aging: +1 request priority per this many "
                         "seconds spent waiting unadmitted (default: off)")
    ap.add_argument("--replicas", type=int, default=None,
                    help="data-parallel replica pools behind one shared "
                         "queue (core/router.py); each replica gets its "
                         "own --batch-slots-sized pool + KV cache, pinned "
                         "to its own device when the host has several "
                         "(default: plain single scheduler, no router)")
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel degree: shard each pool's "
                         "executables + KV cache over an N-device "
                         "('model',) mesh (distributed/tp_pool.py); "
                         "composes with --replicas (DP x TP, disjoint "
                         "submeshes). Tokens are identical to "
                         "single-device serving")
    ap.add_argument("--mix-classes", nargs="?", metavar="CLASSES",
                    const="greedy,sampling,beam,cfg,speculative",
                    default=None,
                    help="heterogeneous trace: every request draws a "
                         "random SLA class from this comma list (greedy | "
                         "sampling | beam | cfg | speculative) with bursty "
                         "arrivals, and the per-class p50/p99 TTFT/TPOT "
                         "breakdown is printed (default classes: all five)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrivals per second; 0 = all at t=0")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature; 0 = greedy")
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile", default="llama_humaneval",
                    choices=sorted(data_mod.PAPER_PROFILES))
    args = ap.parse_args(argv)
    if args.chunked and not args.paged:
        ap.error("--chunked requires --paged (chunks append into KV blocks)")
    if args.prefix_cache and not args.chunked:
        ap.error("--prefix-cache requires --chunked (the cursor must be "
                 "able to start at the first uncached prompt token)")
    if args.mix_classes and (args.profile_mix or args.shared_prefix):
        ap.error("--mix-classes is its own trace generator; drop "
                 "--profile-mix / --shared-prefix")

    dev = jax.devices()[0]
    print(f"[serve] platform={dev.platform} device_kind={dev.device_kind} "
          f"device_count={len(jax.devices())}")
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    prof = data_mod.PAPER_PROFILES[args.profile]
    mix_class_kinds = [
        k.strip() for k in (args.mix_classes or "").split(",") if k.strip()
    ]
    if args.mix_classes:
        ins, _ = data_mod.sample_lengths(
            prof, args.n_requests, seed=args.seed + 1
        )
        pad_to = int(min(max(ins), 256))
        reqs = mix_class_trace(
            prof, args.n_requests, pad_to=pad_to, max_new_cap=args.max_new,
            vocab_size=cfg.vocab_size, arrival_rate=args.arrival_rate,
            classes=mix_class_kinds, burst_size=args.burst_size,
            seed=args.seed,
            temperature=args.temperature if args.temperature > 0 else 0.8,
            top_p=args.top_p if args.top_p < 1.0 else 0.9,
            n_beams=args.n_beams,
            beam_eos_id=args.eos_id if args.eos_id is not None else 2,
            guidance=args.guidance, exit_layer=args.exit_layer,
            n_draft=args.n_draft,
        )
    elif args.shared_prefix is not None:
        pad_to = int(min(args.prefix_len * 2, 256))
        reqs = shared_prefix_trace(
            args.n_requests, n_prefixes=args.shared_prefix,
            prefix_len=args.prefix_len, pad_to=pad_to,
            max_new_cap=args.max_new, vocab_size=cfg.vocab_size,
            arrival_rate=args.arrival_rate, zipf_a=args.zipf_a,
            burst_size=args.burst_size, seed=args.seed,
            temperature=args.temperature, top_p=args.top_p,
        )
    else:
        ins, _ = data_mod.sample_lengths(
            prof, args.n_requests, seed=args.seed + 1
        )
        pad_to = int(min(max(ins), 256))
        reqs = poisson_trace(
            prof, args.n_requests, pad_to=pad_to, max_new_cap=args.max_new,
            vocab_size=cfg.vocab_size, arrival_rate=args.arrival_rate,
            seed=args.seed, temperature=args.temperature, top_p=args.top_p,
        )
    if args.profile_mix:
        mask_offset = None
        if getattr(cfg, "vlm", None) is not None:
            from repro.models import vlm

            mask_offset = vlm.image_token_offset(cfg)
        apply_profile_mix(
            reqs, args.profile_mix, n_beams=args.n_beams,
            beam_eos_id=args.eos_id if args.eos_id is not None else 2,
            guidance=args.guidance, mask_offset=mask_offset,
            exit_layer=args.exit_layer, n_draft=args.n_draft,
        )
    mix_kinds = [k.strip() for k in (args.profile_mix or "").split(",")]
    has_groups = bool(args.profile_mix) or bool(
        {"beam", "cfg", "contrastive"} & set(mix_class_kinds)
    )
    t_warm = time.perf_counter()
    warmup(model, params, slots=args.batch_slots, pad_to=pad_to,
           max_new_cap=args.max_new, paged=args.paged,
           block_size=args.block_size, num_blocks=args.num_blocks,
           chunked=args.chunked, prefill_budget=args.prefill_budget,
           profile_mix=has_groups, n_beams=args.n_beams,
           speculative=("speculative" in mix_kinds
                        or "speculative" in mix_class_kinds),
           exit_layer=args.exit_layer, n_draft=args.n_draft, tp=args.tp,
           replicas=args.replicas)
    warmup_s = time.perf_counter() - t_warm
    m, done = run_scheduler(
        model, params, reqs, slots=args.batch_slots, pad_to=pad_to,
        max_new_cap=args.max_new, eos_id=args.eos_id, policy=args.policy,
        paged=args.paged, block_size=args.block_size,
        num_blocks=args.num_blocks, chunked=args.chunked,
        prefill_budget=args.prefill_budget,
        prefix_cache=args.prefix_cache,
        priority_boost_after=args.boost_after, seed=args.seed,
        replicas=args.replicas, tp=args.tp, return_requests=True,
    )
    # compiling every serving executable is warm-up's cost; the timed run
    # above then replays compiled programs only
    m["warmup_s"] = warmup_s
    mode = args.policy + ("/paged" if args.paged else "") + (
        "/chunked" if args.chunked else "") + (
        "/pfx" if args.prefix_cache else "") + (
        "/mix" if args.profile_mix else "") + (
        "/classes" if args.mix_classes else "") + (
        f"/x{args.replicas}" if args.replicas is not None else "") + (
        f"/tp{args.tp}" if args.tp is not None else "")
    print(f"[serve/{mode}] {m['n_requests']} requests in "
          f"{m['wall_s']:.2f}s | {m['tokens_per_s']:.1f} tok/s | "
          f"occupancy={m['mean_slot_occupancy']:.2f} | "
          f"ttft p50={m['ttft_p50_ms']:.0f}ms p99={m['ttft_p99_ms']:.0f}ms | "
          f"tpot p50={m['tpot_p50_ms']:.1f}ms | "
          f"e2e p50={m['e2e_p50_s']:.2f}s p99={m['e2e_p99_s']:.2f}s | "
          f"stall p50={m['admission_stall_p50_ms']:.1f}ms "
          f"max={m['admission_stall_max_ms']:.1f}ms | "
          f"kv reserved={m['kv_reserved_bytes'] / 1e6:.1f}MB")
    if args.paged:
        print(f"[serve/{mode}] block occupancy="
              f"{m['mean_block_occupancy']:.2f} | "
              f"preemptions={m['n_preemptions']} | "
              f"kv used peak={m['kv_used_peak_bytes'] / 1e6:.1f}MB")
    if args.chunked:
        print(f"[serve/{mode}] mixed steps={m['mixed_steps']} | "
              f"chunks={m['prefill_chunks']} "
              f"({m['prefill_chunk_tokens']} tokens) | "
              f"full prefills={m['full_prefills']}")
    if args.prefix_cache:
        print(f"[serve/{mode}] prefix hits={m['prefix_hits']}/"
              f"{m['prefix_lookups']} (rate={m['prefix_hit_rate']:.2f}) | "
              f"prefill tokens skipped={m['prefill_tokens_skipped']} | "
              f"cached blocks mean={m['mean_cached_blocks']:.1f} | "
              f"reclaimed={m['prefix_blocks_reclaimed']}")
    if args.boost_after is not None:
        print(f"[serve/{mode}] priority boosts={m['priority_boosts']}")
    if args.profile_mix and "group_admissions" in m:
        print(f"[serve/{mode}] slot groups={m['group_admissions']} | "
              f"cache reorders={m['cache_reorders']} | "
              f"block permutes={m['block_permutes']}"
              + (f" | cow copies={m['cow_copies']}" if "cow_copies" in m
                 else ""))
    if "spec_steps" in m:
        print(f"[serve/{mode}] spec steps={m['spec_steps']} | "
              f"acceptance={m['spec_acceptance']:.2f} | "
              f"tokens/step={m['spec_tokens_per_step']:.2f} | "
              f"commit hist={m['spec_commit_hist']}")
    if args.mix_classes:
        for cls, row in m["per_class"].items():
            print(f"[serve/{mode}]   class {cls}: "
                  f"{row['n_requests']} reqs | "
                  f"ttft p50={row['ttft_p50_ms']:.0f}ms "
                  f"p99={row['ttft_p99_ms']:.0f}ms | "
                  f"tpot p50={row['tpot_p50_ms']:.1f}ms "
                  f"p99={row['tpot_p99_ms']:.1f}ms")
    if "tp" in m:
        print(f"[serve/{mode}] tp={m['tp']} | kv reserved/device="
              f"{m['kv_reserved_per_device_bytes'] / 1e6:.1f}MB "
              f"(pool {m['kv_reserved_bytes'] / 1e6:.1f}MB logical)")
    if args.replicas is not None:
        print(f"[serve/{mode}] spills={m['spills']} | "
              f"requeues={m['requeues']} | "
              f"aggregate={m['aggregate_tokens_per_s']:.1f} tok/s over "
              f"busy max={m['busy_max_s']:.2f}s (fleet service rate; "
              f"wall tok/s above is the single-host time-share)")
        for e in m["per_replica"]:
            print(f"[serve/{mode}]   replica {e['replica']}: "
                  f"{e['n_requests']} reqs | steps={e['decode_steps']} | "
                  f"preempt={e['preemptions']} | busy={e['busy_s']:.2f}s | "
                  f"occ={e['mean_slot_occupancy']:.2f} | "
                  f"ttft p50={e['ttft_p50_ms']:.0f}ms | "
                  f"tpot p50={e['tpot_p50_ms']:.1f}ms")
    if return_requests:
        return m, done
    return m


if __name__ == "__main__":
    main()
