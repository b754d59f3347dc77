"""Continuous-batching scheduler: slot recycling over the KV slot-pool.

The paper's Obs #2 pathology is decode-side idle time: auto-regressive
steps are tiny, so any dead slot in the batch is pure waste. The seed's
fixed-slot server ran every batch to completion — a slot that hit EOS (or
a queue shorter than the pool) kept burning decode steps as padding. This
module is the "system software" fix the paper's 3.88× baseline credits
(Orca/vLLM-style continuous batching) expressed in the repo's §4.1.2
static-shape discipline:

- ONE compiled single-slot prefill executable (``engine.prefill`` with
  batch=1) admits a request into a free slot via the slot-pool's donated
  row scatter;
- ONE compiled decode-step executable (``engine.decode_step`` over the
  whole pool) is replayed forever;
- on every decode step, finished slots (per-slot EOS / max-new, tracked in
  ``SlotState``) are evicted immediately and refilled from the waiting
  queue, so the decode batch is always as full as the queue allows.

``policy="fixed"`` degrades the same machinery to the paper's baseline:
admission only happens when the pool is completely drained (run-to-
completion batches), which is the A/B lever ``benchmarks/bench_serve.py``
measures. Both policies share every compiled program, so the comparison
isolates scheduling.

``paged=True`` swaps the contiguous SlotPool for the BlockPool (paged KV,
vLLM-style): admission is additionally gated on free *blocks* (the
prompt's blocks plus a one-block watermark), each decode step first grows
every active slot on demand (its next token's block must exist before the
pool-wide write), and when the pool runs out of blocks the youngest
request is preempted — evicted, its blocks freed, and requeued at the
queue's front for full recompute. Greedy decoding and the per-(rid, step)
fold_in sampling keys make recompute replay token-identical, so paging
and preemption are pure memory-systems changes, never numerics changes.

``chunked=True`` (requires ``paged=True``) removes the remaining admission
stall: instead of running a whole ``pad_to``-token prefill program between
decode steps, admission just enqueues a chunk cursor (core/prefill.py) and
every step becomes ``engine.mixed_step`` — decode tokens for all live
slots PLUS up to ``prefill_budget`` prompt-chunk tokens written straight
into the admitted slot's KV blocks. Resident requests never wait on a
full prefill (the decode-stall-per-admission metric in launch/serve.py);
the admitted request trades a slightly longer TTFT for it. Steps with no
pending chunks fall back to the plain ``decode_step`` executable. A
half-prefilled request can be preempted like any other resident: its
blocks are freed, its cursor dropped, and re-admission replays the prompt
from chunk zero — token-identical under greedy / per-(rid, step) keys.

Admission and preemption honor ``ServeRequest.priority`` (default 0,
higher = more urgent): the admission loop picks the highest-priority
arrived request (stable FIFO within a class), and the preemption victim
is always the youngest request of the LOWEST resident priority.
``priority_boost_after=T`` adds the SLA aging seam: every waiting
request's priority is bumped by one per full T seconds waited
(``age_waiting``), so low-priority traffic cannot starve behind a
steady high-priority stream — the boost is remembered on the request
(``n_boosts``) and survives preemption replay and router requeues.

``prefix_cache=True`` (requires ``chunked=True``) turns the pool's
refcounted block sharing into a cross-request radix prefix cache
(core/prefix_cache.py): chunked admission first walks the trie over the
prompt's full-block spans, adopts every matched block into the slot's
table (refcounted, zero device work), and starts the chunk cursor at
the first uncached token — shared-prompt traffic skips most of its
prefill compute. A finishing (or preempted / evicted) request's full
prompt blocks are handed over to the trie instead of freed; cached
blocks nobody references are reclaimed LRU-first by the out-of-blocks
back-pressure path BEFORE preemption. Hits are bit-identical to cold
prefill at any temperature: matched K/V was produced by the same
compiled executables over the same token prefix, at least one suffix
token is always re-computed (so the first sampled token's logits come
off the same mixed-step executable), and sampling keys are pure
per-(rid, stream, token-index).

``ServeRequest.profile`` (core/profiles.py) generalizes WHAT a request
decodes: a multi-stream ``DecodingProfile`` (beam, contrastive) is
admitted as a *slot group* of ``profile.n_streams`` slots — acquired,
evicted, and preempted all-or-nothing, with the group treated as ONE
unit by priority ordering and victim selection. Group streams ride the
same pool-wide decode executable as everyone else. A group's decoding
state lives in a lane of its profile's *lane bank* (``LaneBank``; one per
distinct profile value, ``slots // n_streams`` lanes): after the decode
step ONE jitted program per bank (``profiles.lane_step``) gathers every
lane's logits rows and runs the profile's step on all of them, picking
each stream's next token plus an optional intra-group cache permutation
(beam's Obs #4 KV reorder); its outputs join the step's sampled tokens
in the step's one ``device_get``. A group's first step, on its prefill
logits, runs at admission, and its state then moves into a free lane.
Under
``paged=True`` that permutation is a pure host-side block-table rewrite
with copy-on-write sharing of common-prefix blocks (``BlockPool.share``
/ ``permute_group`` / ``ensure_writable``) — no device KV gather ever
runs; the contiguous pool falls back to ``kv_cache.reorder_donated``.
Per-request ``extra_inputs`` (encoder frames) ride the admission prefill
into per-slot cross-attention cache rows, so enc-dec beam requests serve
through the (contiguous) pool too. Preempting a group frees every slot
and block it holds and replays it from scratch — token-identical, since
profiles re-``init`` pure state and keys derive from (rid, stream, step).

``SpeculativeProfile`` requests (LayerSkip self-speculative decoding,
paper §4.3) generalize the pool step from one token per slot to a
VARIABLE number: when a resident speculative slot still has >= 2 tokens
of budget, the step becomes a draft+verify pair — greedy-draft up to
``n_draft`` tokens per slot with the first ``exit_layer`` layers
(``layerskip.draft_window``, writing straight into the pool cache), then
verify the whole window with ONE full-model multi-token forward
(``engine.verify_step``). Each lane's full-model logits are sampled
under the per-(rid, stream, token-index) key that lane's token would use
under plain decoding, so the committed stream is bit-identical to the
non-speculative engine at ANY temperature; the accepted prefix plus the
full model's correction token commit in one stride, and the rejected
suffix is rolled back host-side (a ``lengths`` rewind; paged adds a
block-table truncation — never a device gather or copy). Plain-sampling
slots ride the same step with width-1 windows; resident groups force
plain stepping (beam reorders and variable strides don't compose yet).

**Replica routing** (core/router.py) scales this pool horizontally — the
paper's fleet-scaling argument that serving "billions of users" is won at
the serving layer, not inside one device's kernels. A ``ReplicaRouter``
owns N independent schedulers behind one shared queue; this class exposes
the hooks it drives: ``normalize``/``admissible``/``try_admit`` (placement
with back-pressure), ``drain_waiting`` (replica-level preemption bounces
requests back to the SHARED queue front), ``drain_finished``, and the
two-phase ``step_begin``/``step_finish`` split (dispatch every replica's
device work before syncing any — JAX async dispatch overlaps replicas on
multi-device hosts). ``busy_s`` is the host wall time of this replica's
admissions and steps (read from its spans, below), the denominator the
router's aggregate-throughput accounting uses. Because every committed
token is sampled under pure per-(rid, stream, token-index) keys folded
from a SHARED ``base_key``, a request's output is bit-identical no matter
which replica serves it, how often it is preempted, or who its batch
mates are — replica placement is a pure scheduling decision, never a
numerics decision.

**Host spans** (core/tracing.py): every phase of the host step runs
inside a named span (``self.spans``) that lands in a profiler trace as a
``TraceAnnotation`` and in per-name totals (``spans.totals()``).
``sched.step`` (an XProf step) holds ``sched.begin`` (children
``sched.blocks``: block growth and the block-table upload, holding
``sched.plan`` in a mixed step: the chunk packer and the blocks its
chunks need; ``sched.launch``: the jitted step call) and ``sched.finish`` (children
``sched.sync``: the step's one device_get; ``sched.commit``; and, with
resident groups, ``sched.groups`` twice: the lane banks' dispatch, meta
``lanes``, before the sync, and the groups' commit after it, holding
``sched.groups.sync`` when a group finishes). ``sched.admit`` wraps an
admission, with ``sched.prefill`` around its prefill dispatch and
``sched.admit.sync`` around its first token's transfer. Every
host<->device round trip is a span named ``*.sync``.
"""
from __future__ import annotations

import time
import types
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hotpath import hot_path
from repro.core import engine, kv_cache, layerskip, profiles, sampling
from repro.core.prefill import ChunkCursor, ChunkedPrefill
from repro.core.prefix_cache import PrefixCache
from repro.core.slot_pool import BlockPool, SlotPool
from repro.core.tracing import Spans
from repro.models import attention
from repro.models.registry import Model


@dataclass
class ServeRequest:
    """One generation request plus its measured lifecycle timestamps
    (all relative to the scheduler run's t0; ``t_arrival`` is when the
    request becomes visible to the admission loop)."""

    rid: int
    prompt: np.ndarray  # [<= pad_to] int token ids
    max_new: int
    t_arrival: float = 0.0
    temperature: float = 0.0  # 0 => greedy
    top_p: float = 1.0
    # per-request EOS override; None = the scheduler-level eos_id (set
    # automatically from a single-stream SamplingProfile's eos_id)
    eos_id: Optional[int] = None
    priority: int = 0  # higher = more urgent (admission + preemption)
    # SLA aging bookkeeping (``age_waiting``): boosts already folded into
    # ``priority``, so requeues/replays never re-grant a boost
    n_boosts: int = 0
    # HOW to decode: None = plain per-slot sampling (temperature/top_p
    # above); a multi-stream DecodingProfile (beam/contrastive) makes this
    # request a slot GROUP of profile.n_streams slots
    profile: Optional[profiles.DecodingProfile] = None
    # per-request prefill inputs beyond the prompt (e.g. encoder frames
    # [1, F, d] + frame_lengths [1]) — written into the slot's own
    # cross-attention cache rows at admission
    extra_inputs: Optional[Dict[str, np.ndarray]] = None
    # ---- filled in by the scheduler ----
    tokens: List[int] = field(default_factory=list)
    score: Optional[float] = None  # beam: best hypothesis' normalized score
    t_admit: Optional[float] = None
    t_first: Optional[float] = None  # first token (TTFT reference)
    t_done: Optional[float] = None
    # per-token commit timestamps (t_first repeated as element 0) — the
    # inter-token gaps feed the decode-stall-per-admission metric
    t_tokens: List[float] = field(default_factory=list)

    @property
    def ttft(self) -> float:
        return self.t_first - self.t_arrival

    @property
    def tpot(self) -> float:
        """Mean time per output token after the first."""
        n = max(len(self.tokens) - 1, 1)
        return (self.t_done - self.t_first) / n

    @property
    def e2e(self) -> float:
        return self.t_done - self.t_arrival

    def padded_output(self, eos_id: Optional[int]) -> np.ndarray:
        """[max_new] output, EOS-padded — engine.generate's contract."""
        pad = eos_id if eos_id is not None else 0
        out = np.full((self.max_new,), pad, np.int32)
        out[: len(self.tokens)] = self.tokens
        return out


def age_waiting(waiting, now: float, boost_after: Optional[float]) -> int:
    """Max-waiting-time SLA boost, shared by ``Scheduler._admit`` and the
    router's placement loop: bump an ARRIVED request's priority by one for
    every full ``boost_after`` seconds it has waited, so low-priority
    traffic ages its way past a steady high-priority stream instead of
    starving. Monotonic and replay-safe: boosts already granted are
    remembered on the request (``n_boosts``) and never re-granted after a
    preemption requeue or router spill. Returns boosts granted."""
    if boost_after is None or boost_after <= 0:
        return 0
    granted = 0
    for r in waiting:
        if r.t_arrival > now:
            break  # arrivals are a sorted queue prefix (see _next_candidate)
        due = int((now - r.t_arrival) // boost_after)
        if due > r.n_boosts:
            r.priority += due - r.n_boosts
            granted += due - r.n_boosts
            r.n_boosts = due
    return granted


@dataclass
class SlotState:
    """Host-side view of one occupied pool slot.

    ``kv_len`` mirrors the slot's device-side token counter (prompt tokens
    at admission, +1 per decode step) — it is the logical position the
    NEXT decode write lands in, which is what paged growth must cover.
    ``admit_seq`` orders slots oldest-first for block contention (the
    preemption victim is always the youngest)."""

    req: ServeRequest
    slot: int
    n_generated: int = 0
    kv_len: int = 0
    admit_seq: int = 0

    def finished(self, token: int, eos_id: Optional[int]) -> bool:
        return (eos_id is not None and token == eos_id) or (
            self.n_generated >= self.req.max_new
        )


@dataclass
class GroupState:
    """Host-side view of one occupied slot GROUP (a multi-stream decoding
    profile): stream ``i`` lives in pool slot ``slots[i]``. Streams advance
    in lockstep, so one ``kv_len`` covers the whole group. Its decoding
    state lives in lane ``lane`` of ``bank`` (the profile's lane bank;
    ``pstate`` reads it, G = 1) and is re-initialized on preemption
    replay. ``admit_seq`` orders the group as ONE unit against other
    residents for block growth and victim selection."""

    req: ServeRequest
    slots: List[int]
    profile: profiles.DecodingProfile
    bank: "LaneBank"
    lane: int = -1  # -1 until the first step's state is in the bank
    n_generated: int = 0
    kv_len: int = 0
    admit_seq: int = 0

    @property
    def pstate(self) -> Any:
        """The group's profile state (G = 1): its lane of the bank."""
        return jax.tree.map(lambda x: x[self.lane], self.bank.state)


class LaneBank:
    """The stacked decoding state of every resident group of one profile
    value, advanced by ONE jitted program a step (``profiles.lane_step``).
    ``n_lanes = slots // n_streams`` covers every group the pool can hold;
    a group takes a free lane at admission and returns it at finish or
    preemption. ``lanes`` is the host mirror the program reads, one
    int32 row per lane: the group's slots, its rid, its token index and
    a live flag (dead lanes are stepped but keep their state)."""

    def __init__(self, profile: profiles.DecodingProfile, n_lanes: int,
                 max_new: int, device=None):
        self.profile = profile
        # n_lanes copies of a fresh G = 1 state on a leading lane axis
        self.state = jax.tree.map(
            lambda x: jnp.repeat(x[None], n_lanes, axis=0),
            profile.init(1, max_new),
        )
        if device is not None:
            self.state = jax.device_put(self.state, device)
        self.lanes = np.zeros((n_lanes, profile.n_streams + 3), np.int32)
        self._free = list(range(n_lanes - 1, -1, -1))  # lowest lane first
        self.n_live = 0

    def admit(self, g: GroupState, lane_state) -> None:
        """Give ``g`` a free lane holding ``lane_state`` (its state after
        the first step)."""
        g.lane = self._free.pop()
        self.state = profiles.put_lane(self.state, g.lane, lane_state)
        self.lanes[g.lane] = [*g.slots, g.req.rid, g.n_generated, 1]
        self.n_live += 1

    def release(self, g: GroupState) -> None:
        self.lanes[g.lane] = 0
        self._free.append(g.lane)
        g.lane = -1
        self.n_live -= 1

    def launch(self, logits, base_key):
        """Dispatch this step's program over every lane: (feed, perm,
        done) device arrays, one row per lane."""
        self.state, *out = profiles.lane_step(
            self.profile, self.state, logits, jnp.asarray(self.lanes),
            base_key,
        )
        return out


class Scheduler:
    """Admission + decode-step loop over a ``SlotPool``.

    The per-slot decoding state (last token, RNG stream index, sampler
    params) lives in host numpy mirrors and is shipped to the device as
    ONE small transfer per step — the compiled executables themselves
    never change shape.
    """

    def __init__(
        self,
        model: Model,
        params,
        *,
        slots: int,
        pad_to: int,
        max_new_cap: int,
        eos_id: Optional[int] = None,
        policy: str = "continuous",
        paged: bool = False,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        chunked: bool = False,
        prefill_budget: Optional[int] = None,
        prefix_cache: bool = False,
        priority_boost_after: Optional[float] = None,
        base_key: Optional[jax.Array] = None,
        clock=time.perf_counter,
        replica_id: int = 0,
        device=None,
        tp_mesh=None,
    ):
        if policy not in ("continuous", "fixed"):
            raise ValueError(f"unknown policy {policy!r}")
        if tp_mesh is not None and device is not None:
            raise ValueError(
                "tp_mesh and device are mutually exclusive: a TP pool's "
                "placement is the mesh itself"
            )
        if chunked and not paged:
            raise ValueError("chunked prefill requires the paged block-pool")
        if chunked and policy != "continuous":
            raise ValueError("chunked prefill requires policy='continuous'")
        if prefix_cache and not chunked:
            # the chunk path is the only position-correct vehicle for a
            # partial prompt: dense ``engine.prefill`` always computes
            # from position 0, while a ChunkCursor starts anywhere
            raise ValueError("prefix_cache requires chunked prefill")
        self.model = model
        self.params = params
        self.slots = slots
        self.pad_to = pad_to
        self.max_new_cap = max_new_cap
        self.max_len = pad_to + max_new_cap + 1
        self.eos_id = eos_id
        self.policy = policy
        self.paged = paged
        self.base_key = base_key if base_key is not None else jax.random.PRNGKey(0)
        self.clock = clock
        # replica identity (core/router.py): which data-parallel pool this
        # is, and the device its cache/keys are pinned to (None = default
        # placement). The ROUTER's base_key must be shared across replicas
        # so per-(rid, stream, token-index) sampling keys — and therefore
        # tokens — are replica-placement-independent.
        self.replica_id = replica_id
        self.device = device

        if paged:
            self.pool = BlockPool(
                model, slots, self.max_len,
                block_size=block_size, num_blocks=num_blocks,
            )
        else:
            self.pool = SlotPool(model, slots, self.max_len)
        if device is not None:
            # pin this replica's KV pool + sampling key source to its own
            # device; params are placed by the router (sharding.place_replica)
            self.pool.cache = jax.device_put(self.pool.cache, device)
            self.base_key = jax.device_put(self.base_key, device)
        # the ONE executable-dispatch seam: every prefill / decode /
        # mixed / verify / draft call below goes through ``self._steps``.
        # With a mesh, the TP context commits params + pool cache to their
        # per-device shards and binds the sharded step family; block
        # tables, slot bookkeeping and preemption replay stay pure host
        # state either way.
        self.tp_mesh = tp_mesh
        if tp_mesh is not None:
            from repro.distributed import tp_pool  # serving stays mesh-free

            self._tp = tp_pool.TPContext(
                model, params, tp_mesh,
                cache_like=self.pool.cache, max_len=self.max_len,
            )
            self.params = self._tp.params
            self.pool.cache = self._tp.place_cache(self.pool.cache)
            self._steps = self._tp.executables()
        else:
            self._tp = None
            self._steps = types.SimpleNamespace(
                prefill=engine.prefill,
                decode_step=engine.decode_step,
                mixed_step=engine.mixed_step,
                verify_step=engine.verify_step,
                draft_window=layerskip.draft_window,
            )
        self.chunked = chunked
        self.chunk_mgr: Optional[ChunkedPrefill] = None
        if chunked:
            budget = prefill_budget if prefill_budget is not None else block_size
            self.chunk_mgr = ChunkedPrefill(slots, budget, pad_to)
        # cross-request prefix cache (host-only trie over the pool's
        # blocks; allocates ZERO device memory — reuse, not growth)
        self._pcache: Optional[PrefixCache] = (
            PrefixCache(self.pool.block_size) if prefix_cache else None
        )
        self.priority_boost_after = priority_boost_after
        self.active: Dict[int, SlotState] = {}
        # slot groups (multi-stream profiles), keyed by their first slot,
        # and their states: one lane bank per distinct profile value
        self.groups: Dict[int, GroupState] = {}
        self._banks: Dict[profiles.DecodingProfile, LaneBank] = {}
        self.waiting: Deque[ServeRequest] = deque()
        self.finished: List[ServeRequest] = []
        # host mirrors of per-slot decode state (free slots: greedy + rid 0;
        # their sampled tokens are discarded; group slots also decode greedy
        # garbage here — their REAL tokens come from the profile's step)
        self._token = np.zeros((slots,), np.int32)
        self._rid = np.zeros((slots,), np.int32)
        self._stream = np.zeros((slots,), np.int32)  # stream idx in a group
        self._ngen = np.zeros((slots,), np.int32)
        self._temp = np.zeros((slots,), np.float32)
        self._top_p = np.ones((slots,), np.float32)
        # metrics
        self.n_decode_steps = 0
        self.n_prefills = 0
        self.n_preemptions = 0
        self.n_mixed_steps = 0  # steps that carried at least one chunk
        self.n_chunks = 0
        self.n_chunk_tokens = 0
        self.n_group_admissions = 0
        self.n_group_batches = 0  # lane-bank programs dispatched
        self.group_lane_trace: List[int] = []  # live lanes, per program
        # per mixed step: (valid lanes = the step's real tokens, lanes the
        # packed step computes = slots + prefill budget)
        self.mixed_lane_trace: List[Tuple[int, int]] = []
        # per paged decode or mixed step: (tiles the paged decode walk
        # runs, tiles of the whole block table)
        self.walk_trace: List[Tuple[int, int]] = []
        # speculative decoding accounting: a "spec slot-step" is one slot
        # carrying a >= 2-wide draft/verify window through one pool step;
        # the histogram keys on tokens committed by that slot that step
        # (1 = all drafts rejected, n_draft + 1 = whole window accepted)
        self.n_spec_steps = 0  # pool steps that ran the draft+verify pair
        self.n_spec_slot_steps = 0
        self.n_spec_drafted = 0  # draft tokens proposed (window width - 1)
        self.n_spec_accepted = 0  # draft tokens the full model kept
        self.n_spec_committed = 0  # tokens committed by spec slot-steps
        self.spec_commit_hist: Dict[int, int] = {}
        # cache-permutation accounting: device gathers (contiguous beam
        # fallback) vs pure host-side block-table permutations (paged beam)
        self.n_cache_reorders = 0
        self.n_block_permutes = 0
        # cross-request prefix cache accounting: a "lookup" is one
        # eligible chunked admission's trie walk; skipped tokens are
        # prompt positions admission adopted instead of prefilling
        self.n_prefix_lookups = 0
        self.n_prefix_hits = 0
        self.n_prefix_tokens_skipped = 0
        self.cached_block_trace: List[int] = []  # per step, like occupancy
        self.n_priority_boosts = 0  # SLA aging grants (age_waiting)
        # decode-stall-per-admission, measured DIRECTLY: when a request is
        # admitted while residents are decoding, the stall is the interval
        # from the previous step's commit to the next step's commit — the
        # inter-token gap the admission work sat inside. Immune to
        # preemption resetting per-request timestamp lists, and recompute
        # prefills after preemption count as the re-admissions they are.
        self.admission_stalls: List[float] = []
        self._last_commit_t: Optional[float] = None
        self._stall_marks: List[float] = []
        self.occupancy_trace: List[float] = []
        self.block_occupancy_trace: List[float] = []
        self.peak_used_blocks = 0
        self.spans = Spans(clock)  # host spans of this replica (busy_s)
        self._seq = 0  # admission order (preemption picks the youngest)
        self._t0 = self.clock()  # run() rebases; timestamps are offsets

    def _now(self) -> float:
        return self.clock() - self._t0

    # ---- request intake --------------------------------------------------
    def normalize(self, r: ServeRequest) -> ServeRequest:
        """Submit-time validation + canonicalization of one request against
        THIS scheduler's geometry (the router calls it once against replica
        0 — all replicas share one geometry, so one pass covers the fleet):
        caps ``max_new``, rejects groups that could never fit, and collapses
        single-stream SamplingProfiles onto the vectorized per-slot path."""
        r.max_new = min(r.max_new, self.max_new_cap)
        s_n = profiles.n_streams_of(r.profile)
        if s_n > self.slots:
            raise ValueError(
                f"request {r.rid} needs a slot group of {s_n} streams "
                f"but the pool has only {self.slots} slots"
            )
        if self.paged and s_n * self.pool.max_blocks > self.pool.num_blocks - 1:
            # the preemption ladder's termination guarantee: the oldest
            # resident must always be able to run ALONE, worst case
            raise ValueError(
                f"request {r.rid}: a {s_n}-stream group can need up to "
                f"{s_n * self.pool.max_blocks} blocks but the pool has "
                f"{self.pool.num_blocks - 1} usable"
            )
        if s_n == 1 and isinstance(r.profile, profiles.SamplingProfile):
            # single-stream profiles collapse onto the vectorized
            # per-slot sampling path (same numerics, no group machinery)
            if r.profile.sampler is not None:
                raise ValueError(
                    "SamplingProfile.sampler callables are a batch-"
                    "engine escape hatch; the pool serves the "
                    "(temperature, top_p, eos_id) spec"
                )
            r.temperature = r.profile.temperature
            r.top_p = r.profile.top_p
            if r.profile.eos_id is not None:
                r.eos_id = r.profile.eos_id
            if isinstance(r.profile, profiles.SpeculativeProfile):
                self._check_speculative(r.rid, r.profile)
        return r

    def submit(self, requests: List[ServeRequest]) -> None:
        # arrival order first; within an arrival instant, higher priority
        # first (stable — submission order breaks remaining ties)
        for r in sorted(requests, key=lambda r: (r.t_arrival, -r.priority)):
            self.waiting.append(self.normalize(r))

    def _check_speculative(
        self, rid: int, prof: profiles.SpeculativeProfile
    ) -> None:
        """Submit-time validation of a speculative request against THIS
        scheduler's model: drafting needs rollback-able attention caches
        (the rejected window is undone by a lengths rewind / block-table
        truncation — SSM/hybrid recurrent state cannot be masked away) and
        a real early-exit point strictly inside the layer stack."""
        cfg = self.model.config
        if cfg.family not in ("dense", "moe", "mla_moe", "vlm"):
            raise ValueError(
                f"request {rid}: SpeculativeProfile needs rollback-able "
                f"attention caches; family {cfg.family!r} is unsupported "
                f"(DESIGN.md §4)"
            )
        if getattr(cfg, "scan_layers", False):
            raise ValueError(
                f"request {rid}: early-exit drafting slices the layer "
                f"stack per layer; scan_layers models are unsupported"
            )
        if not 1 <= prof.exit_layer < cfg.n_layers:
            raise ValueError(
                f"request {rid}: exit_layer must be in "
                f"[1, {cfg.n_layers - 1}], got {prof.exit_layer}"
            )
        if prof.n_draft < 1:
            raise ValueError(
                f"request {rid}: n_draft must be >= 1, got {prof.n_draft}"
            )

    # ---- admission -------------------------------------------------------
    def _trim_prompt(self, prompt: np.ndarray) -> np.ndarray:
        """The ONE trim/truncation policy shared by both admission paths
        (dense prefill and chunk cursors)."""
        return np.asarray(prompt, np.int32)[: self.pad_to]

    def _pad_prompt(self, prompt: np.ndarray):
        p = self._trim_prompt(prompt)
        buf = np.zeros((1, self.pad_to), np.int32)
        buf[0, : len(p)] = p
        return jnp.asarray(buf), jnp.asarray([len(p)], jnp.int32)

    def _eos(self, req: ServeRequest) -> Optional[int]:
        """The EOS id governing one request: its own override (a
        single-stream SamplingProfile's eos_id) or the scheduler-wide
        default."""
        return req.eos_id if req.eos_id is not None else self.eos_id

    def _mark_admission_stall(self) -> None:
        """Residents are mid-decode: whatever admission work happens now
        widens their current inter-token gap. Remember the gap's start (the
        last step's commit time); the next step's commit closes it."""
        if (self.active or self.groups) and self._last_commit_t is not None:
            self._stall_marks.append(self._last_commit_t)

    def _request_extra(self, req: ServeRequest):
        """Per-request prefill extras (encoder frames etc.) as device
        arrays; their pytree structure is part of the compiled prefill
        signature, so one executable serves every request of a family."""
        if not req.extra_inputs:
            return None
        return {k: jnp.asarray(v) for k, v in req.extra_inputs.items()}

    def _admit_one(self, req: ServeRequest, now: float) -> None:
        self._mark_admission_stall()
        slot = self.pool.acquire()
        assert slot is not None
        tokens, length = self._pad_prompt(req.prompt)
        n_prompt = int(length[0])
        with self.spans("sched.prefill"):
            logits, row = self._steps.prefill(
                self.model, self.params, tokens, length, self.max_len,
                self._request_extra(req),
            )
        if self.paged:
            # dense assign pops the free-list directly: reclaim any
            # shortfall from the prefix cache's LRU list first (the
            # admission gate counted those blocks as effectively free)
            self._reclaim_for(self.pool.blocks_for(n_prompt))
        self.pool.assign(slot, row, n_prompt)
        if self.paged:
            # claim the first decode step's block NOW (the admission gate
            # checked a watermark but assign only took the prompt's blocks;
            # without this a block-aligned prompt could be preempted on its
            # first step). May fail on an idle just-fits pool — harmless,
            # _ensure_blocks grows it at step time.
            self.pool.ensure(slot, n_prompt)
        self.n_prefills += 1
        if req.temperature <= 0.0:  # greedy: skip the top-p pipeline
            tok = sampling.greedy(logits)
        else:
            keys = sampling.slot_step_keys(
                self.base_key, jnp.asarray([req.rid]), jnp.asarray([0])
            )
            tok = sampling.sample_slots(
                logits, keys,
                jnp.asarray([req.temperature], jnp.float32),
                jnp.asarray([req.top_p], jnp.float32),
            )
        with self.spans("sched.admit.sync"):
            first = int(jax.device_get(tok)[0])
        req.t_admit, req.t_first = now, self._now()
        req.tokens.append(first)
        req.t_tokens.append(req.t_first)
        state = SlotState(
            req=req, slot=slot, n_generated=1, kv_len=n_prompt,
            admit_seq=self._seq,
        )
        self._seq += 1
        if state.finished(first, self._eos(req)):
            req.t_done = req.t_first
            self.finished.append(req)
            self.pool.evict(slot)
            return
        self.active[slot] = state
        self._token[slot] = first
        self._rid[slot] = req.rid
        self._stream[slot] = 0  # a group may have left a stale stream index
        self._ngen[slot] = 1
        self._temp[slot] = req.temperature
        self._top_p[slot] = req.top_p

    def _admit_one_chunked(self, req: ServeRequest, now: float) -> None:
        """Chunked admission: no prefill program, no dense row — acquire a
        slot, enqueue a chunk cursor, and let the mixed steps stream the
        prompt into the slot's blocks ``prefill_budget`` tokens at a time.
        With the prefix cache on, the cursor starts at the first UNCACHED
        token: every leading full block found in the trie is adopted into
        the slot's table (refcounted sharing, zero device KV work) and its
        tokens never enter a prefill chunk at all."""
        self._mark_admission_stall()
        slot = self.pool.acquire()
        assert slot is not None
        prompt = self._trim_prompt(req.prompt)
        pos = self._prefix_admit(slot, prompt) if self._pcache is not None else 0
        cursor = ChunkCursor(req=req, slot=slot, prompt=prompt,
                             admit_seq=self._seq, pos=pos)
        self._seq += 1
        self.chunk_mgr.add(cursor)
        req.t_admit = now
        # pre-stage the slot's sampling state so the step that completes
        # the prefill samples the first token with the (rid, stream 0, 0)
        # key in the same vectorized call as everyone else's decode tokens
        self._rid[slot] = req.rid
        self._stream[slot] = 0  # a group may have left a stale stream index
        self._ngen[slot] = 0
        self._temp[slot] = req.temperature
        self._top_p[slot] = req.top_p

    def _admit_one_group(self, req: ServeRequest, now: float) -> None:
        """Slot-group admission (multi-stream profile): acquire
        ``n_streams`` slots all-or-nothing, prefill the profile's stream
        prompts, and run the profile's FIRST step on the prefill logits.
        Prefix-shared profiles (beam: every stream prefills the same
        prompt) run ONE prefill; on the paged pool the other streams then
        ``share`` its blocks copy-on-write — zero extra device copies —
        while the contiguous pool re-scatters the row per stream."""
        prof = req.profile
        s_n = prof.n_streams
        self._mark_admission_stall()
        slots = [self.pool.acquire() for _ in range(s_n)]
        assert all(s is not None for s in slots)
        prompts = prof.stream_prompts(self._trim_prompt(req.prompt))
        n_lens = {len(p) for p in prompts}
        assert len(n_lens) == 1, "group streams must share one prompt length"
        n_prompt = n_lens.pop()
        if self.paged:
            # group assigns pop the free-list directly (no adopt path for
            # groups): reclaim any prefix-cache shortfall up front
            self._reclaim_for(
                self.pool.blocks_for(n_prompt)
                * (1 if prof.prefix_shared else s_n)
            )
        extra = self._request_extra(req)
        if prof.prefix_shared:
            tokens, length = self._pad_prompt(prompts[0])
            with self.spans("sched.prefill"):
                logits, row = self._steps.prefill(
                    self.model, self.params, tokens, length, self.max_len,
                    extra,
                )
            self.n_prefills += 1
            self.pool.assign(slots[0], row, n_prompt)
            for s in slots[1:]:
                if self.paged:
                    self.pool.share(s, slots[0])
                else:
                    self.pool.assign(s, row, n_prompt)
            logit_rows = jnp.repeat(logits, s_n, axis=0)  # identical streams
        else:
            rows = []
            for s, p in zip(slots, prompts):
                tokens, length = self._pad_prompt(p)
                with self.spans("sched.prefill"):
                    logits, row = self._steps.prefill(
                        self.model, self.params, tokens, length,
                        self.max_len, extra,
                    )
                self.n_prefills += 1
                self.pool.assign(s, row, n_prompt)
                rows.append(logits)
            logit_rows = jnp.concatenate(rows, axis=0)
        req.t_admit = now
        bank = self._banks.get(prof)
        if bank is None:
            bank = self._banks[prof] = LaneBank(
                prof, self.slots // s_n, self.max_new_cap, self.device
            )
        g = GroupState(
            req=req, slots=slots, profile=prof, bank=bank, kv_len=n_prompt,
            admit_seq=self._seq,
        )
        self._seq += 1
        self.n_group_admissions += 1
        for i, s in enumerate(slots):
            self._rid[s] = req.rid
            self._stream[s] = i
            self._ngen[s] = 0
            self._temp[s] = 0.0  # group sampling lives in the profile
        if not self._advance_group(g, logit_rows, self._now()):
            self.groups[g.slots[0]] = g

    def _admissible(self, req: ServeRequest) -> bool:
        """Pool-side admission gate. Contiguous: ``n_streams`` free slots.
        Paged: the slots AND enough free blocks for the streams' prompts
        plus a one-block watermark (optimistic vLLM-style admission — later
        growth is served on demand and backed by preemption, not reserved
        up front). A prefix-shared group's streams SHARE the prompt blocks,
        so it only needs them once plus ``n_streams - 1`` copy-on-write
        spares for the write-cursor block. Chunked (single-stream only):
        blocks are claimed chunk by chunk, so admission only needs the
        FIRST chunk's block (+ watermark when the pool is busy)."""
        s_n = profiles.n_streams_of(req.profile)
        if self.pool.n_free < s_n:
            return False
        if not self.paged:
            return True
        n_prompt = max(1, min(len(req.prompt), self.pad_to))
        if s_n > 1:
            if req.profile.prefix_shared:
                need = self.pool.blocks_for(n_prompt) + (s_n - 1)
            else:
                need = self.pool.blocks_for(n_prompt) * s_n
        elif self.chunked:
            need = 1
        else:
            need = self.pool.blocks_for(n_prompt)
        # cached-only blocks (prefix cache holds them, nobody reads them)
        # are free-list overflow: admission reclaims the shortfall LRU-
        # first before any assign/ensure pops the real free-list
        free_b = self.pool.n_free_blocks
        if self._pcache is not None:
            free_b += self.pool.n_reclaimable_blocks
        if self.pool.n_active == 0:
            # idle pool: every block is free and one worst-case request is
            # guaranteed to fit — gating on the watermark here could wedge
            return free_b >= need
        return free_b >= need + 1

    def _next_candidate(self, now: float):
        """(index, request) of the highest-priority ARRIVED request; stable
        (leftmost wins ties, so preemption's requeue-front and FIFO order
        survive within a class). Arrived requests are a queue prefix —
        submit keeps arrivals sorted and preemption only prepends already-
        arrived requests — so the scan stops at the first future arrival."""
        best_i, best = None, None
        for i, r in enumerate(self.waiting):
            if r.t_arrival > now:
                break
            if best is None or r.priority > best.priority:
                best_i, best = i, r
        return best_i, best

    def _admit(self, now: float) -> None:
        if self.policy == "fixed" and (self.active or self.groups):
            return  # run-to-completion: no refill until the pool drains
        self.n_priority_boosts += age_waiting(
            self.waiting, now, self.priority_boost_after
        )
        while True:
            i, cand = self._next_candidate(now)
            if cand is None or not self._admissible(cand):
                return
            del self.waiting[i]
            self._admit_request(cand, now)

    def _admit_request(self, req: ServeRequest, now: float) -> None:
        """Admit ``req`` (already gated by ``_admissible``) down its path:
        a slot group, a chunk cursor, or a dense prefill."""
        with self.spans("sched.admit", rid=req.rid):
            if profiles.n_streams_of(req.profile) > 1:
                self._admit_one_group(req, now)
            elif self.chunked and not req.extra_inputs:
                # extra-input requests need the prefill program (the chunk
                # path streams tokens only), so they take the dense path
                self._admit_one_chunked(req, now)
            else:
                self._admit_one(req, now)

    # ---- router hooks (core/router.py) -----------------------------------
    @property
    def has_work(self) -> bool:
        """True while any resident (slot, group, or chunk cursor) needs a
        step — the router steps a replica only while this holds."""
        return bool(
            self.active or self.groups
            or (self.chunk_mgr is not None and len(self.chunk_mgr))
        )

    def free_capacity(self) -> int:
        """Load signal for placement: free blocks when paged (the binding
        resource Fig 1 identifies), free slots otherwise."""
        return self.pool.n_free_blocks if self.paged else self.pool.n_free

    def admissible(self, req: ServeRequest) -> bool:
        """Public admission gate (the router's no-stall invariant checks
        it): would ``try_admit`` succeed right now?"""
        return self._admissible(req)

    def try_admit(self, req: ServeRequest, now: float) -> bool:
        """Router placement hook: admit ``req`` (already ``normalize``d by
        the router's shared submit) if this replica has room, else refuse —
        back-pressure the router answers by spilling to the next replica.
        Admission work (a prefill program, or a chunk-cursor enqueue) is
        this replica's own work, so it lands in ``busy_s``."""
        if not self._admissible(req):
            return False
        self._admit_request(req, now)
        return True

    @property
    def busy_s(self) -> float:
        """Host wall time of this replica's admissions and steps (its
        ``sched.admit``, ``sched.begin`` and ``sched.finish`` spans): the
        denominator of the router's aggregate-throughput accounting. On a
        one-device host replicas time-share the device, so
        max-over-replicas ``busy_s`` is the wall a real fleet (one device
        per replica) would take."""
        t = self.spans.totals()
        return sum(t[n]["seconds"] for n in
                   ("sched.admit", "sched.begin", "sched.finish") if n in t)

    def drain_waiting(self) -> List[ServeRequest]:
        """Reclaim requests this replica preempted back to ITS queue front
        (front-first order preserved) so the router can requeue them at the
        SHARED queue's front — a preempted request may resume on ANY
        replica; per-(rid, stream, token-index) keys keep its tokens
        identical wherever the replay lands."""
        out = list(self.waiting)
        self.waiting.clear()
        return out

    def drain_finished(self) -> List[ServeRequest]:
        """Hand completed requests to the router (finish-order preserved)."""
        out = self.finished
        self.finished = []
        return out

    def rebase(self, t0: float) -> None:
        """Pin this replica's clock origin (the router rebases every
        replica to ONE t0 so merged TTFT/TPOT timestamps are comparable)."""
        self._t0 = t0

    # ---- cross-request prefix cache (core/prefix_cache.py) ----------------
    @hot_path
    def _prefix_admit(self, slot: int, prompt: np.ndarray) -> int:
        """Admission trie walk: adopt every cached leading full block of
        ``prompt`` into ``slot``'s block table (refcounted sharing — zero
        device KV work) and return the matched token count, i.e. where
        the chunk cursor starts. The match is capped so >= 1 suffix token
        always remains: the last prompt position's logits (the first
        sampled token's input) are recomputed by the same mixed-step
        executable cold serving uses, keeping hits bit-identical."""
        self.n_prefix_lookups += 1
        blocks = self._pcache.match(prompt)
        if not blocks:
            return 0
        matched = len(blocks) * self.pool.block_size
        self.pool.adopt(slot, blocks, matched)
        self.n_prefix_hits += 1
        self.n_prefix_tokens_skipped += matched
        return matched

    def _prefix_insert(self, slot: int, req: ServeRequest,
                       n_written: Optional[int] = None) -> None:
        """Refcount handoff at every slot-release site (finish, preempt):
        hand the request's fully written prompt blocks to the trie BEFORE
        ``pool.evict`` drops the slot's references, so they transit
        owned -> cached without visiting the free-list. ``n_written``
        caps the insertable span for half-prefilled cursors (only
        positions the chunks actually wrote). Re-inserting blocks the
        trie already holds — including a replayed request hitting blocks
        it itself inserted before preemption — is a clean no-op: the
        incumbent node wins and the duplicate (or self-same) block just
        loses this slot's reference in the eviction that follows."""
        if self._pcache is None or req.extra_inputs:
            return
        prompt = self._trim_prompt(req.prompt)
        n = len(prompt) if n_written is None else min(n_written, len(prompt))
        n_full = n // self.pool.block_size
        if n_full <= 0:
            return
        self._pcache.insert(
            prompt, self.pool.owned_blocks(slot)[:n_full], self.pool
        )

    def _reclaim_for(self, need: int) -> None:
        """Make ``need`` blocks REALLY free for a path that pops the
        free-list directly (dense/group ``assign``): reclaim the
        shortfall from the prefix cache's LRU leaves."""
        if self._pcache is not None:
            short = need - self.pool.n_free_blocks
            if short > 0:
                self._pcache.reclaim(self.pool, short)

    @hot_path
    def _ensure_or_reclaim(self, slot: int, kv_len: int,
                           writable: bool = False) -> bool:
        """``BlockPool.ensure``/``ensure_writable`` with the prefix cache
        as the FIRST line of out-of-blocks back-pressure: when the
        free-list runs dry, LRU-reclaim cached-only blocks and retry;
        only when the trie has nothing reclaimable does the caller fall
        back to preemption. Keeps the preemption ladder's termination
        argument intact — reclaim strictly grows the free-list."""
        grow = self.pool.ensure_writable if writable else self.pool.ensure
        while not grow(slot, kv_len):
            if self._pcache is None or not self._pcache.reclaim(self.pool, 1):
                return False
        return True

    # ---- paged back-pressure ---------------------------------------------
    def _victim(self):
        """Preemption victim: the YOUNGEST request of the LOWEST priority
        among all residents — decoding slots, half-prefilled chunk cursors
        (the cheapest victim: no tokens to recompute, only chunks to
        replay), and whole slot GROUPS alike (a group is one unit: its
        admit_seq/priority rank it, and preemption takes every stream)."""
        cands: list = list(self.active.values()) + list(self.groups.values())
        if self.chunk_mgr is not None:
            cands += list(self.chunk_mgr.cursors.values())
        return min(cands, key=lambda s: (s.req.priority, -s.admit_seq))

    def _preempt(self, st) -> None:
        """Out-of-blocks back-pressure: evict the slot(s), free the blocks,
        and requeue the request at the FRONT of the waiting queue for full
        recompute. Greedy decoding / per-(rid, stream, step) keys / pure
        profile ``init`` state replay the identical token stream, so
        preemption costs work, not tokens. ``st`` is a SlotState
        (decoding), a ChunkCursor (mid-prefill — the cursor is dropped and
        re-admission restarts at chunk zero), or a GroupState (every
        stream's slot is evicted and the profile state discarded)."""
        if isinstance(st, GroupState):
            del self.groups[st.slots[0]]
            st.bank.release(st)
            for s in st.slots:
                self.pool.evict(s)
                self._temp[s] = 0.0
        else:
            if isinstance(st, ChunkCursor):
                self.chunk_mgr.remove(st.slot)
                # only the chunk-written span is insertable; its full
                # blocks seed the trie so the replay (which may well be
                # the very next admission) adopts them back — the
                # refcount self-collision insert() handles
                self._prefix_insert(st.slot, st.req, n_written=st.pos)
            else:
                del self.active[st.slot]
                self._prefix_insert(st.slot, st.req)
            self.pool.evict(st.slot)
            self._temp[st.slot] = 0.0
        st.req.tokens = []
        st.req.t_tokens = []
        st.req.score = None
        self.waiting.appendleft(st.req)
        self.n_preemptions += 1

    @hot_path
    def _ensure_blocks(self, extra: Optional[np.ndarray] = None) -> None:
        """Before a paged decode step every active slot must own the block
        its next token writes into — EXCLUSIVELY, for group streams whose
        write-cursor block may be shared (copy-on-write unshare via
        ``ensure_writable``). ``extra`` [slots] widens a slot's target by
        that many positions past ``kv_len`` (a speculative step's draft +
        verify writes reach ``kv_len + w - 1``; groups never step
        speculatively). Residents grow oldest-first; when the pool runs
        dry the youngest lowest-priority resident is preempted
        (repeatedly if needed). Terminates: BlockPool guarantees one
        worst-case single request fits, and ``submit`` enforces the same
        for whole groups, so the oldest resident can always run alone."""
        ents = sorted(
            list(self.active.values()) + list(self.groups.values()),
            key=lambda st: st.admit_seq,
        )
        for ent in ents:
            if isinstance(ent, GroupState):
                if ent.slots[0] not in self.groups:
                    continue  # already preempted while growing an older one
                gone = False
                for s in ent.slots:
                    while not self._ensure_or_reclaim(s, ent.kv_len,
                                                      writable=True):
                        victim = self._victim()
                        self._preempt(victim)
                        if victim is ent:
                            gone = True
                            break
                    if gone:
                        break
            else:
                if ent.slot not in self.active:
                    continue  # already preempted while growing an older one
                tgt = ent.kv_len
                if extra is not None:
                    tgt = tgt + extra[ent.slot]
                while not self._ensure_or_reclaim(ent.slot, tgt):
                    victim = self._victim()
                    self._preempt(victim)
                    if victim is ent:
                        break  # this slot WAS the victim; it queues

    # ---- decode ----------------------------------------------------------
    @hot_path
    def _sample(self, logits):
        """Per-slot sampling, dispatched: the [slots] tokens stay on the
        device until the step's one round trip (``_sync_step``)."""
        if not self._temp.any():  # all-greedy pool: skip the top-p pipeline
            return sampling.greedy(logits)
        keys = sampling.slot_step_keys(
            self.base_key, jnp.asarray(self._rid), jnp.asarray(self._ngen),
            jnp.asarray(self._stream),
        )
        return sampling.sample_slots(
            logits, keys, jnp.asarray(self._temp), jnp.asarray(self._top_p)
        )

    @hot_path
    def _sync_step(self, logits):
        """The step's ONE host<->device round trip: the per-slot samples
        and, with resident groups, every lane bank's (feed, perm, done),
        in one ``device_get``. Returns (host tokens, host bank outputs)."""
        toks = self._sample(logits)
        outs = self._launch_groups(logits)
        with self.spans("sched.sync"):
            return jax.device_get((toks, outs))

    def _record_step_metrics(self) -> None:
        self.n_decode_steps += 1
        self.occupancy_trace.append(self.pool.occupancy)
        if self.paged:
            self.block_occupancy_trace.append(self.pool.block_occupancy)
            self.peak_used_blocks = max(
                self.peak_used_blocks, self.pool.n_used_blocks
            )
        if self._pcache is not None:
            self.cached_block_trace.append(self.pool.n_cached_blocks)

    def _harvest_stalls(self, now: float) -> None:
        """Close every admission gap opened since the last step: residents
        just got their next token, so the stall each admission imposed on
        them is this commit minus the pre-admission commit."""
        if self._stall_marks:
            self.admission_stalls.extend(now - m for m in self._stall_marks)
            self._stall_marks.clear()
        self._last_commit_t = now

    def _commit_decode(self, toks: np.ndarray, now: float) -> List[ServeRequest]:
        self._harvest_stalls(now)
        done: List[ServeRequest] = []
        for slot, st in list(self.active.items()):
            token = int(toks[slot])
            st.req.tokens.append(token)
            st.req.t_tokens.append(now)
            st.n_generated += 1
            st.kv_len += 1  # this step wrote the slot's K/V at kv_len
            self._token[slot] = token
            self._ngen[slot] = st.n_generated
            if st.finished(token, self._eos(st.req)):
                st.req.t_done = now
                self.finished.append(st.req)
                done.append(st.req)
                del self.active[slot]
                self._prefix_insert(slot, st.req)
                self.pool.evict(slot)
                self._temp[slot] = 0.0  # free slots decode greedy garbage
        return done

    # ---- speculative decoding (SpeculativeProfile windows) ----------------
    def _spec_ready(self) -> bool:
        """A draft+verify step pays off only when some resident slot can
        commit >= 2 tokens this step. Resident groups force plain
        stepping (beam's per-step KV permutation and variable-stride
        commits don't compose yet — see ROADMAP); pending chunk cursors
        already routed to the mixed step before this is consulted."""
        if self.groups:
            return False
        return any(
            isinstance(st.req.profile, profiles.SpeculativeProfile)
            and st.req.max_new - st.n_generated >= 2
            for st in self.active.values()
        )

    def _window_widths(self) -> np.ndarray:
        """Per-slot verify-window width for one speculative step. A
        speculative slot gets ``min(n_draft + 1, budget left)`` — >= 1
        while active, so a variable-stride commit can never overshoot
        ``max_new`` and the window never writes past the pool's
        ``max_len`` sizing. Plain-sampling slots ride along with width 1
        (their lane-0 sample is exactly the plain decode step's); free
        slots get 0 and are frozen through draft AND verify."""
        w = np.zeros((self.slots,), np.int32)
        for slot, st in self.active.items():
            left = st.req.max_new - st.n_generated
            if isinstance(st.req.profile, profiles.SpeculativeProfile):
                w[slot] = min(st.req.profile.n_draft + 1, left)
            else:
                w[slot] = 1
        return w

    @hot_path
    def _begin_speculative(self):
        """Dispatch phase of one draft+verify pool step (LayerSkip, paper
        §4.3): greedy-draft up to K tokens per speculative slot with the
        early-exit submodel straight into the pool cache, then verify
        every slot's window with ONE full-model multi-token forward —
        both dispatched WITHOUT a host sync. The step runs at the LARGEST
        resident (exit_layer, n_draft) geometry — ONE executable pair per
        geometry — and narrower slots are frozen via per-slot ``n_live``
        widths."""
        if self.paged:
            # draft writes reach kv_len + w - 2, verify kv_len + w - 1:
            # grow every slot's blocks for its whole window up front (may
            # preempt — widths are rebuilt below for the survivors)
            with self.spans("sched.blocks"):
                w = self._window_widths()
                self._ensure_blocks(extra=np.maximum(w - 1, 0))
                self.pool.sync()
            if not self.active:
                return None  # everything preempted back to the queue
        w = self._window_widths()
        k_step, e_step = 0, 1
        for st in self.active.values():
            prof = st.req.profile
            if isinstance(prof, profiles.SpeculativeProfile):
                k_step = max(k_step, prof.n_draft)
                e_step = max(e_step, prof.exit_layer)
        if k_step == 0:  # every speculative slot was preempted away
            return self._begin_decode()
        n_live = np.maximum(w - 1, 0)
        base = np.zeros((self.slots,), np.int32)
        for slot, st in self.active.items():
            base[slot] = st.kv_len
        with self.spans("sched.launch"):
            lengths = jnp.asarray(base)
            window, cache = self._steps.draft_window(
                self.model, e_step, k_step, self.params, self.pool.cache,
                jnp.asarray(self._token), jnp.asarray(n_live), lengths,
            )
            logits, cache = self._steps.verify_step(
                self.model, self.params, cache, window, jnp.asarray(w),
                lengths,
            )
        self.pool.cache = cache
        return ("spec", logits, window, w)

    @hot_path
    def _finish_speculative(self, logits, window, w) -> List[ServeRequest]:
        """Commit phase: sample each verify lane under the key its token
        index would use under plain decoding (the step's ONE device_get),
        commit the accepted prefix plus the full model's correction token,
        and roll back every rejected suffix host-side (``kv_cache.rewind``
        + paged block-table truncation — no device gather or copy)."""
        samples, win = self._sample_window(logits, window)
        with self.spans("sched.commit"):
            self._record_step_metrics()
            self.n_spec_steps += 1
            now = self._now()
            done = self._commit_window(samples, win, w, now)
            # host-side rollback of every rejected suffix: ONE pool-wide
            # lengths rewind (+ block-table truncation when paged), built
            # after evictions so freed slots rewind to zero
            new_len = np.zeros((self.slots,), np.int32)
            for slot, st in self.active.items():
                new_len[slot] = st.kv_len
                self.pool.truncate(slot, st.kv_len)
            self.pool.cache = kv_cache.rewind(
                self.pool.cache, jnp.asarray(new_len)
            )
        return done

    @hot_path
    def _sample_window(self, logits, window):
        """Sample every verify lane under its own (rid, stream, token
        index) key — lane ``j`` of slot ``b`` holds that request's token
        index ``n_generated + j``, the SAME key plain decoding would fold
        in for it — and ship (samples, window) to the host as the step's
        ONE device_get."""
        if not self._temp.any():  # all-greedy pool: skip the top-p pipeline
            samples = sampling.greedy(logits)
        else:
            steps = jnp.asarray(self._ngen)[:, None] + jnp.arange(
                logits.shape[1]
            )[None]
            keys = sampling.window_step_keys(
                self.base_key, jnp.asarray(self._rid), steps,
                jnp.asarray(self._stream),
            )
            samples = sampling.sample_window(
                logits, keys, jnp.asarray(self._temp), jnp.asarray(self._top_p)
            )
        with self.spans("sched.sync"):
            return jax.device_get((samples, window))

    def _commit_window(
        self, samples: np.ndarray, win: np.ndarray, w: np.ndarray, now: float
    ) -> List[ServeRequest]:
        """Variable-stride commit. Slot ``b`` commits ``samples[b, 0..m]``
        where ``m`` is the first lane whose full-model sample contradicts
        the draft (that sample IS the full model's correction token), the
        last lane, or an EOS / max_new finish — whichever comes first, so
        EOS inside an accepted window truncates exactly like
        token-at-a-time decoding. Width-1 (plain) slots reduce to
        ``_commit_decode``. ``kv_len`` grows by the commit count: the
        verify step wrote lanes ``0..m-1``'s K/V at ``kv_len..kv_len+m-1``
        (committed lanes matched the window entries whose K/V they are),
        and the correction token's K/V lands in the NEXT step's lane 0."""
        self._harvest_stalls(now)
        done: List[ServeRequest] = []
        for slot, st in list(self.active.items()):
            wi = int(w[slot])
            if wi <= 0:
                continue
            eos = self._eos(st.req)
            commits, token, fin = 0, 0, False
            for j in range(wi):
                token = int(samples[slot, j])
                st.req.tokens.append(token)
                st.req.t_tokens.append(now)
                st.n_generated += 1
                commits += 1
                fin = st.finished(token, eos)
                # stop at the first draft the full model contradicts
                if fin or j + 1 >= wi or token != int(win[slot, j + 1]):
                    break
            st.kv_len += commits
            self._token[slot] = token
            self._ngen[slot] = st.n_generated
            if wi > 1:
                self.n_spec_slot_steps += 1
                self.n_spec_drafted += wi - 1
                self.n_spec_accepted += commits - 1
                self.n_spec_committed += commits
                self.spec_commit_hist[commits] = (
                    self.spec_commit_hist.get(commits, 0) + 1
                )
            if fin:
                st.req.t_done = now
                self.finished.append(st.req)
                done.append(st.req)
                del self.active[slot]
                self._prefix_insert(slot, st.req)
                self.pool.evict(slot)
                self._temp[slot] = 0.0  # free slots decode greedy garbage
        return done

    @hot_path
    def step(self) -> List[ServeRequest]:
        """One pool-wide step; returns requests finished by it. With
        pending chunk cursors the step is the mixed-step executable; with
        a speculative resident that still has >= 2 tokens of budget (and
        no resident groups) it is the draft+verify pair; otherwise (and
        always when not chunked) the plain decode step.

        Split into ``step_begin`` (dispatch the device work, NO sync) and
        ``step_finish`` (the one device_get + host commit) so the replica
        router can dispatch EVERY replica's step before syncing any —
        JAX's async dispatch then overlaps replica compute on multi-device
        hosts. ``step()`` is the fused single-pool form."""
        with self.spans.step("sched.step", self.n_decode_steps):
            return self.step_finish(self.step_begin())

    @hot_path
    def step_begin(self):
        """Phase 1 of one pool-wide step: route to the step kind, dispatch
        its device work asynchronously, and return an opaque pending
        handle for ``step_finish`` (None = the step did all its work on
        the host — e.g. everything was preempted back to the queue)."""
        with self.spans("sched.begin"):
            if self.chunked and len(self.chunk_mgr):
                return self._begin_mixed()
            if self._spec_ready():
                return self._begin_speculative()
            return self._begin_decode()

    @hot_path
    def step_finish(self, pending) -> List[ServeRequest]:
        """Phase 2 of one pool-wide step: the step's ONE device_get plus
        all host-side commit bookkeeping. Admissions must not run between
        a replica's begin and finish — the commit walks the ``active``
        set the dispatch captured."""
        if pending is None:
            return []
        with self.spans("sched.finish"):
            kind = pending[0]
            if kind == "mixed":
                return self._finish_mixed(*pending[1:])
            if kind == "spec":
                return self._finish_speculative(*pending[1:])
            return self._finish_decode(*pending[1:])

    @hot_path
    def _begin_decode(self):
        with self.spans("sched.blocks"):
            if self.paged:
                self._ensure_blocks()
                if not self.active and not self.groups:
                    return None  # everything preempted back to the queue
                self._record_walk(self._positions())
            self.pool.sync()
        with self.spans("sched.launch"):
            logits, cache = self._steps.decode_step(
                self.model, self.params, self.pool.cache,
                jnp.asarray(self._token),
            )
        self.pool.cache = cache
        return ("decode", logits)

    @hot_path
    def _finish_decode(self, logits) -> List[ServeRequest]:
        toks, outs = self._sync_step(logits)
        with self.spans("sched.commit"):
            self._record_step_metrics()
            now = self._now()
            done = self._commit_decode(toks, now)
        done += self._commit_lanes(outs, now)
        return done

    @hot_path
    def _begin_mixed(self):
        """Dispatch phase of one token-budget mixed step: decode tokens
        for every live slot PLUS up to ``prefill_budget`` prompt-chunk
        tokens (the plan from core/prefill.py), dispatched as ONE compiled
        executable — admission rides the pool-wide step instead of
        stalling it."""
        with self.spans("sched.blocks"):
            # decode growth first (victims incl. cursors)
            self._ensure_blocks()
            # pack, then back every chunk's span with blocks; a starved
            # cursor is excluded and the plan rebuilt so its budget share
            # flows to cursors whose chunks ARE backed (no budget hoarding)
            decode_slots = list(self.active) + [
                s for g in self.groups.values() for s in g.slots
            ]
            starved: set = set()
            with self.spans("sched.plan"):
                while True:
                    plan = self.chunk_mgr.plan(self._token, decode_slots,
                                               skip=starved)
                    kept = list(plan.chunks)
                    newly = [ch.slot for ch in plan.chunks
                             if not self._ensure_or_reclaim(
                                 ch.slot, ch.start + ch.t - 1)]
                    if not newly:
                        break
                    starved.update(newly)
            self.pool.sync()
        if not kept:
            if self.active or self.groups:
                # every pending chunk is block-starved: run the cheap
                # 1-lane decode executable, not a C-lane mixed step that
                # would carry zero prefill tokens
                return self._begin_decode()
            # nothing runnable: several cursors wedged on blocks — free the
            # youngest lowest-priority one and retry on the next loop turn
            if len(self.chunk_mgr) <= 1:
                raise RuntimeError(
                    "single prefill cursor wedged: BlockPool must fit one "
                    "worst-case request"
                )
            self._preempt(self._victim())
            return None
        # authoritative per-slot write positions from host state: plain
        # decode steps drift the device counters of free and mid-prefill
        # rows (every row increments), so the mixed step pins them — inside
        # its own executable, no resync dispatch — before any write
        base = self._positions()
        self._record_walk(base)
        self.mixed_lane_trace.append(
            (len(decode_slots) + sum(ch.t for ch in kept),
             self.slots + self.chunk_mgr.budget))
        with self.spans("sched.launch"):
            logits, cache = self._steps.mixed_step(
                self.model, self.params, self.pool.cache,
                jnp.asarray(plan.tokens), jnp.asarray(plan.t_new),
                jnp.asarray(base), self.pad_to,
            )
        self.pool.cache = cache
        return ("mixed", logits, kept)

    def _positions(self) -> np.ndarray:
        """Each row's write position from host state [slots]: a decoding
        slot's or group stream's kv length, a prefilling slot's chunk
        cursor, 0 for a free row."""
        base = np.zeros((self.slots,), np.int32)
        for slot, st in self.active.items():
            base[slot] = st.kv_len
        for g in self.groups.values():
            for s in g.slots:
                base[s] = g.kv_len
        if self.chunk_mgr is not None:
            for slot, cur in self.chunk_mgr.cursors.items():
                base[slot] = cur.pos
        return base

    def _record_walk(self, positions: np.ndarray) -> None:
        """One ``walk_trace`` entry for a paged step about to launch: the
        device's rule (``attention.walk_tiles``) on the host's positions
        and the block table the step reads, with no device sync. Exact for
        mixed steps, which pin every row's device counter to
        ``positions``, and for decode steps of decoding slots; a mid-prefill
        row in a decode step (taken only when every pending chunk is
        block-starved) has a device counter that drifted past its cursor,
        so the device may walk more tiles than recorded there."""
        bt, bs = self.pool.block_tables, self.pool.block_size
        mb = bt.shape[1]
        n_valid = np.minimum(positions + 1, mb * bs)
        walked = attention.walk_tiles(n_valid, bt[:, 0] != 0, bs, mb)
        table = -(-mb // attention.walk_tile_blocks(bs, mb))
        self.walk_trace.append((int(walked), table))

    @hot_path
    def _finish_mixed(self, logits, kept) -> List[ServeRequest]:
        toks, outs = self._sync_step(logits)
        with self.spans("sched.commit"):
            self._record_step_metrics()
            self.n_mixed_steps += 1
            now = self._now()
            done = self._commit_decode(toks, now)
        done += self._commit_lanes(outs, now)
        with self.spans("sched.commit"):
            for ch in kept:
                cur = self.chunk_mgr.advance(ch)
                self.n_chunks += 1
                self.n_chunk_tokens += ch.t
                if cur.done:
                    self.chunk_mgr.remove(ch.slot)
                    self._finish_prefill(cur, toks[ch.slot], now)
        return done

    def _finish_prefill(self, cur: ChunkCursor, first, now: float) -> None:
        """The final chunk's last-lane logits ARE the first-token logits:
        commit the request's first token and flip the slot from prefill to
        decode (its device length already equals the prompt length)."""
        first = int(first)  # host value from _sync_step's device_get
        req = cur.req
        req.t_first = now
        req.tokens.append(first)
        req.t_tokens.append(now)
        state = SlotState(
            req=req, slot=cur.slot, n_generated=1, kv_len=cur.n_prompt,
            admit_seq=cur.admit_seq,
        )
        if state.finished(first, self._eos(req)):
            req.t_done = now
            self.finished.append(req)
            self._prefix_insert(cur.slot, req)
            self.pool.evict(cur.slot)
            self._temp[cur.slot] = 0.0
            return
        self.active[cur.slot] = state
        self._token[cur.slot] = first
        self._ngen[cur.slot] = 1

    # ---- slot groups (multi-stream decoding profiles) ---------------------
    def _advance_group(self, g: GroupState, logit_rows, now: float) -> bool:
        """The group's FIRST profile step, at admission: a fresh G = 1
        state steps on the prefill's [n_streams, V] logits rows (eagerly,
        once a request), its feed tokens are committed, and the state
        moves into a lane of the group's bank, where ``_launch_groups``
        advances it from then on. The key is the one the bank uses,
        (rid, token index 0), so preemption replay is key-identical.
        Returns True when the group finished (its slots already
        evicted)."""
        state = g.profile.init(1, self.max_new_cap)
        key = jax.random.fold_in(
            sampling.request_key(self.base_key, g.req.rid), g.n_generated
        )
        out = g.profile.step(state, logit_rows, key)
        with self.spans("sched.admit.sync"):
            feed, perm, done = jax.device_get((out.feed, out.perm, out.done))
        if self._commit_group(g, feed, perm, done, now):
            with self.spans("sched.admit.sync"):
                fin = jax.device_get(g.profile.finalize(out.state))
            self._finish_group(g, fin, now)
            return True
        g.bank.admit(g, out.state)
        return False

    @hot_path
    def _commit_group(self, g: GroupState, feed: np.ndarray, perm, done,
                      now: float) -> bool:
        """Commit one group's step from host values: the streams' feed
        tokens [S], the optional intra-group cache permutation [S] and
        done flag. Returns True when the group is finished."""
        if perm is not None:
            self._apply_group_perm(g, perm)
        g.n_generated += 1
        if g.n_generated == 1:
            g.req.t_first = now
        g.req.t_tokens.append(now)
        self._token[g.slots] = feed
        self._ngen[g.slots] = g.n_generated
        return (done is not None and done.all()) or (
            g.n_generated >= g.req.max_new
        )

    @hot_path
    def _apply_group_perm(self, g: GroupState, perm: np.ndarray) -> None:
        """Re-bind each stream's cache to its surviving parent's (beam's
        Obs #4 reorder). Paged: a pure host-side block-table permutation
        with refcounted common-prefix sharing — NO device KV gather or
        copy (the write-cursor block is unshared copy-on-write by the next
        ``_ensure_blocks``). Contiguous fallback: one donated pool-wide
        row gather (``kv_cache.reorder_donated``), identity outside the
        group's slots."""
        if np.array_equal(perm, np.arange(len(g.slots))):
            return  # every stream kept its own cache
        if self.paged:
            self.pool.permute_group(g.slots, perm)
            self.n_block_permutes += 1
        else:
            full = np.arange(self.slots)
            # repro-lint: disable=HS001 — g.slots is a host list of slot
            # ids; this asarray never touches the device
            sl = np.asarray(g.slots)
            full[sl] = sl[perm]
            self.pool.cache = kv_cache.reorder_donated(
                self.pool.cache, jnp.asarray(full)
            )
            self.n_cache_reorders += 1

    @hot_path
    def _launch_groups(self, logits) -> list:
        """Dispatch one ``profiles.lane_step`` per lane bank with live
        lanes, on the pool-wide step's logits: every resident group
        advances in its bank's one program. Returns the banks' device
        outputs (None for a bank with no resident group); nothing is
        dispatched without resident groups."""
        if not self.groups:
            return []
        with self.spans("sched.groups", lanes=len(self.groups)):
            outs = []
            for bank in self._banks.values():
                if not bank.n_live:
                    outs.append(None)
                    continue
                outs.append(bank.launch(logits, self.base_key))
                self.n_group_batches += 1
                self.group_lane_trace.append(bank.n_live)
            return outs

    @hot_path
    def _commit_lanes(self, outs: list, now: float) -> List[ServeRequest]:
        """Commit every resident group from its bank's host outputs (the
        step wrote each stream's K/V at kv_len, hence the increment);
        finishing groups read their outputs in one more round trip."""
        if not outs:
            return []
        with self.spans("sched.groups"):
            host = dict(zip(self._banks.values(), outs))
            finishing: List[GroupState] = []
            for g in list(self.groups.values()):
                feed, perm, done = host[g.bank]
                g.kv_len += 1
                lane = g.lane
                if self._commit_group(
                    g, feed[lane], None if perm is None else perm[lane],
                    None if done is None else done[lane], now,
                ):
                    finishing.append(g)
                else:
                    g.bank.lanes[lane, -2] = g.n_generated  # token index
            if finishing:
                banks = list(dict.fromkeys(g.bank for g in finishing))
                with self.spans("sched.groups.sync"):
                    fins = jax.device_get([
                        profiles.lane_finalize(b.profile, b.state)
                        for b in banks
                    ])
                fin_of = dict(zip(banks, fins))
                for g in finishing:
                    lane = g.lane
                    self._finish_group(
                        g, {k: v[lane] for k, v in fin_of[g.bank].items()},
                        now,
                    )
        return [g.req for g in finishing]

    def _finish_group(self, g: GroupState, fin: Dict[str, np.ndarray],
                      now: float) -> None:
        """Write the group's finalized host output (G = 1; beam: best
        hypothesis + normalized score) into its request and free every
        stream slot and its lane."""
        toks = fin["tokens"][0]
        g.req.tokens = [int(t) for t in toks[: g.n_generated]]
        if "scores" in fin:
            g.req.score = float(fin["scores"][0])
        g.req.t_done = now
        self.finished.append(g.req)
        self.groups.pop(g.slots[0], None)
        if g.lane >= 0:
            g.bank.release(g)
        for s in g.slots:
            self.pool.evict(s)
            self._temp[s] = 0.0

    # ---- driver ----------------------------------------------------------
    def run(self, requests: List[ServeRequest]) -> List[ServeRequest]:
        """Serve ``requests`` to completion; returns them in finish order.
        Arrival offsets are honored against the wall clock: a request is
        invisible to admission until ``t0 + t_arrival``."""
        self.submit(requests)
        self._t0 = self.clock()
        while self.waiting or self.active or self.groups or (
            self.chunk_mgr is not None and len(self.chunk_mgr)
        ):
            self._admit(self._now())
            if not self.active and not self.groups and not (
                self.chunk_mgr is not None and len(self.chunk_mgr)
            ):
                if self.waiting:  # pool idle, next request not arrived yet
                    wait = self.waiting[0].t_arrival - self._now()
                    if wait > 0:
                        time.sleep(min(wait, 1e-3))
                continue
            self.step()
        return self.finished

    @property
    def mean_occupancy(self) -> float:
        if not self.occupancy_trace:
            return 0.0
        return float(np.mean(self.occupancy_trace))

    @property
    def mean_block_occupancy(self) -> float:
        if not self.block_occupancy_trace:
            return 0.0
        return float(np.mean(self.block_occupancy_trace))

    # ---- prefix-cache metrics (launch/serve.py report) --------------------
    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of eligible chunked admissions that adopted >= 1
        cached block."""
        return self.n_prefix_hits / max(self.n_prefix_lookups, 1)

    @property
    def n_prefix_reclaimed(self) -> int:
        return self._pcache.n_reclaimed_blocks if self._pcache else 0

    @property
    def mean_cached_blocks(self) -> float:
        """Mean per-step count of blocks the prefix cache holds (the
        cached-block occupancy the serve report prints)."""
        if not self.cached_block_trace:
            return 0.0
        return float(np.mean(self.cached_block_trace))
