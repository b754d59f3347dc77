"""Chunked-prefill subsystem tests (ISSUE 4 acceptance).

Locks down the core/prefill.py contract and its scheduler integration:

- chunk-cursor invariants: spans are contiguous, disjoint, and strictly
  advancing (no prompt token is ever written twice), per-step chunk tokens
  never exceed the budget, and a final partial chunk is PADDED to the lane
  width (``t_new`` records its true length), never dropped;
- the chunked+paged scheduler is token-identical to per-request
  ``engine.generate`` under greedy decoding, with ZERO full-prefill
  programs dispatched;
- mid-prefill preemption replays the prompt from chunk zero and still
  yields the identical token stream (per-(rid, step) fold_in keys);
- the mixed step leaves idle and mid-prefill rows untouched (device
  lengths advance by exactly ``t_new``).

Property tests run under hypothesis when installed (tests/_hyp.py shim)
and as fixed-seed unit sequences otherwise.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hyp import given, hst, settings
from repro.configs import SMOKE_CONFIGS
from repro.core import engine, sampling
from repro.core.prefill import ChunkCursor, ChunkedPrefill
from repro.core.scheduler import Scheduler, ServeRequest
from repro.models import get_model

KEY = jax.random.PRNGKey(0)
PAD_TO = 8


@pytest.fixture(scope="module")
def llama():
    cfg = SMOKE_CONFIGS["llama3.2-1b"].replace(dtype="float32")
    model = get_model(cfg)
    return model, model.init(KEY)


def _reference(model, params, req, *, pad_to=PAD_TO, eos_id=None):
    buf = np.zeros((1, pad_to), np.int32)
    buf[0, : len(req.prompt)] = req.prompt
    return np.asarray(
        engine.generate(
            model, params, jnp.asarray(buf),
            prompt_lengths=jnp.asarray([len(req.prompt)]),
            max_new_tokens=req.max_new, sampler=sampling.greedy, eos_id=eos_id,
        )["tokens"]
    )[0]


def _req(rng, cfg, rid, size, max_new, **kw):
    return ServeRequest(
        rid=rid, prompt=rng.integers(0, cfg.vocab_size, size=size),
        max_new=max_new, **kw,
    )


# -------------------------------------------------------------- the packer
def _drain(slots, budget, prompt_lens, seed):
    """Drive plan/advance to completion; check every packer invariant."""
    rng = np.random.default_rng(seed)
    mgr = ChunkedPrefill(slots, budget, prompt_len=max(prompt_lens))
    prompts = {}
    for i, n in enumerate(prompt_lens):
        prompts[i] = rng.integers(1, 1000, size=n).astype(np.int32)
        mgr.add(ChunkCursor(req=None, slot=i, prompt=prompts[i], admit_seq=i))
    covered = {i: [] for i in prompts}  # slot -> list of (start, t)
    decode_tokens = np.zeros((slots,), np.int32)
    steps = 0
    while len(mgr):
        plan = mgr.plan(decode_tokens, decode_slots=[])
        assert plan.tokens.shape == (slots, budget)
        assert sum(ch.t for ch in plan.chunks) <= budget, "budget exceeded"
        assert len(plan.chunks) <= mgr.max_chunks, "too many chunks"
        assert plan.chunks, "pending cursors but an empty plan (livelock)"
        for ch in plan.chunks:
            cur = mgr.cursors[ch.slot]
            assert ch.start == cur.pos, "span must start at the cursor"
            assert 1 <= ch.t <= budget
            # lane payload is exactly the prompt slice; padding lanes are 0
            np.testing.assert_array_equal(
                plan.tokens[ch.slot, : ch.t],
                prompts[ch.slot][ch.start : ch.start + ch.t],
            )
            assert (plan.tokens[ch.slot, ch.t :] == 0).all(), \
                "final partial chunk must be padded, not widened"
            assert plan.t_new[ch.slot] == ch.t
            covered[ch.slot].append((ch.start, ch.t))
        for ch in plan.chunks:
            cur = mgr.advance(ch)
            if cur.done:
                mgr.remove(ch.slot)
        steps += 1
        assert steps <= sum(prompt_lens) + slots, "packer failed to progress"
    for slot, spans in covered.items():
        # contiguous, disjoint, complete: no token written twice or dropped
        spans.sort()
        pos = 0
        for start, t in spans:
            assert start == pos, f"slot {slot}: gap or overlap at {start}"
            pos += t
        assert pos == len(prompts[slot]), f"slot {slot}: prompt not covered"


def test_packer_fixed_sequences():
    """Hypothesis-free coverage of the same invariant machinery."""
    _drain(3, 4, [5, 1, 9], seed=0)  # partial final chunks + tiny prompt
    _drain(2, 16, [16, 3], seed=1)  # one-shot chunk + budget sharing
    _drain(4, 3, [7, 7, 7, 7], seed=2)  # budget contention, FIFO drain


@settings(max_examples=30, deadline=None)
@given(
    hst.integers(min_value=1, max_value=17),
    hst.lists(hst.integers(min_value=1, max_value=23), min_size=1, max_size=4),
    hst.integers(min_value=0, max_value=2**31 - 1),
)
def test_packer_property(budget, prompt_lens, seed):
    """Random budgets/prompt lengths preserve every cursor invariant."""
    _drain(len(prompt_lens), budget, prompt_lens, seed)


def test_packer_interleaves_decode_lanes():
    """Decode slots keep lane 0 (t_new=1) while a cursor's chunk shares the
    same step — and the cursor never steals a decode slot's row."""
    mgr = ChunkedPrefill(3, 4, prompt_len=8)
    mgr.add(ChunkCursor(req=None, slot=1, prompt=np.arange(1, 7), admit_seq=0))
    decode_tokens = np.asarray([7, 0, 9], np.int32)
    plan = mgr.plan(decode_tokens, decode_slots=[0, 2])
    np.testing.assert_array_equal(plan.t_new, [1, 4, 1])
    assert plan.tokens[0, 0] == 7 and plan.tokens[2, 0] == 9
    np.testing.assert_array_equal(plan.tokens[1], [1, 2, 3, 4])
    assert [(c.slot, c.start, c.t) for c in plan.chunks] == [(1, 0, 4)]


def test_packer_skip_redistributes_budget():
    """A block-starved head cursor (skip) must not hoard the step budget:
    its share flows to the next cursor in FIFO order."""
    mgr = ChunkedPrefill(3, 4, prompt_len=8)
    mgr.add(ChunkCursor(req=None, slot=0, prompt=np.arange(1, 9), admit_seq=0))
    mgr.add(ChunkCursor(req=None, slot=2, prompt=np.arange(1, 7), admit_seq=1))
    plan = mgr.plan(np.zeros((3,), np.int32), decode_slots=[], skip=[0])
    assert [(c.slot, c.start, c.t) for c in plan.chunks] == [(2, 0, 4)]
    np.testing.assert_array_equal(plan.t_new, [0, 0, 4])


def test_cursor_rejects_empty_prompt():
    with pytest.raises(ValueError):
        ChunkCursor(req=None, slot=0, prompt=np.zeros((0,), np.int32))


# ------------------------------------------------ scheduler integration
def test_chunked_matches_generate_greedy(llama):
    """ISSUE 4 acceptance: chunked+paged serving is token-identical to
    per-request generate, with ZERO full-prefill programs dispatched —
    admission rides the mixed step."""
    model, params = llama
    rng = np.random.default_rng(0)
    reqs = [
        _req(rng, model.config, i, int(rng.integers(3, PAD_TO + 1)),
             [5, 12, 3, 9][i % 4])
        for i in range(6)
    ]
    # budget 3 does not divide block_size 4: chunks cross block boundaries
    sched = Scheduler(
        model, params, slots=2, pad_to=PAD_TO, max_new_cap=12,
        paged=True, block_size=4, num_blocks=12, chunked=True,
        prefill_budget=3,
    )
    done = sched.run([dataclasses.replace(r, tokens=[], t_tokens=[])
                      for r in reqs])
    assert len(done) == len(reqs)
    assert sched.n_prefills == 0, "chunked admission ran a full prefill"
    assert sched.n_chunk_tokens == sum(len(r.prompt) for r in reqs)
    for r in reqs:
        got = next(d for d in done if d.rid == r.rid)
        np.testing.assert_array_equal(
            np.array(got.tokens), _reference(model, params, r),
            err_msg=f"request {r.rid} diverged under chunked prefill",
        )


def test_chunked_matches_generate_with_eos(llama):
    """Per-slot EOS eviction (including EOS on the FIRST token, sampled
    from the final chunk's logits) still matches generate's contract."""
    model, params = llama
    rng = np.random.default_rng(2)
    reqs = [_req(rng, model.config, i, 5 + (i % 4), [10, 8][i % 2])
            for i in range(5)]
    probe = _reference(model, params, reqs[0])
    eos_id = int(probe[2])
    sched = Scheduler(
        model, params, slots=2, pad_to=PAD_TO, max_new_cap=10, eos_id=eos_id,
        paged=True, block_size=4, num_blocks=12, chunked=True,
    )
    done = sched.run([dataclasses.replace(r, tokens=[], t_tokens=[])
                      for r in reqs])
    for r in reqs:
        got = next(d for d in done if d.rid == r.rid)
        want = _reference(model, params, r, eos_id=eos_id)
        np.testing.assert_array_equal(got.padded_output(eos_id), want)


def test_chunked_mid_prefill_preemption_replays(llama):
    """A half-prefilled request preempted mid-stream must replay from
    chunk zero and still emit the identical greedy tokens."""
    model, params = llama
    rng = np.random.default_rng(3)
    reqs = [_req(rng, model.config, i, PAD_TO, 6) for i in range(3)]
    sched = Scheduler(
        model, params, slots=2, pad_to=PAD_TO, max_new_cap=6,
        paged=True, block_size=4, num_blocks=12, chunked=True,
        prefill_budget=4,
    )
    run_reqs = [dataclasses.replace(r, tokens=[], t_tokens=[]) for r in reqs]
    sched.submit(run_reqs)
    sched._t0 = sched.clock()
    sched._admit(0.0)
    assert len(sched.chunk_mgr) >= 1
    sched.step()  # first chunk lands (4 of 8 prompt tokens)
    slot, cur = next(iter(sched.chunk_mgr.cursors.items()))
    assert 0 < cur.pos < cur.n_prompt, "cursor should be mid-prefill"
    sched._preempt(cur)  # deterministic mid-prefill preemption
    assert sched.n_preemptions == 1
    assert slot not in sched.chunk_mgr.cursors
    while sched.waiting or sched.active or len(sched.chunk_mgr):
        sched._admit(sched._now())
        sched.step()
    assert len(sched.finished) == len(reqs)
    for r in reqs:
        got = next(d for d in sched.finished if d.rid == r.rid)
        np.testing.assert_array_equal(
            np.array(got.tokens), _reference(model, params, r),
            err_msg=f"request {r.rid} corrupted by mid-prefill preemption",
        )


def test_chunked_late_admission_into_drifted_slot(llama):
    """Regression: plain decode steps (no cursors pending) increment EVERY
    device row's length counter, including freed slots. A request admitted
    into such a slot later must not write its chunks at the drifted
    offset — the mixed step pins every row's counter from the scheduler's
    host state (decode kv length / chunk cursor / 0 for free rows)."""
    model, params = llama
    rng = np.random.default_rng(7)
    a = _req(rng, model.config, 0, 6, 2)
    b = _req(rng, model.config, 1, 6, 12)
    c = _req(rng, model.config, 2, 7, 6)
    sched = Scheduler(
        model, params, slots=2, pad_to=PAD_TO, max_new_cap=12,
        paged=True, block_size=4, num_blocks=12, chunked=True,
    )
    sched.submit([dataclasses.replace(r, tokens=[], t_tokens=[])
                  for r in (a, b)])
    sched._t0 = sched.clock()
    sched._admit(0.0)
    while not any(d.rid == 0 for d in sched.finished):
        sched.step()  # drain A; B keeps decoding
    for _ in range(3):  # cursor queue empty: plain decode steps — the
        sched.step()  # freed slot's device length counter drifts upward
    sched.submit([dataclasses.replace(c, tokens=[], t_tokens=[])])
    sched._admit(sched._now())  # C lands in the drifted slot
    slot = next(iter(sched.chunk_mgr.cursors))
    assert int(np.asarray(sched.pool.cache["lengths"])[slot]) > 0, \
        "test setup: the freed slot's device counter should have drifted"
    while sched.waiting or sched.active or len(sched.chunk_mgr):
        sched._admit(sched._now())
        was_mixed = sched.n_mixed_steps
        sched.step()
        if sched.n_mixed_steps > was_mixed:
            # a mixed step just pinned every counter from host state
            lengths = np.asarray(sched.pool.cache["lengths"])
            for s, cur in sched.chunk_mgr.cursors.items():
                assert lengths[s] == cur.pos, \
                    "cursor slot device length desynced from the cursor"
            for s, st in sched.active.items():
                assert lengths[s] == st.kv_len
    for r in (a, b, c):
        got = next(d for d in sched.finished if d.rid == r.rid)
        np.testing.assert_array_equal(
            np.array(got.tokens), _reference(model, params, r),
            err_msg=f"request {r.rid} corrupted by slot-length drift",
        )


def test_chunked_block_exhaustion_queues_and_recovers(llama):
    """Scheduler-driven back-pressure: a pool too small for two full
    requests must preempt (possibly mid-prefill) and still finish every
    request token-identically."""
    model, params = llama
    pad_to, max_new = 8, 16
    rng = np.random.default_rng(2)
    reqs = [_req(rng, model.config, i, 8, max_new) for i in range(4)]
    # max_len=25, bs=4 -> 7 blocks/request worst case; 7 usable blocks
    sched = Scheduler(
        model, params, slots=2, pad_to=pad_to, max_new_cap=max_new,
        paged=True, block_size=4, num_blocks=8, chunked=True,
    )
    done = sched.run([dataclasses.replace(r, tokens=[], t_tokens=[])
                      for r in reqs])
    assert len(done) == len(reqs)
    assert sched.n_preemptions >= 1
    for r in reqs:
        got = next(d for d in done if d.rid == r.rid)
        want = _reference(model, params, r, pad_to=pad_to)
        np.testing.assert_array_equal(np.array(got.tokens), want,
                                      err_msg=f"request {r.rid} corrupted")


def test_mixed_step_advances_lengths_by_t_new(llama):
    """Device length counters end at exactly base + t_new: decode rows +1,
    the chunk row by its chunk, idle rows pinned to 0 — regardless of how
    far the device counters had drifted (the host base is authoritative,
    pinned inside the executable)."""
    model, params = llama
    sched = Scheduler(
        model, params, slots=3, pad_to=PAD_TO, max_new_cap=4,
        paged=True, block_size=4, num_blocks=13, chunked=True,
    )
    pool = sched.pool
    for slot, n in ((0, 3), (1, 4)):  # cover each row's whole write span
        assert pool.acquire() == slot
        assert pool.ensure(slot, n)  # blocks for the fake occupants
    # drifted device counters: the host-provided base must win
    pool.cache["lengths"] = jnp.asarray([9, 8, 7], jnp.int32)
    pool.sync()
    tokens = jnp.zeros((3, 4), jnp.int32)
    t_new = jnp.asarray([1, 3, 0], jnp.int32)  # decode, chunk, idle
    base = jnp.asarray([3, 2, 0], jnp.int32)
    logits, cache = engine.mixed_step(
        model, params, pool.cache, tokens, t_new, base
    )
    assert logits.shape[0] == 3
    np.testing.assert_array_equal(np.asarray(cache["lengths"]), [4, 5, 0])


def test_scheduler_rejects_chunked_without_paged(llama):
    model, params = llama
    with pytest.raises(ValueError):
        Scheduler(model, params, slots=2, pad_to=PAD_TO, max_new_cap=4,
                  chunked=True)
    with pytest.raises(ValueError):
        Scheduler(model, params, slots=2, pad_to=PAD_TO, max_new_cap=4,
                  paged=True, chunked=True, policy="fixed")
