"""Serving-stack tests: KV slot-pool, continuous-batching scheduler, and
the fixed-slot baseline server.

The central acceptance property: continuous batching (slot recycling,
arbitrary admission order, shared decode batches) produces TOKEN-IDENTICAL
outputs to per-request ``engine.generate`` under greedy decoding — the
scheduler is a pure systems optimization, not a numerics change."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_CONFIGS
from repro.core import engine, sampling
from repro.core.scheduler import Scheduler, ServeRequest
from repro.core.slot_pool import SlotPool
from repro.launch import serve
from repro.models import get_model

KEY = jax.random.PRNGKey(0)
PAD_TO = 8


@pytest.fixture(scope="module")
def llama():
    cfg = SMOKE_CONFIGS["llama3.2-1b"].replace(dtype="float32")
    model = get_model(cfg)
    return model, model.init(KEY)


def _requests(cfg, n, rng, max_news):
    return [
        ServeRequest(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, size=int(rng.integers(3, PAD_TO + 1))),
            max_new=max_news[i % len(max_news)],
        )
        for i in range(n)
    ]


def _reference(model, params, req, *, eos_id=None):
    """Per-request engine.generate on the same padded prompt."""
    buf = np.zeros((1, PAD_TO), np.int32)
    buf[0, : len(req.prompt)] = req.prompt
    return np.asarray(
        engine.generate(
            model, params, jnp.asarray(buf),
            prompt_lengths=jnp.asarray([len(req.prompt)]),
            max_new_tokens=req.max_new, sampler=sampling.greedy, eos_id=eos_id,
        )["tokens"]
    )[0]


# ------------------------------------------------------------- slot pool
def test_slot_pool_free_list_and_occupancy(llama):
    model, _ = llama
    pool = SlotPool(model, slots=3, max_len=16)
    assert pool.n_free == 3 and pool.occupancy == 0.0
    a, b = pool.acquire(), pool.acquire()
    assert (a, b) == (0, 1) and pool.n_active == 2
    pool.evict(a)
    assert pool.n_free == 2 and pool.acquire() == 0  # lowest-first recycle
    pool.reset()
    assert pool.n_free == 3
    assert np.asarray(pool.cache["lengths"]).sum() == 0


def test_slot_pool_assign_writes_one_row_only(llama):
    model, params = llama
    cfg = model.config
    pool = SlotPool(model, slots=3, max_len=16)
    toks = jax.random.randint(KEY, (1, 4), 0, cfg.vocab_size)
    _, row = engine.prefill(model, params, toks, jnp.asarray([4]), 16, None)
    before = jax.tree.map(np.asarray, pool.cache)
    pool.assign(1, row)
    after = pool.cache
    assert int(after["lengths"][1]) == 4
    assert int(after["lengths"][0]) == 0 and int(after["lengths"][2]) == 0
    for b, a, r in zip(
        jax.tree.leaves(before), jax.tree.leaves(after), jax.tree.leaves(row)
    ):
        a = np.asarray(a)
        np.testing.assert_array_equal(a[1], np.asarray(r)[0])  # row replaced
        np.testing.assert_array_equal(a[0], b[0])  # neighbours untouched
        np.testing.assert_array_equal(a[2], b[2])


# ------------------------------------------------- scheduler equivalence
def test_continuous_batching_matches_generate_greedy(llama):
    """Slot recycling with queue > slots: token-identical to per-request
    generate. This is the ISSUE acceptance equivalence."""
    model, params = llama
    rng = np.random.default_rng(0)
    reqs = _requests(model.config, 6, rng, [5, 12, 3, 9])
    sched = Scheduler(model, params, slots=2, pad_to=PAD_TO, max_new_cap=12)
    done = sched.run([dataclasses.replace(r, tokens=[]) for r in reqs])
    assert len(done) == len(reqs)
    assert sched.n_prefills == len(reqs)
    for r in reqs:
        got = next(d for d in done if d.rid == r.rid)
        np.testing.assert_array_equal(
            np.array(got.tokens), _reference(model, params, r),
            err_msg=f"request {r.rid} diverged under continuous batching",
        )


def test_fixed_policy_matches_continuous_greedy(llama):
    """Both policies share the compiled programs: same tokens, different
    schedule (fixed takes at least as many decode steps)."""
    model, params = llama
    rng = np.random.default_rng(1)
    reqs = _requests(model.config, 5, rng, [4, 10, 6])
    outs = {}
    steps = {}
    for policy in ("continuous", "fixed"):
        sched = Scheduler(
            model, params, slots=2, pad_to=PAD_TO, max_new_cap=10, policy=policy
        )
        done = sched.run([dataclasses.replace(r, tokens=[]) for r in reqs])
        outs[policy] = {d.rid: list(d.tokens) for d in done}
        steps[policy] = sched.n_decode_steps
    assert outs["fixed"] == outs["continuous"]
    assert steps["fixed"] >= steps["continuous"]


def test_continuous_keeps_more_slots_busy_than_fixed(llama):
    """On a backlog of uneven output lengths, continuous admission refills
    the slots that run-to-completion batches leave idle: a higher mean
    occupancy and fewer decode steps for the same tokens."""
    model, params = llama
    rng = np.random.default_rng(4)
    reqs = _requests(model.config, 12, rng, [3, 16, 6, 12])
    occupancy, steps, outs = {}, {}, {}
    for policy in ("continuous", "fixed"):
        sched = Scheduler(
            model, params, slots=4, pad_to=PAD_TO, max_new_cap=16,
            policy=policy,
        )
        done = sched.run([dataclasses.replace(r, tokens=[]) for r in reqs])
        assert len(done) == len(reqs)
        outs[policy] = {d.rid: list(d.tokens) for d in done}
        occupancy[policy] = sched.mean_occupancy
        steps[policy] = sched.n_decode_steps
    assert outs["continuous"] == outs["fixed"]
    assert occupancy["continuous"] > occupancy["fixed"]
    assert steps["continuous"] < steps["fixed"]


def test_scheduler_eos_eviction_matches_generate(llama):
    """EOS-finished slots are evicted and refilled mid-flight; outputs
    still match generate's (EOS-padded) contract request by request."""
    model, params = llama
    rng = np.random.default_rng(2)
    reqs = _requests(model.config, 5, rng, [10, 8])
    # pick an eos id the model actually emits: token at step 2 of request 0
    probe = _reference(model, params, reqs[0])
    eos_id = int(probe[2])
    sched = Scheduler(
        model, params, slots=2, pad_to=PAD_TO, max_new_cap=10, eos_id=eos_id
    )
    done = sched.run([dataclasses.replace(r, tokens=[]) for r in reqs])
    for r in reqs:
        got = next(d for d in done if d.rid == r.rid)
        want = _reference(model, params, r, eos_id=eos_id)
        np.testing.assert_array_equal(got.padded_output(eos_id), want)
        if eos_id in got.tokens:
            assert got.tokens[-1] == eos_id  # stopped AT the eos token


def test_paged_pool_matches_contiguous_on_poisson_trace(llama):
    """ISSUE 3 acceptance: the same Poisson trace through the scheduler
    yields identical token streams with the contiguous SlotPool and the
    paged BlockPool — paging changes memory layout, never tokens."""
    model, params = llama
    cfg = model.config

    def trace():
        return serve.poisson_trace(
            serve.data_mod.PAPER_PROFILES["seamless_s2t"], 8,
            pad_to=PAD_TO, max_new_cap=12, vocab_size=cfg.vocab_size,
            arrival_rate=500.0, seed=11,
        )

    outs = {}
    for paged in (False, True):
        sched = Scheduler(
            model, params, slots=2, pad_to=PAD_TO, max_new_cap=12,
            paged=paged, block_size=4, num_blocks=12,
        )
        done = sched.run(trace())
        assert len(done) == 8
        outs[paged] = {d.rid: list(d.tokens) for d in done}
    assert outs[True] == outs[False]


def test_paged_pool_matches_generate_with_eos(llama):
    """Paged serving honors per-slot EOS eviction + block recycling and
    still matches per-request generate's EOS-padded contract."""
    model, params = llama
    rng = np.random.default_rng(2)
    reqs = _requests(model.config, 5, rng, [10, 8])
    probe = _reference(model, params, reqs[0])
    eos_id = int(probe[2])
    sched = Scheduler(
        model, params, slots=2, pad_to=PAD_TO, max_new_cap=10, eos_id=eos_id,
        paged=True, block_size=4, num_blocks=12,
    )
    done = sched.run([dataclasses.replace(r, tokens=[]) for r in reqs])
    for r in reqs:
        got = next(d for d in done if d.rid == r.rid)
        want = _reference(model, params, r, eos_id=eos_id)
        np.testing.assert_array_equal(got.padded_output(eos_id), want)


def test_preemption_replay_token_identical_under_sampling(llama):
    """Satellite: preempt -> full recompute must be token-identical even
    with temperature > 0 — the per-(rid, step) fold_in keys make the
    replayed stream independent of slot placement, batch mates, and how
    many times the request was restarted. A block-starved pool (forced
    preemptions) and a roomy one must emit identical tokens."""
    model, params = llama
    rng = np.random.default_rng(4)
    reqs = [
        ServeRequest(
            rid=i, prompt=rng.integers(0, model.config.vocab_size, size=8),
            max_new=16, temperature=0.8, top_p=0.9,
        )
        for i in range(4)
    ]
    outs = {}
    preempts = {}
    # max_len=25, bs=4: 7 blocks/request worst case; 8 usable cannot hold
    # two full requests => guaranteed mid-decode preemption in the tight arm
    for tag, num_blocks in (("tight", 8), ("roomy", 15)):
        sched = Scheduler(
            model, params, slots=2, pad_to=8, max_new_cap=16,
            paged=True, block_size=4, num_blocks=num_blocks,
            base_key=jax.random.PRNGKey(9),
        )
        done = sched.run([dataclasses.replace(r, tokens=[], t_tokens=[])
                          for r in reqs])
        assert len(done) == len(reqs)
        outs[tag] = {d.rid: list(d.tokens) for d in done}
        preempts[tag] = sched.n_preemptions
    assert preempts["tight"] >= 1 and preempts["roomy"] == 0
    assert outs["tight"] == outs["roomy"], \
        "preemption replay diverged under stochastic sampling"


def test_priority_orders_admission(llama):
    """Satellite: the admission loop picks the highest-priority arrived
    request (stable FIFO within a class) — with one slot, finish order
    follows priority, not submission order."""
    model, params = llama
    rng = np.random.default_rng(5)
    reqs = [
        ServeRequest(rid=i,
                     prompt=rng.integers(0, model.config.vocab_size, size=4),
                     max_new=3, priority=p)
        for i, p in enumerate([0, 5, 1, 0])
    ]
    sched = Scheduler(model, params, slots=1, pad_to=PAD_TO, max_new_cap=3)
    done = sched.run([dataclasses.replace(r, tokens=[], t_tokens=[])
                      for r in reqs])
    assert [d.rid for d in done] == [1, 2, 0, 3]  # priority, then FIFO


def test_age_waiting_boosts_once_per_interval():
    """Unit contract of scheduler.age_waiting: +1 priority per FULL
    boost_after interval waited, idempotent across repeated calls at the
    same clock (n_boosts remembers grants — requeue/replay safe), and
    inert for requests that have not arrived yet or when disabled."""
    from repro.core.scheduler import age_waiting

    def reqs():
        return [
            ServeRequest(rid=0, prompt=np.array([1]), max_new=1,
                         t_arrival=0.0),
            ServeRequest(rid=1, prompt=np.array([1]), max_new=1,
                         t_arrival=0.04, priority=2),
            ServeRequest(rid=2, prompt=np.array([1]), max_new=1,
                         t_arrival=9.0),  # future: the sorted-prefix stop
        ]

    waiting = reqs()
    assert age_waiting(waiting, 0.05, None) == 0  # disabled
    assert age_waiting(waiting, 0.05, 0.02) == 2  # rid0: +2, rid1: +0
    assert [r.priority for r in waiting] == [2, 2, 0]
    assert age_waiting(waiting, 0.05, 0.02) == 0  # idempotent at same now
    assert age_waiting(waiting, 0.09, 0.02) == 4  # rid0 -> 4, rid1 -> 2
    assert [r.priority for r in waiting] == [4, 4, 0]
    assert [r.n_boosts for r in waiting] == [4, 2, 0]


def test_aging_prevents_starvation(llama):
    """ISSUE 9 satellite: max-waiting-time priority boosts in admission.
    A lone priority-0 request facing a steady priority-2 arrival stream
    starves to the very back without aging (every arrived stream request
    outranks it at each slot-free instant); with priority_boost_after
    set, its accrued wait outranks later arrivals and it finishes well
    before the stream drains."""
    model, params = llama

    def trace():
        r = np.random.default_rng(11)

        def mk(rid, priority, t):
            return ServeRequest(
                rid=rid, prompt=r.integers(0, model.config.vocab_size, size=4),
                max_new=4, priority=priority, t_arrival=t,
            )

        # stream pressure: inter-arrival (2 ms) < per-request service
        # time (>= 5 device programs), so the plain arm's queue always
        # holds an arrived priority-2 candidate
        return [mk(0, 0, 0.0)] + [
            mk(i, 2, 0.002 * (i - 1)) for i in range(1, 11)
        ]

    def run(boost):
        sched = Scheduler(model, params, slots=1, pad_to=PAD_TO,
                          max_new_cap=4, priority_boost_after=boost)
        done = sched.run(trace())
        return [d.rid for d in done], sched

    order_plain, sched_plain = run(None)
    assert order_plain[-1] == 0, "stream should starve the p0 request"
    assert sched_plain.n_priority_boosts == 0
    order_aged, sched_aged = run(0.002)
    pos = order_aged.index(0)
    assert pos < len(order_aged) - 1, "aging must break the starvation"
    # after ~2 intervals of waiting, rid 0 outranks every stream request
    # that arrived >= 2 intervals after it — i.e. all but the first few
    assert pos <= 4
    assert sched_aged.n_priority_boosts > 0


def test_preemption_victim_is_youngest_lowest_priority(llama):
    """Satellite: the preemption ladder targets the LOWEST priority class
    and the youngest request inside it — never the high-priority slot."""
    model, params = llama
    rng = np.random.default_rng(6)

    def req(rid, priority):
        return ServeRequest(
            rid=rid, prompt=rng.integers(0, model.config.vocab_size, size=4),
            max_new=8, priority=priority,
        )

    sched = Scheduler(
        model, params, slots=3, pad_to=PAD_TO, max_new_cap=8,
        paged=True, block_size=4, num_blocks=22,
    )
    sched._t0 = sched.clock()
    for r in (req(0, 0), req(1, 0), req(2, 3)):  # old p0, young p0, p3
        sched._admit_one(r, 0.0)
    victim = sched._victim()
    assert victim.req.rid == 1  # youngest of the lowest priority class
    sched._preempt(victim)
    assert sched.waiting[0].rid == 1 and 1 not in {
        st.req.rid for st in sched.active.values()
    }


def test_scheduler_timestamps_and_occupancy(llama):
    model, params = llama
    rng = np.random.default_rng(3)
    reqs = _requests(model.config, 4, rng, [6])
    sched = Scheduler(model, params, slots=2, pad_to=PAD_TO, max_new_cap=6)
    done = sched.run([dataclasses.replace(r, tokens=[]) for r in reqs])
    for r in done:
        assert 0.0 <= r.t_arrival <= r.t_first <= r.t_done
        assert r.ttft >= 0 and r.tpot >= 0 and r.e2e >= r.ttft
    assert 0.0 < sched.mean_occupancy <= 1.0
    # 4 equal-length requests over 2 slots: pool should stay saturated
    assert sched.mean_occupancy > 0.9


def test_per_slot_sampling_mixes_greedy_and_stochastic():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(3, 32)), jnp.float32)
    keys = sampling.slot_step_keys(KEY, jnp.arange(3), jnp.zeros((3,), jnp.int32))
    toks = sampling.sample_slots(
        logits, keys,
        jnp.asarray([0.0, 1.0, 0.0]),  # slots 0/2 greedy, slot 1 sampled
        jnp.asarray([1.0, 1.0, 1.0]),
    )
    greedy = np.argmax(np.asarray(logits), axis=-1)
    assert int(toks[0]) == greedy[0] and int(toks[2]) == greedy[2]
    # slot-placement independence: same (rid, step) key => same sample
    keys2 = sampling.slot_step_keys(
        KEY, jnp.asarray([1]), jnp.zeros((1,), jnp.int32)
    )
    tok2 = sampling.sample_slots(
        logits[1:2], keys2, jnp.asarray([1.0]), jnp.asarray([1.0])
    )
    assert int(toks[1]) == int(tok2[0])


# ------------------------------------------------------- engine contract
def test_generate_pads_to_max_new_on_early_eos(llama):
    """Satellite: early EOS exit must not return ragged tokens."""
    model, params = llama
    prompts = jax.random.randint(KEY, (2, 6), 0, model.config.vocab_size)
    probe = np.asarray(
        engine.generate(model, params, prompts, max_new_tokens=10,
                        sampler=sampling.greedy)["tokens"]
    )
    eos_id = int(probe[0, 1])  # force an early stop on row 0
    out = engine.generate(model, params, prompts, max_new_tokens=10,
                          sampler=sampling.greedy, eos_id=eos_id)
    toks = np.asarray(out["tokens"])
    assert toks.shape == (2, 10)  # padded, never ragged
    assert out["n_steps"] <= 10
    row = toks[0]
    stop = int(np.argmax(row == eos_id))
    assert (row[stop:] == eos_id).all()  # tail is EOS padding


def test_generate_live_mask_unblocks_early_exit(llama):
    """Dead (padding) rows must not stall the all-done early exit, and the
    live row's tokens must be unaffected by the dead row's presence."""
    model, params = llama
    cfg = model.config
    prompt = jax.random.randint(KEY, (1, 6), 0, cfg.vocab_size)
    probe = np.asarray(
        engine.generate(model, params, prompt, max_new_tokens=8,
                        sampler=sampling.greedy)["tokens"]
    )
    eos_id = int(probe[0, 3])
    stop = int(np.argmax(probe[0] == eos_id))  # first occurrence may be < 3
    padded = jnp.concatenate([prompt, jnp.zeros((1, 6), jnp.int32)], axis=0)
    out = engine.generate(
        model, params, padded,
        prompt_lengths=jnp.asarray([6, 1]), max_new_tokens=8,
        sampler=sampling.greedy, eos_id=eos_id,
        live=jnp.asarray([True, False]),
    )
    toks = np.asarray(out["tokens"])
    assert out["n_steps"] == stop + 1  # stopped right at the live row's EOS
    np.testing.assert_array_equal(toks[0, : stop + 1], probe[0, : stop + 1])
    assert (toks[1] == eos_id).all()  # dead row emits only EOS


def test_generate_live_mask_without_eos_masks_dead_rows(llama):
    """Even with no EOS id the live mask must zero dead-row outputs (and
    leave live rows untouched vs an unmasked run)."""
    model, params = llama
    cfg = model.config
    prompt = jax.random.randint(KEY, (1, 6), 0, cfg.vocab_size)
    want = np.asarray(
        engine.generate(model, params, prompt, max_new_tokens=6,
                        sampler=sampling.greedy)["tokens"]
    )
    padded = jnp.concatenate([prompt, jnp.zeros((1, 6), jnp.int32)], axis=0)
    out = engine.generate(
        model, params, padded, prompt_lengths=jnp.asarray([6, 1]),
        max_new_tokens=6, sampler=sampling.greedy,
        live=jnp.asarray([True, False]),
    )
    toks = np.asarray(out["tokens"])
    assert toks.shape == (2, 6)
    np.testing.assert_array_equal(toks[0], want[0])
    assert (toks[1] == 0).all()  # dead row emits only the fill token


def test_batchserver_partial_batch(llama):
    """Satellite: a partial batch (3 requests, 4 slots) serves correctly —
    dead slots are masked and every output matches per-request generate."""
    model, params = llama
    cfg = model.config
    rng = np.random.default_rng(4)
    reqs = [
        serve.Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=5),
                      max_new=6)
        for i in range(3)
    ]
    server = serve.BatchServer(
        model, params, slots=4, sampler=sampling.greedy
    )
    done = server.serve(list(reqs), pad_to=PAD_TO, max_new=6)
    assert len(done) == 3
    for r in reqs:
        buf = np.zeros((1, PAD_TO), np.int32)
        buf[0, :5] = r.prompt
        want = np.asarray(
            engine.generate(
                model, params, jnp.asarray(buf),
                prompt_lengths=jnp.asarray([5]), max_new_tokens=6,
                sampler=sampling.greedy, key=jax.random.PRNGKey(0),
            )["tokens"]
        )[0]
        got = next(d for d in done if d.rid == r.rid).output
        np.testing.assert_array_equal(got, want)


def test_poisson_trace_is_deterministic_and_sorted():
    prof = serve.data_mod.PAPER_PROFILES["seamless_s2t"]
    a = serve.poisson_trace(prof, 8, pad_to=16, max_new_cap=32,
                            vocab_size=100, arrival_rate=50.0, seed=7)
    b = serve.poisson_trace(prof, 8, pad_to=16, max_new_cap=32,
                            vocab_size=100, arrival_rate=50.0, seed=7)
    assert [r.t_arrival for r in a] == [r.t_arrival for r in b]
    assert all(x.t_arrival <= y.t_arrival for x, y in zip(a, a[1:]))
    assert all(1 <= r.max_new <= 32 and len(r.prompt) <= 16 for r in a)
    np.testing.assert_array_equal(a[3].prompt, b[3].prompt)
