"""SpeculativeProfile in the serving pool (paper §4.3, Fig 8).

The defining property carries over from the scheduler suite: speculative
decoding is a pure systems optimization. Draft/verify windows change how
many pool steps a request takes — NEVER its tokens. Every committed
token is sampled from full-model logits under the same per-(request,
stream, token-index) key plain pool decoding uses, so the speculative
arm must be bit-identical to the non-speculative scheduler AND to
per-request ``engine.generate`` at any temperature, through preemption
replays, EOS inside an accepted window, and ``max_new`` truncation
mid-window."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_CONFIGS
from repro.core import engine, profiles, sampling
from repro.core.scheduler import Scheduler, ServeRequest
from repro.models import get_model

KEY = jax.random.PRNGKey(0)
PAD_TO = 8


@pytest.fixture(scope="module")
def llama():
    cfg = SMOKE_CONFIGS["llama3.2-1b"].replace(dtype="float32")
    model = get_model(cfg)
    return model, model.init(KEY)


def _spec(temperature=0.0, top_p=1.0, eos_id=None, exit_layer=1, n_draft=4):
    return profiles.SpeculativeProfile(
        temperature=temperature, top_p=top_p, eos_id=eos_id,
        exit_layer=exit_layer, n_draft=n_draft,
    )


def _requests(cfg, n, rng, max_news, profile=None):
    return [
        ServeRequest(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(3, PAD_TO + 1))),
            max_new=max_news[i % len(max_news)],
            profile=profile,
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


def _fresh(reqs):
    return [dataclasses.replace(r, tokens=[], t_tokens=[]) for r in reqs]


@pytest.mark.parametrize("paged", [False, True])
def test_speculative_matches_generate_greedy(llama, paged):
    """ISSUE 7 acceptance: speculative serving is token-identical to the
    non-speculative engine per request, on BOTH pool kinds, and the
    speculative counters stay internally consistent (each slot-step
    commits its accepted draft prefix plus exactly one full-model
    token)."""
    model, params = llama
    rng = np.random.default_rng(0)
    reqs = _requests(model.config, 6, rng, [16, 9, 12], profile=_spec())
    sched = Scheduler(
        model, params, slots=2, pad_to=PAD_TO, max_new_cap=16,
        paged=paged, block_size=4, num_blocks=15,
    )
    done = sched.run(_fresh(reqs))
    assert len(done) == len(reqs)
    for r in reqs:
        got = next(d for d in done if d.rid == r.rid)
        np.testing.assert_array_equal(
            np.array(got.tokens), _reference(model, params, r),
            err_msg=f"request {r.rid} diverged under speculative decoding",
        )
    assert sched.n_spec_steps >= 1
    # commits = accepted draft tokens + one sampled token per slot-step
    assert (sched.n_spec_committed
            == sched.n_spec_accepted + sched.n_spec_slot_steps)
    assert sched.n_spec_accepted <= sched.n_spec_drafted
    assert sum(sched.spec_commit_hist.values()) == sched.n_spec_slot_steps
    assert (sum(k * v for k, v in sched.spec_commit_hist.items())
            == sched.n_spec_committed)


def test_speculative_fewer_steps_than_plain(llama):
    """The perf claim at its floor: the same trace takes strictly fewer
    pool steps speculatively (windows commit > 1 token on average) with
    identical outputs."""
    model, params = llama
    rng = np.random.default_rng(1)
    reqs = _requests(model.config, 5, rng, [16, 12])
    outs = {}
    steps = {}
    for tag, profile in (("plain", None), ("spec", _spec())):
        sched = Scheduler(
            model, params, slots=2, pad_to=PAD_TO, max_new_cap=16,
            paged=True, block_size=4, num_blocks=15,
        )
        done = sched.run([dataclasses.replace(r, tokens=[], t_tokens=[],
                                              profile=profile)
                          for r in reqs])
        outs[tag] = {d.rid: list(d.tokens) for d in done}
        steps[tag] = sched.n_decode_steps
        if tag == "spec":
            assert (sched.n_spec_committed
                    > sched.n_spec_slot_steps), "windows never accepted"
    assert outs["spec"] == outs["plain"]
    assert steps["spec"] < steps["plain"]


def test_speculative_chunked_pool_commits_windows_in_place(llama):
    """The same greedy trace through the chunked+paged pool, plain and as
    draft/verify windows: identical tokens, more than 1.5 tokens committed
    per speculative slot-step, strictly fewer pool steps, and no new KV
    device bytes (drafts write the static pool; a rejection is a host-side
    rewind)."""
    model, params = llama
    from repro.launch import serve

    def trace(speculative):
        reqs = serve.poisson_trace(
            serve.data_mod.PAPER_PROFILES["seamless_s2t"], 8, pad_to=16,
            max_new_cap=32, vocab_size=model.config.vocab_size,
            arrival_rate=0.0, seed=0,
        )
        if speculative:
            serve.apply_profile_mix(reqs, "speculative", exit_layer=1,
                                    n_draft=4)
        return reqs

    outs, steps = {}, {}
    for tag in ("plain", "spec"):
        sched = Scheduler(
            model, params, slots=4, pad_to=16, max_new_cap=32, paged=True,
            block_size=16, num_blocks=14, chunked=True, prefill_budget=4,
        )
        reserved = sched.pool.reserved_bytes
        done = sched.run(trace(tag == "spec"))
        assert len(done) == 8
        assert sched.pool.reserved_bytes == reserved
        outs[tag] = {d.rid: list(d.tokens) for d in done}
        steps[tag] = sched.n_decode_steps
        if tag == "spec":
            assert sched.n_spec_steps >= 1
            assert sched.n_spec_committed > 1.5 * sched.n_spec_slot_steps
    assert outs["spec"] == outs["plain"]
    assert steps["spec"] < steps["plain"]


def test_speculative_stochastic_identity_and_preemption_replay(llama):
    """Sampling at temperature > 0: committed tokens come from full-model
    logits under the per-(rid, stream, step) fold_in keys, so the
    speculative arm equals the plain scheduler bit-for-bit — and a
    block-starved pool that preempts requests MID-WINDOW must replay to
    the same streams as a roomy one."""
    model, params = llama
    rng = np.random.default_rng(4)
    reqs = [
        ServeRequest(
            rid=i, prompt=rng.integers(0, model.config.vocab_size, size=8),
            max_new=16,
        )
        for i in range(4)
    ]
    prof = _spec(temperature=0.8, top_p=0.9)
    outs = {}
    preempts = {}
    # max_len=25, bs=4: 7 blocks/request worst case; 8 usable cannot hold
    # two full requests => guaranteed mid-decode preemption in the tight arm
    for tag, profile, num_blocks in (
        ("plain", None, 15), ("roomy", prof, 15), ("tight", prof, 8),
    ):
        sched = Scheduler(
            model, params, slots=2, pad_to=PAD_TO, max_new_cap=16,
            paged=True, block_size=4, num_blocks=num_blocks,
            base_key=jax.random.PRNGKey(9),
        )
        done = sched.run([
            dataclasses.replace(
                r, tokens=[], t_tokens=[], profile=profile,
                temperature=0.0 if profile else 0.8,
                top_p=1.0 if profile else 0.9,
            )
            for r in reqs
        ])
        assert len(done) == len(reqs)
        outs[tag] = {d.rid: list(d.tokens) for d in done}
        preempts[tag] = sched.n_preemptions
    assert preempts["tight"] >= 1 and preempts["roomy"] == 0
    assert outs["roomy"] == outs["plain"], \
        "speculative sampling diverged from the plain scheduler"
    assert outs["tight"] == outs["roomy"], \
        "mid-window preemption replay diverged"


def test_speculative_eos_inside_window_truncates_exactly(llama):
    """Satellite: an EOS landing inside an accepted window must stop the
    request AT the EOS token — no draft tokens behind it ever commit —
    matching generate's EOS-padded contract."""
    model, params = llama
    rng = np.random.default_rng(2)
    reqs = _requests(model.config, 5, rng, [12, 9])
    probe = _reference(model, params, reqs[0])
    eos_id = int(probe[2])  # an id the model actually emits mid-stream
    sched = Scheduler(
        model, params, slots=2, pad_to=PAD_TO, max_new_cap=12, eos_id=eos_id,
        paged=True, block_size=4, num_blocks=15,
    )
    done = sched.run([dataclasses.replace(r, tokens=[], t_tokens=[],
                                          profile=_spec(eos_id=eos_id))
                      for r in reqs])
    for r in reqs:
        got = next(d for d in done if d.rid == r.rid)
        want = _reference(model, params, r, eos_id=eos_id)
        np.testing.assert_array_equal(got.padded_output(eos_id), want)
        if eos_id in got.tokens:
            assert got.tokens[-1] == eos_id  # stopped AT the eos token


def test_speculative_max_new_never_overshoots(llama):
    """Satellite: variable-stride commits must truncate at max_new even
    when the final window straddles it (max_new not a multiple of the
    n_draft + 1 window)."""
    model, params = llama
    rng = np.random.default_rng(3)
    reqs = _requests(model.config, 4, rng, [7, 11, 3, 1], profile=_spec())
    sched = Scheduler(
        model, params, slots=2, pad_to=PAD_TO, max_new_cap=11,
        paged=True, block_size=4, num_blocks=15,
    )
    done = sched.run(_fresh(reqs))
    for r in reqs:
        got = next(d for d in done if d.rid == r.rid)
        assert len(got.tokens) == r.max_new, \
            f"request {r.rid}: {len(got.tokens)} tokens vs max_new={r.max_new}"
        np.testing.assert_array_equal(
            np.array(got.tokens), _reference(model, params, r))


def test_mixed_speculative_and_plain_share_pool(llama):
    """Speculative and plain requests share ONE pool: spec slots step
    through draft/verify windows while plain neighbours advance one token
    per step, and both match their references."""
    model, params = llama
    rng = np.random.default_rng(5)
    reqs = _requests(model.config, 6, rng, [12, 10])
    for r in reqs:
        if r.rid % 2 == 0:
            r.profile = _spec()
    sched = Scheduler(
        model, params, slots=3, pad_to=PAD_TO, max_new_cap=12,
        paged=True, block_size=4, num_blocks=18,
    )
    done = sched.run(_fresh(reqs))
    assert sched.n_spec_steps >= 1
    for r in reqs:
        got = next(d for d in done if d.rid == r.rid)
        np.testing.assert_array_equal(
            np.array(got.tokens), _reference(model, params, r),
            err_msg=f"request {r.rid} ({'spec' if r.profile else 'plain'}) "
                    "diverged in the mixed pool",
        )


def test_submit_rejects_invalid_speculative_profiles(llama):
    """exit_layer must leave layers to verify with; n_draft must draft."""
    model, params = llama
    n_layers = model.config.n_layers
    sched = Scheduler(model, params, slots=1, pad_to=PAD_TO, max_new_cap=4)
    prompt = np.zeros((4,), np.int64)
    for bad in (
        _spec(exit_layer=0),
        _spec(exit_layer=n_layers),
        _spec(n_draft=0),
    ):
        with pytest.raises(ValueError):
            sched.submit([ServeRequest(rid=0, prompt=prompt, max_new=2,
                                       profile=bad)])
