"""DecodingProfile equivalence suite (ISSUE 5 acceptance).

The central property: beam and contrastive requests served as slot GROUPS
through the continuous-batching scheduler are TOKEN- (and score-)
IDENTICAL to their batch-at-a-time engines under greedy settings — the
profile API and the group machinery (all-or-nothing slot acquisition,
block-table permutation + copy-on-write beam reorder, group preemption
replay) are pure systems changes, never numerics changes.

Also locks down the per-(request, stream) RNG fix: an n-beam/contrastive
group's streams must never share a sampling key (fold in the stream
index, not just the rid)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_CONFIGS
from repro.core import engine, kv_cache, profiles, sampling
from repro.core.scheduler import Scheduler, ServeRequest
from repro.core.slot_pool import BlockPool
from repro.models import attention as A
from repro.models import get_model

KEY = jax.random.PRNGKey(0)
PAD_TO = 8


@pytest.fixture(scope="module")
def llama():
    cfg = SMOKE_CONFIGS["llama3.2-1b"].replace(dtype="float32")
    model = get_model(cfg)
    return model, model.init(KEY)


@pytest.fixture(scope="module")
def whisper():
    cfg = SMOKE_CONFIGS["whisper-base"].replace(dtype="float32")
    model = get_model(cfg)
    return model, model.init(KEY)


# ---------------------------------------------------- per-stream RNG fix
def test_request_key_folds_stream_index():
    """Satellite: streams of one request get DISTINCT keys — folding in
    only the rid handed an n-beam/contrastive group one shared stream."""
    k0 = sampling.request_key(KEY, 3, 0)
    k1 = sampling.request_key(KEY, 3, 1)
    assert not np.array_equal(np.asarray(k0), np.asarray(k1))
    # stream 0 is the default: existing single-stream callers unchanged
    np.testing.assert_array_equal(
        np.asarray(sampling.request_key(KEY, 3)), np.asarray(k0)
    )


def test_slot_step_keys_fold_stream_index():
    rids = jnp.asarray([7, 7, 7])
    steps = jnp.asarray([4, 4, 4])
    streams = jnp.asarray([0, 1, 2])
    keys = np.asarray(sampling.slot_step_keys(KEY, rids, steps, streams))
    assert len({tuple(k) for k in keys}) == 3, "group streams shared a key"
    # omitting streams == all-zero streams (backwards compatible)
    np.testing.assert_array_equal(
        np.asarray(sampling.slot_step_keys(KEY, rids, steps))[0], keys[0]
    )
    # distinct streams sample independently even at equal (rid, step)
    logits = jnp.asarray(
        np.random.default_rng(0).normal(size=(3, 64)), jnp.float32
    )
    toks = np.asarray(
        sampling.sample_slots(
            logits, jnp.asarray(sampling.slot_step_keys(KEY, rids, steps, streams)),
            jnp.full((3,), 1.0), jnp.full((3,), 1.0),
        )
    )
    assert len(set(toks.tolist())) > 1, "identical keys across streams"


# ------------------------------------------------ engine wrapper contract
def test_generate_beam_accepts_prompt_tokens(llama):
    """The profile rework generalizes generate_beam beyond BOS-only
    prompts; the historical (batch, bos_id) form must stay identical."""
    model, params = llama
    old = engine.generate_beam(
        model, params, batch=2, n_beams=2, bos_id=1, eos_id=2,
        max_new_tokens=5,
    )
    new = engine.generate_beam(
        model, params, n_beams=2, eos_id=2, max_new_tokens=5,
        prompt_tokens=jnp.ones((2, 1), jnp.int32),
    )
    np.testing.assert_array_equal(np.asarray(old["tokens"]),
                                  np.asarray(new["tokens"]))
    np.testing.assert_allclose(np.asarray(old["scores"]),
                               np.asarray(new["scores"]), rtol=1e-6)
    with pytest.raises(ValueError):
        engine.generate_beam(model, params, n_beams=2, eos_id=2,
                             max_new_tokens=5)  # no prompt, no batch/bos


# ------------------------------------------- beam groups == batch engine
def test_beam_group_matches_batch_engine_encdec(whisper):
    """Two concurrent 4-beam enc-dec requests through the contiguous pool
    (per-slot cross-attention rows carry each request's OWN encoder
    frames) must reproduce batch generate_beam's tokens AND scores —
    whisper beams genuinely diverge, so this exercises non-trivial
    per-step cache permutations."""
    model, params = whisper
    cfg = model.config
    frames = np.asarray(
        jax.random.normal(jax.random.PRNGKey(1),
                          (2, cfg.encdec.n_frames, cfg.d_model))
    )
    flens = np.asarray([40, cfg.encdec.n_frames], np.int32)
    ref = engine.generate_beam(
        model, params, batch=2, n_beams=4, bos_id=1, eos_id=2,
        max_new_tokens=8,
        extra_inputs={"frames": jnp.asarray(frames),
                      "frame_lengths": jnp.asarray(flens)},
    )
    sched = Scheduler(model, params, slots=8, pad_to=4, max_new_cap=8)
    reqs = [
        ServeRequest(
            rid=i, prompt=np.asarray([1]), max_new=8,
            profile=profiles.BeamProfile(n_beams=4, eos_id=2),
            extra_inputs={"frames": frames[i: i + 1],
                          "frame_lengths": flens[i: i + 1]},
        )
        for i in range(2)
    ]
    done = sched.run(reqs)
    assert sched.n_cache_reorders >= 1  # contiguous fallback engaged
    for i in range(2):
        got = next(d for d in done if d.rid == i)
        np.testing.assert_array_equal(
            np.asarray(got.tokens),
            np.asarray(ref["tokens"])[i][: len(got.tokens)],
            err_msg=f"beam group {i} diverged from batch generate_beam",
        )
        assert got.score == pytest.approx(float(ref["scores"][i]), abs=1e-4)


def test_beam_group_paged_block_table_reorder(llama):
    """Paged beam groups: token/score-identical to the batch engine with
    the KV reorder done ENTIRELY as host-side block-table permutation +
    copy-on-write — zero device cache reorders (the acceptance criterion:
    no per-step device KV gather on the paged path)."""
    model, params = llama
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, model.config.vocab_size, size=5)
    ref = engine.generate_beam(
        model, params, n_beams=3, eos_id=2, max_new_tokens=10,
        prompt_tokens=jnp.asarray(prompt[None]),
    )
    sched = Scheduler(model, params, slots=3, pad_to=PAD_TO, max_new_cap=10,
                      paged=True, block_size=4, num_blocks=22)
    reserved = sched.pool.reserved_bytes
    req = ServeRequest(rid=0, prompt=prompt, max_new=10,
                       profile=profiles.BeamProfile(n_beams=3, eos_id=2))
    done = sched.run([req])
    np.testing.assert_array_equal(
        np.asarray(done[0].tokens),
        np.asarray(ref["tokens"])[0][: len(done[0].tokens)],
    )
    assert done[0].score == pytest.approx(float(ref["scores"][0]), abs=1e-5)
    assert sched.n_cache_reorders == 0, "paged beam used the device gather"
    assert sched.n_block_permutes >= 1, "block-table permutation never ran"
    assert sched.pool.reserved_bytes == reserved  # no new KV device buffers
    # the pool drained: every block came home despite sharing/CoW
    assert sorted(sched.pool._free_blocks) == list(range(1, 22))
    assert (sched.pool._ref[1:] == 0).all()


# ------------------------------------------ contrastive groups == batch
def test_contrastive_group_matches_batch_engine(llama):
    model, params = llama
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, model.config.vocab_size, size=6)
    ref = engine.generate_contrastive(
        model, params, jnp.asarray(prompt[None]), uncond_token=0,
        n_image_tokens=8, guidance=2.5,
    )
    for paged in (False, True):
        sched = Scheduler(model, params, slots=2, pad_to=PAD_TO,
                          max_new_cap=8, paged=paged, block_size=4,
                          num_blocks=22 if paged else None)
        req = ServeRequest(
            rid=0, prompt=prompt, max_new=8,
            profile=profiles.ContrastiveProfile(uncond_token=0, guidance=2.5),
        )
        done = sched.run([req])
        np.testing.assert_array_equal(
            np.asarray(done[0].tokens), np.asarray(ref["tokens"])[0],
            err_msg=f"contrastive group diverged (paged={paged})",
        )


def test_contrastive_group_respects_image_mask():
    """A VLM contrastive group in the pool only emits image-range tokens,
    matching the batch engine exactly."""
    cfg = SMOKE_CONFIGS["chameleon-34b"].replace(dtype="float32")
    model = get_model(cfg)
    params = model.init(KEY)
    from repro.models import vlm

    off = vlm.image_token_offset(cfg)
    prompt = np.asarray(
        jax.random.randint(KEY, (5,), 0, off), np.int32
    )
    ref = engine.generate_contrastive(
        model, params, jnp.asarray(prompt[None]), uncond_token=0,
        n_image_tokens=6, guidance=3.0,
    )
    sched = Scheduler(model, params, slots=2, pad_to=PAD_TO, max_new_cap=6)
    req = ServeRequest(
        rid=0, prompt=prompt, max_new=6,
        profile=profiles.ContrastiveProfile(uncond_token=0, guidance=3.0,
                                            mask_offset=off),
    )
    done = sched.run([req])
    np.testing.assert_array_equal(np.asarray(done[0].tokens),
                                  np.asarray(ref["tokens"])[0])
    assert all(t >= off for t in done[0].tokens)


# ---------------------------------------------- group preemption replay
def test_group_preemption_replays_token_identically(llama):
    """A block-starved pool must preempt WHOLE groups and replay them
    token-identically: the tight and roomy arms emit the same streams for
    every request (beam groups AND greedy singles)."""
    model, params = llama
    rng = np.random.default_rng(4)
    v = model.config.vocab_size

    def reqs():
        beam = profiles.BeamProfile(n_beams=2, eos_id=2)
        return [
            ServeRequest(rid=0, prompt=rng.integers(0, v, size=6), max_new=12,
                         profile=dataclasses.replace(beam)),
            ServeRequest(rid=1, prompt=rng.integers(0, v, size=8), max_new=12),
            ServeRequest(rid=2, prompt=rng.integers(0, v, size=5), max_new=12,
                         profile=dataclasses.replace(beam)),
            ServeRequest(rid=3, prompt=rng.integers(0, v, size=7), max_new=12),
        ]

    trace = reqs()
    outs, scores, preempts = {}, {}, {}
    # max_len=21, bs=4 -> max_blocks=6; a 2-beam group can need 12 blocks,
    # so 13 usable blocks (tight) serve ONE group alone but preempt under
    # concurrency; 40 (roomy) never preempt
    for tag, num_blocks in (("tight", 14), ("roomy", 41)):
        sched = Scheduler(model, params, slots=6, pad_to=PAD_TO,
                          max_new_cap=12, paged=True, block_size=4,
                          num_blocks=num_blocks)
        done = sched.run([
            dataclasses.replace(r, tokens=[], t_tokens=[]) for r in trace
        ])
        assert len(done) == len(trace)
        outs[tag] = {d.rid: list(d.tokens) for d in done}
        scores[tag] = {d.rid: d.score for d in done}
        preempts[tag] = sched.n_preemptions
    assert preempts["tight"] >= 1 and preempts["roomy"] == 0
    assert outs["tight"] == outs["roomy"], "group preemption replay diverged"
    assert scores["tight"] == scores["roomy"]


# ------------------------------------- mixed-profile trace, tight pools
def test_mixed_profile_poisson_trace_tight_pool(llama):
    """Satellite: a Poisson trace cycling greedy/beam/contrastive through
    the chunked+paged scheduler under a tight block pool — groups admit,
    decode, preempt, and replay alongside chunk cursors, and every request
    matches its batch engine."""
    model, params = llama
    cfg = model.config
    from repro.launch import serve

    reqs = serve.poisson_trace(
        serve.data_mod.PAPER_PROFILES["seamless_s2t"], 6, pad_to=PAD_TO,
        max_new_cap=10, vocab_size=cfg.vocab_size, arrival_rate=300.0,
        seed=11,
    )
    serve.apply_profile_mix(reqs, "greedy,beam,contrastive", n_beams=2,
                            beam_eos_id=2, guidance=2.0)
    # max_len=19, bs=4 -> max_blocks=5; 2-beam groups need <= 10 <= 12
    sched = Scheduler(model, params, slots=5, pad_to=PAD_TO, max_new_cap=10,
                      paged=True, block_size=4, num_blocks=13,
                      chunked=True, prefill_budget=4)
    done = sched.run(reqs)
    assert len(done) == len(reqs)
    assert sched.n_group_admissions >= 4
    assert sched.n_cache_reorders == 0
    for r in reqs:
        got = next(d for d in done if d.rid == r.rid)
        prompt = jnp.asarray(np.asarray(r.prompt, np.int32)[None])
        if isinstance(r.profile, profiles.BeamProfile):
            want = np.asarray(engine.generate_beam(
                model, params, n_beams=2, eos_id=2, max_new_tokens=r.max_new,
                prompt_tokens=prompt,
            )["tokens"])[0]
        elif isinstance(r.profile, profiles.ContrastiveProfile):
            want = np.asarray(engine.generate_contrastive(
                model, params, prompt, uncond_token=0,
                n_image_tokens=r.max_new, guidance=2.0,
            )["tokens"])[0]
        else:
            want = np.asarray(engine.generate(
                model, params, prompt, max_new_tokens=r.max_new,
                sampler=sampling.greedy,
            )["tokens"])[0]
        np.testing.assert_array_equal(
            np.asarray(got.tokens), want[: len(got.tokens)],
            err_msg=f"request {r.rid} ({type(r.profile).__name__}) diverged",
        )


def test_mixed_profile_trace_reorders_beams_in_place(llama):
    """A greedy/beam/contrastive backlog through the chunked+paged pool
    with room for every group: each group is admitted once, the beam
    reorder runs only as block-table permutation (never the device
    gather), the pool's reserved bytes never change, and every beam
    group's score matches its batch engine's."""
    model, params = llama
    from repro.launch import serve

    reqs = serve.poisson_trace(
        serve.data_mod.PAPER_PROFILES["seamless_s2t"], 6, pad_to=PAD_TO,
        max_new_cap=10, vocab_size=model.config.vocab_size,
        arrival_rate=0.0, seed=3,
    )
    serve.apply_profile_mix(reqs, "greedy,beam,contrastive", n_beams=2,
                            beam_eos_id=2, guidance=2.0)
    sched = Scheduler(model, params, slots=4, pad_to=PAD_TO, max_new_cap=10,
                      paged=True, block_size=4, num_blocks=22,
                      chunked=True, prefill_budget=4)
    reserved = sched.pool.reserved_bytes
    done = sched.run(reqs)
    assert len(done) == len(reqs)
    assert sched.n_preemptions == 0
    assert sched.n_group_admissions == 4  # two beam and two CFG groups
    assert sched.n_block_permutes >= 1, "beam reorder never ran"
    assert sched.n_cache_reorders == 0, "paged beam used the device gather"
    assert sched.pool.reserved_bytes == reserved  # no new KV device buffers
    beams = [r for r in reqs if isinstance(r.profile, profiles.BeamProfile)]
    assert beams
    for r in beams:
        got = next(d for d in done if d.rid == r.rid)
        ref = engine.generate_beam(
            model, params, n_beams=2, eos_id=2, max_new_tokens=r.max_new,
            prompt_tokens=jnp.asarray(np.asarray(r.prompt, np.int32)[None]),
        )
        np.testing.assert_array_equal(
            np.asarray(got.tokens),
            np.asarray(ref["tokens"])[0][: len(got.tokens)])
        assert got.score == pytest.approx(float(ref["scores"][0]), abs=1e-4)


# ------------------------------------------------- group admission gates
def test_group_feasibility_checks(llama):
    model, params = llama
    beam = profiles.BeamProfile(n_beams=4, eos_id=2)
    with pytest.raises(ValueError):  # group wider than the pool
        Scheduler(model, params, slots=2, pad_to=4, max_new_cap=4).submit(
            [ServeRequest(rid=0, prompt=np.asarray([1]), max_new=4,
                          profile=beam)]
        )
    with pytest.raises(ValueError):  # group can exceed the whole block pool
        Scheduler(
            model, params, slots=4, pad_to=4, max_new_cap=4,
            paged=True, block_size=4, num_blocks=9,  # max_blocks=3, 4*3 > 8
        ).submit(
            [ServeRequest(rid=0, prompt=np.asarray([1]), max_new=4,
                          profile=beam)]
        )


def test_single_stream_sampling_profile_maps_to_slot_sampler(llama):
    """A 1-stream SamplingProfile rides the vectorized per-slot path:
    identical tokens to the equivalent (temperature, top_p) request."""
    model, params = llama
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, model.config.vocab_size, size=6)

    def run(req):
        sched = Scheduler(model, params, slots=2, pad_to=PAD_TO,
                          max_new_cap=8, base_key=jax.random.PRNGKey(5))
        return sched.run([req])[0].tokens

    a = run(ServeRequest(rid=0, prompt=prompt, max_new=8,
                         temperature=0.7, top_p=0.9))
    b = run(ServeRequest(
        rid=0, prompt=prompt, max_new=8,
        profile=profiles.SamplingProfile(temperature=0.7, top_p=0.9),
    ))
    assert a == b
    # callable samplers are a batch-engine escape hatch, rejected loudly
    with pytest.raises(ValueError):
        run(ServeRequest(
            rid=0, prompt=prompt, max_new=8,
            profile=profiles.SamplingProfile(sampler=sampling.greedy),
        ))


def test_sampling_profile_eos_id_honored_in_pool(llama):
    """Regression: a single-stream SamplingProfile's eos_id must stop the
    served request exactly like engine.generate with the same profile —
    the scheduler-level default must not silently win."""
    model, params = llama
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, model.config.vocab_size, size=6)
    probe = np.asarray(engine.generate(
        model, params, jnp.asarray(prompt[None]), max_new_tokens=10,
        sampler=sampling.greedy,
    )["tokens"])[0]
    eos_id = int(probe[2])  # an id the model actually emits at step 2
    want = np.asarray(engine.generate(
        model, params, jnp.asarray(prompt[None]), max_new_tokens=10,
        sampler=sampling.greedy, eos_id=eos_id,
    )["tokens"])[0]
    sched = Scheduler(model, params, slots=2, pad_to=PAD_TO, max_new_cap=10)
    done = sched.run([ServeRequest(
        rid=0, prompt=prompt, max_new=10,
        profile=profiles.SamplingProfile(eos_id=eos_id),
    )])
    np.testing.assert_array_equal(done[0].padded_output(eos_id), want)
    assert done[0].tokens[-1] == eos_id and len(done[0].tokens) < 10


def test_group_slot_reuse_keeps_stream_keys_slot_independent(llama):
    """Regression: a slot vacated by a GROUP stream (stale stream index)
    must sample a later single-stream stochastic request with stream=0
    keys — tokens must be identical with and without the preceding beam
    group (slot-placement independence of the RNG)."""
    model, params = llama
    rng = np.random.default_rng(8)
    v = model.config.vocab_size
    # the smoke model's logit gaps are ~100 nats, so only an extreme
    # temperature makes the sampling distribution genuinely flat — i.e.
    # makes the KEY matter, and the stale-stream bug observable
    stoch_a = ServeRequest(rid=5, prompt=rng.integers(0, v, size=6),
                           max_new=8, temperature=50.0, top_p=1.0)
    stoch_b = ServeRequest(rid=6, prompt=rng.integers(0, v, size=6),
                           max_new=8, temperature=50.0, top_p=1.0)
    pin = ServeRequest(rid=9, prompt=rng.integers(0, v, size=5), max_new=8)

    def run(with_beam):
        # the pin holds slot 0 throughout, so the two stochastic requests
        # land in slots 1 and 2 in BOTH runs — with the beam group, slot 2
        # previously held the group's stream 1 (the stale nonzero index
        # the fix resets; slot 1 held stream 0, which is benign)
        reqs = [dataclasses.replace(pin, tokens=[], t_tokens=[])]
        if with_beam:
            reqs.append(ServeRequest(
                rid=0, prompt=rng.integers(0, v, size=4), max_new=3,
                profile=profiles.BeamProfile(n_beams=3, eos_id=2),
            ))
        reqs.append(dataclasses.replace(stoch_a, tokens=[], t_tokens=[]))
        reqs.append(dataclasses.replace(stoch_b, tokens=[], t_tokens=[]))
        sched = Scheduler(model, params, slots=4, pad_to=PAD_TO,
                          max_new_cap=8, base_key=jax.random.PRNGKey(4))
        done = sched.run(reqs)
        return {d.rid: list(d.tokens) for d in done if d.rid in (5, 6)}

    assert run(True) == run(False), \
        "stale group stream index leaked into single-stream sampling keys"


# --------------------------------------- block sharing / CoW invariants
class _FakeConfig:
    sliding_window = None
    scan_layers = False
    encdec = None


class _FakeModel:
    config = _FakeConfig()

    def init_cache(self, batch, max_len):
        shape = (batch, max_len, 1, 2)
        return {
            "lengths": jnp.zeros((batch,), jnp.int32),
            "layers": [{"k": jnp.zeros(shape, jnp.float32),
                        "v": jnp.zeros(shape, jnp.float32)}],
        }


def _check_refs(pool: BlockPool):
    """Refcount == number of owning slots; free-list == refcount-0 blocks;
    sink block 0 never owned."""
    counts = np.zeros((pool.num_blocks,), np.int32)
    for s in range(pool.slots):
        for b in pool.owned_blocks(s):
            assert b != 0, "sink block handed out"
            counts[b] += 1
    np.testing.assert_array_equal(counts, np.asarray(pool._ref))
    assert sorted(pool._free_blocks) == [
        b for b in range(1, pool.num_blocks) if counts[b] == 0
    ], "free-list must hold exactly the unreferenced blocks"


def test_block_share_permute_cow_against_dense_mirror():
    """The beam-group block machinery, end to end against a host mirror:
    assign -> share x2 (common-prefix, zero copies) -> per-step
    [ensure_writable (CoW) -> write at kv_len -> random intra-group
    permutation], with every step checking (a) each slot's gathered
    logical view equals the mirror and (b) refcount/free-list
    conservation. This is the correctness core of paged beam reorder."""
    slots, max_len, bs, nb = 3, 12, 4, 16
    pool = BlockPool(_FakeModel(), slots, max_len, block_size=bs,
                     num_blocks=nb)
    rng = np.random.default_rng(0)
    n_prompt = 5
    row_k = rng.normal(size=(1, max_len, 1, 2)).astype(np.float32)
    row = {"lengths": jnp.asarray([n_prompt], jnp.int32),
           "layers": [{"k": jnp.asarray(row_k), "v": jnp.asarray(row_k)}]}
    s0 = pool.acquire()
    pool.assign(s0, row, n_prompt)
    s1, s2 = pool.acquire(), pool.acquire()
    pool.share(s1, s0)
    pool.share(s2, s0)
    group = [s0, s1, s2]
    # sharing is copy-free: 3 streams, still only the prompt's blocks used
    assert pool.n_used_blocks == pool.blocks_for(n_prompt)
    _check_refs(pool)

    mirror = np.zeros((slots, max_len + bs, 1, 2), np.float32)
    for s in group:
        mirror[s, :n_prompt] = row_k[0, :n_prompt]
    kv_len = n_prompt
    for step in range(6):
        for s in group:
            assert pool.ensure_writable(s, kv_len)
        _check_refs(pool)
        new = rng.normal(size=(slots, 1, 2)).astype(np.float32)
        pool.sync()
        layer = pool.cache["layers"][0]
        lengths = jnp.full((slots,), kv_len, jnp.int32)
        pool.cache["layers"][0] = {
            "k": A.paged_write_token(layer["k"], jnp.asarray(new),
                                     pool.cache["block_tables"], lengths),
            "v": layer["v"],
        }
        mirror[group, kv_len] = new[group]
        kv_len += 1
        perm = rng.integers(0, len(group), size=len(group))
        pool.permute_group(group, perm)
        mirror[group] = mirror[[group[p] for p in perm]]
        _check_refs(pool)
        pool.sync()
        gathered = np.asarray(
            A.paged_gather(pool.cache["layers"][0]["k"],
                           pool.cache["block_tables"])
        )
        for s in group:
            np.testing.assert_array_equal(
                gathered[s, :kv_len], mirror[s, :kv_len],
                err_msg=f"slot {s} logical view diverged at step {step}",
            )
    for s in group:
        pool.evict(s)
    assert sorted(pool._free_blocks) == list(range(1, nb))
    assert (np.asarray(pool._ref)[1:] == 0).all()


# ------------------------------------ lane banks == the per-group path
def _eager_group_path(sched):
    """The per-group path the lane banks replaced, as the reference: each
    group keeps its own G = 1 state (sized by its request's ``max_new``),
    and every step gathers its logits rows, runs ``profile.step`` eagerly
    under ``fold_in(request_key(base, rid), n_generated)``, and reads the
    result back in a get of its own. Lanes are still taken and returned
    (preemption releases them) but never read."""
    states = {}  # id(group) -> (group, its G = 1 state)
    seen = {}

    def commit_one(g, out, now):
        feed, perm, fin = jax.device_get((out.feed, out.perm, out.done))
        if perm is not None:
            sched._apply_group_perm(g, perm)
        g.n_generated += 1
        g.req.t_tokens.append(now)
        for i, s in enumerate(g.slots):
            sched._token[s] = int(feed[i])
            sched._ngen[s] = g.n_generated
        if not ((fin is not None and bool(fin.all()))
                or g.n_generated >= g.req.max_new):
            states[id(g)] = (g, out.state)
            return False
        res = jax.device_get(g.profile.finalize(out.state))
        g.req.tokens = [int(t) for t in res["tokens"][0][: g.n_generated]]
        if "scores" in res:
            g.req.score = float(res["scores"][0])
        g.req.t_done = now
        sched.finished.append(g.req)
        sched.groups.pop(g.slots[0])
        if g.lane >= 0:
            g.bank.release(g)
        for s in g.slots:
            sched.pool.evict(s)
        return True

    def step_of(g, st, rows, now):
        key = jax.random.fold_in(
            sampling.request_key(sched.base_key, g.req.rid), g.n_generated)
        return commit_one(g, g.profile.step(st, rows, key), now)

    def advance(g, rows, now):
        if step_of(g, g.profile.init(1, g.req.max_new), rows, now):
            return True
        g.bank.admit(g, g.profile.init(1, sched.max_new_cap))  # lane only
        return False

    def launch(logits):
        seen["logits"] = logits
        return []

    def commit(outs, now):
        done = []
        for g in list(sched.groups.values()):
            _, st = states.pop(id(g))
            g.kv_len += 1
            rows = seen["logits"][jnp.asarray(g.slots)]
            if step_of(g, st, rows, now):
                done.append(g.req)
        return done

    sched._advance_group = advance
    sched._launch_groups, sched._commit_lanes = launch, commit


def _drive(sched, reqs, admit_at):
    """Hand request ``i`` over at step ``admit_at[i]``; serve to the end.
    Returns {rid: (tokens, score)} and the live lanes of every step."""
    todo = sorted(zip(admit_at, range(len(reqs))))
    n, lanes = 0, []
    while todo or sched.waiting or sched.has_work:
        while todo and todo[0][0] <= n:
            sched.submit([reqs[todo.pop(0)[1]]])
        sched._admit(sched._now())
        if sched.has_work:
            lanes.append(sorted((g.lane, g.n_generated, g.req.rid)
                                for g in sched.groups.values()))
            sched.step()
        n += 1
    return {r.rid: (list(r.tokens), r.score) for r in sched.finished}, lanes


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
@pytest.mark.parametrize("kind", ["cfg_greedy", "cfg_top_p", "beam"])
def test_lane_bank_serves_what_the_per_group_step_serves(llama, kind, paged):
    """Groups admitted at different steps (so lanes carry different token
    indices), a dead lane, a lane reused after a finish, a plain request
    beside them and, on the paged pool, a preemption replay: the lane
    banks serve token for token (and beam score for score) what the
    per-group eager step serves on the same logits."""
    model, params = llama
    v = model.config.vocab_size
    rng = np.random.default_rng(21)
    prof = {
        "cfg_greedy": profiles.ContrastiveProfile(uncond_token=0,
                                                  guidance=2.5),
        # the smoke model's logit gaps are ~100 nats: only a flat
        # distribution makes the per-lane keys decide the tokens
        "cfg_top_p": profiles.ContrastiveProfile(
            uncond_token=0, guidance=1.5, temperature=50.0, top_p=0.9),
        "beam": profiles.BeamProfile(n_beams=2, eos_id=2),
    }[kind]
    lens, max_new = [6, 5, 7, 8, 5, 4], [10, 3, 8, 6, 9, 5]
    prompts = [rng.integers(1, v, size=n) for n in lens]
    admit_at = [0, 1, 2, 4, 5, 3]

    def reqs():
        return [
            ServeRequest(rid=i, prompt=prompts[i], max_new=max_new[i],
                         # each request its own (equal) profile object
                         profile=None if i == 5 else dataclasses.replace(prof))
            for i in range(6)
        ]

    def serve(reference):
        # max_len 19, block 4: max_blocks 5, a group needs <= 10 of the
        # 12 usable blocks, so concurrent groups preempt each other
        sched = Scheduler(model, params, slots=6, pad_to=PAD_TO,
                          max_new_cap=10, paged=paged, block_size=4,
                          num_blocks=13 if paged else None,
                          base_key=jax.random.PRNGKey(7))
        if reference:
            _eager_group_path(sched)
        return sched, *_drive(sched, reqs(), admit_at)

    ref_sched, want, _ = serve(reference=True)
    sched, got, lanes = serve(reference=False)
    assert len(got) == 6 and got == want
    assert ref_sched.n_group_batches == 0
    assert sched.n_group_batches == sum(1 for ls in lanes if ls) > 0
    assert len(sched._banks) == 1  # equal profiles share one bank
    assert sched.n_preemptions == ref_sched.n_preemptions
    if paged:
        assert sched.n_preemptions >= 1
    # lanes at different token indices in one program, a dead lane, and a
    # lane that served two requests
    assert any(len({n for _, n, _ in ls}) > 1 for ls in lanes)
    assert min(sched.group_lane_trace) < sched.slots // 2
    rids = {}
    for ls in lanes:
        for lane, _, rid in ls:
            rids.setdefault(lane, set()).add(rid)
    assert max(len(r) for r in rids.values()) > 1, "no lane was reused"


def test_equal_profiles_compile_one_bank_program(llama):
    """A second scheduler of the same geometry, serving requests with
    equal but distinct profile objects (the benchmark's warm-up scheduler
    and its window's), adds no entry to the bank programs' jit caches."""
    model, params = llama
    rng = np.random.default_rng(5)
    v = model.config.vocab_size

    def serve():
        sched = Scheduler(model, params, slots=4, pad_to=PAD_TO,
                          max_new_cap=6, paged=True, block_size=4)
        sched.run([
            ServeRequest(rid=i, prompt=rng.integers(1, v, size=5),
                         max_new=3 + i,
                         profile=profiles.ContrastiveProfile(
                             uncond_token=0, guidance=2.0))
            for i in range(3)
        ])
        assert len(sched._banks) == 1 and sched.n_group_batches > 0

    serve()
    progs = (profiles.lane_step, profiles.put_lane, profiles.lane_finalize)
    sizes = [p._cache_size() for p in progs]
    serve()
    assert [p._cache_size() for p in progs] == sizes


def test_plain_sampling_dispatches_no_bank_program(llama):
    """Traffic without groups never reaches the lane banks: no program,
    no ``sched.groups`` span, one ``*.sync`` a step."""
    model, params = llama
    rng = np.random.default_rng(6)
    v = model.config.vocab_size
    sched = Scheduler(model, params, slots=3, pad_to=PAD_TO, max_new_cap=6,
                      paged=True, chunked=True, block_size=4,
                      prefill_budget=8)
    sched.run([ServeRequest(rid=i, prompt=rng.integers(1, v, size=5),
                            max_new=6, temperature=0.7 * i)
               for i in range(4)])
    t = sched.spans.totals()
    assert sched.n_group_batches == 0 and not sched.group_lane_trace
    assert "sched.groups" not in t and not sched._banks
    syncs = sum(c["count"] for n, c in t.items() if n.endswith(".sync"))
    assert syncs == t["sched.finish"]["count"] == sched.n_decode_steps
