"""The paged decode walk (``attention.paged_decode_attention``): a tiled
online-softmax loop whose trip count is the deepest live slot's. Held to a
plain float32 softmax over each slot's gathered view, and the scheduler's
``walk_trace`` to the rule the step applies on the device.

The walk runs here at ``TILE_KEYS`` = 16 (at most 4 blocks of 4 a tile),
so that tables a few blocks wide span several tiles; a model config of
this file's own keeps those executables apart from every other test's."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_CONFIGS
from repro.core.scheduler import Scheduler, ServeRequest
from repro.models import attention as A
from repro.models import get_model

BS = 4  # tokens a block
TILE = 16  # most keys a tile: G = 4 blocks
G = TILE // BS
HQ, HKV, D = 4, 2, 8
LAT, V_DIM = 12, 8  # MLA latent width and its V slice


@pytest.fixture
def tile(monkeypatch):
    monkeypatch.setattr(A, "TILE_KEYS", TILE)


def _tile_blocks(mb):
    """The fewest tiles of at most G blocks that hold the table, evened."""
    n = -(-mb // G)
    return -(-mb // n)


def _tables(rng, mb, n_valid, free):
    """A block table [B, mb] whose live rows own distinct physical blocks
    for their ``n_valid`` keys (zero past them, as the pool leaves a row)
    and whose ``free`` rows are all zero, and the pool's block count."""
    b = len(n_valid)
    need = [0 if i in free else -(-n // BS) for i, n in enumerate(n_valid)]
    nb = sum(need) + 1
    phys = rng.permutation(np.arange(1, nb))
    bt = np.zeros((b, mb), np.int32)
    at = 0
    for i, k in enumerate(need):
        bt[i, :k] = phys[at:at + k]
        at += k
    return bt, nb


def _reference(q, kbuf, vbuf, bt, n_valid, scale, v_dim):
    """Softmax over each slot's whole gathered view, in float32."""
    hi = jax.lax.Precision.HIGHEST
    b, mb = bt.shape
    k = kbuf.astype(jnp.float32)[bt].reshape((b, mb * BS) + kbuf.shape[2:])
    if vbuf is None:
        v = k[..., :v_dim]
    else:
        v = vbuf.astype(jnp.float32)[bt].reshape((b, mb * BS) + vbuf.shape[2:])
    if k.ndim == 3:
        k, v = k[:, :, None], v[:, :, None]
    rep = q.shape[1] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), k,
                   precision=hi) * scale
    valid = jnp.arange(mb * BS)[None, :] < n_valid[:, None]
    p = jax.nn.softmax(jnp.where(valid[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p, v, precision=hi)


# (table width in blocks, each row's valid keys, free rows, latent form)
CASES = {
    "width_1": (1, [1, 4, 3], (), False),
    "width_G-1": (G - 1, [12, 5, 1], (), False),
    "width_G": (G, [16, 15, 1], (), False),
    "width_G+1": (G + 1, [20, 17, 16, 2], (), False),
    "width_66_tile_borders": (66, [TILE - 1, TILE, TILE + 1, 2 * TILE - 1,
                                   2 * TILE, 2 * TILE + 1, 66 * BS], (), False),
    "deep_and_shallow": (66, [1, 66 * BS - 1, 40, 3], (), False),
    "freed_slot_drifted_past_live": (66, [20, 9, 250], (2,), False),
    "latent": (10, [7, 40, 33, 200], (3,), True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_walk_matches_softmax_over_the_gathered_view(tile, monkeypatch, case):
    """Every live row equals the softmax over its gathered view; a free
    row is masked whole (its output is zero); the trip count covers the deepest LIVE row
    (a freed row's drifted counter does not count); and one jitted
    executable serves depth 1 and the case's depth alike."""
    mb, n_valid, free, latent = CASES[case]
    rng = np.random.default_rng(len(case))
    b = len(n_valid)
    bt, nb = _tables(rng, mb, n_valid, free)
    keys = jax.random.split(jax.random.PRNGKey(mb), 3)
    q = jax.random.normal(keys[0], (b, HQ, LAT if latent else D), jnp.float32)
    if latent:
        kbuf = jax.random.normal(keys[1], (nb, BS, LAT), jnp.bfloat16)
        vbuf, v_dim, scale = None, V_DIM, 0.3
    else:
        kbuf = jax.random.normal(keys[1], (nb, BS, HKV, D), jnp.bfloat16)
        vbuf = jax.random.normal(keys[2], (nb, BS, HKV, D), jnp.bfloat16)
        v_dim, scale = None, D ** -0.5
    bt, n = jnp.asarray(bt), jnp.asarray(n_valid, jnp.int32)

    walked = []
    real = A.walk_tiles
    monkeypatch.setattr(A, "walk_tiles",
                        lambda *a: walked.append(int(real(*a))) or real(*a))
    got = A.paged_decode_attention(q, kbuf, vbuf, bt, n, scale=scale,
                                   v_dim=v_dim)
    monkeypatch.setattr(A, "walk_tiles", real)
    deepest = max(-(-x // BS) for i, x in enumerate(n_valid) if i not in free)
    assert walked == [-(-deepest // _tile_blocks(mb))]

    want = _reference(q, kbuf, vbuf, bt, n, scale, v_dim)
    live = np.array([i not in free for i in range(b)])
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got)[~live], 0.0)

    @jax.jit  # a function of this case's own: a cache of its own
    def step(n_valid):
        return A.paged_decode_attention(q, kbuf, vbuf, bt, n_valid,
                                        scale=scale, v_dim=v_dim)

    shallow, deep = step(jnp.ones_like(n)), step(n)
    assert step._cache_size() == 1
    np.testing.assert_allclose(np.asarray(deep), np.asarray(got),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(shallow)[live],
        np.asarray(_reference(q, kbuf, vbuf, bt, jnp.ones_like(n), scale,
                              v_dim))[live], rtol=1e-5, atol=1e-5)


# ---- the scheduler's host mirror of the trip count ------------------------------

@pytest.fixture(scope="module")
def llama():
    cfg = SMOKE_CONFIGS["llama3.2-1b"].replace(dtype="float32",
                                               name="paged-walk-test")
    model = get_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _device_rule(lengths, bt):
    """(the step's tiles over live rows, the tiles a bound over every row
    would give) from the counters and table a step launches with."""
    lengths, bt = np.asarray(lengths), np.asarray(bt)
    n = np.minimum(lengths + 1, bt.shape[1] * BS)
    every, mb = np.ones(len(n), bool), bt.shape[1]
    return (int(A.walk_tiles(n, bt[:, 0] != 0, BS, mb)),
            int(A.walk_tiles(n, every, BS, mb)))


@pytest.mark.parametrize("chunked", [False, True])
def test_walk_trace_is_the_device_rule(tile, llama, chunked):
    """``Scheduler.walk_trace`` gives, per decode or mixed step, the tiles
    the step's walk runs by the device's rule on the counters and table it
    launches with, and the tiles of the whole table. The first request
    finishes and is evicted while free rows drift; the second lands in the
    freed slot, shallower than the drift (dense admission), or through
    mixed steps that pin every counter (chunked)."""
    model, params = llama
    rng = np.random.default_rng(11)
    vocab = model.config.vocab_size
    first = ServeRequest(rid=0, prompt=rng.integers(0, vocab, 2), max_new=12)
    second = ServeRequest(rid=1, prompt=rng.integers(0, vocab, 7), max_new=16)
    sched = Scheduler(model, params, slots=3, pad_to=8, max_new_cap=16,
                      paged=True, block_size=BS, chunked=chunked,
                      prefill_budget=4)
    if not chunked:
        second = dataclasses.replace(second, prompt=second.prompt[:2])
    steps = sched._steps
    decode, mixed = steps.decode_step, steps.mixed_step
    device = []

    def decode_spy(model, params, cache, token):
        device.append(_device_rule(cache["lengths"], cache["block_tables"]))
        return decode(model, params, cache, token)

    def mixed_spy(model, params, cache, tokens, t_new, lengths, pad_to):
        device.append(_device_rule(lengths, cache["block_tables"]))
        return mixed(model, params, cache, tokens, t_new, lengths, pad_to)

    steps.decode_step, steps.mixed_step = decode_spy, mixed_spy
    sched.submit([first])
    sched._t0 = sched.clock()
    for req in (first, second):
        if req is second:
            sched.submit([second])
        sched._admit(sched._now())
        while sched.active or len(sched.chunk_mgr or ()):
            sched.step()
    assert len(sched.finished) == 2
    assert len(sched.walk_trace) == sched.n_decode_steps == len(device)
    assert [w for w, _ in sched.walk_trace] == [live for live, _ in device]
    mb = sched.pool.max_blocks
    assert {t for _, t in sched.walk_trace} == {-(-mb // _tile_blocks(mb))}
    assert max(w for w, _ in sched.walk_trace) > 1  # the bound moves
    if chunked:
        assert sched.n_mixed_steps >= 2
    else:  # a bound over every row would follow the drifted free rows
        assert any(every > live for live, every in device)
