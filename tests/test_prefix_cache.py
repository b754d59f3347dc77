"""Cross-request radix prefix cache tests (core/prefix_cache.py).

Two layers:

- **Trie unit layer** (fake refcounting pool): the match cap that always
  leaves >= 1 suffix token, LRU stamping, insert dedup + refcount
  handoff, the preemption-replay self-collision no-op, leaf-first LRU
  reclaim with root-path termination, and reset.
- **End-to-end layer** (smoke llama): warm serving over shared-prefix
  traffic is TOKEN-IDENTICAL to a cache-less scheduler at temperature 0
  AND 0.8 (sampling keys are per-(rid, stream, token-index), never
  per-batch-shape), skips the shared blocks' prefill tokens, reclaims
  cached blocks under block pressure instead of failing admission,
  survives preemption replay, and allocates ZERO new device KV bytes.
"""
import collections

import jax
import numpy as np
import pytest

from repro.configs import SMOKE_CONFIGS
from repro.core.prefix_cache import PrefixCache
from repro.core.scheduler import Scheduler, ServeRequest
from repro.models import get_model

KEY = jax.random.PRNGKey(0)
BS = 4  # trie/pool block size used throughout


@pytest.fixture(scope="module")
def llama():
    cfg = SMOKE_CONFIGS["llama3.2-1b"].replace(dtype="float32")
    model = get_model(cfg)
    return model, model.init(KEY)


class _FakePool:
    """Refcount-only stand-in for BlockPool: enough surface for the trie
    (cache_ref / cache_unref / is_sole_cached_ref), with ``extra`` slots
    emulating block references held by adopting schedulers."""

    def __init__(self):
        self.ref = collections.Counter()

    def cache_ref(self, phys):
        self.ref[phys] += 1

    def cache_unref(self, phys):
        self.ref[phys] -= 1
        assert self.ref[phys] >= 0

    def is_sole_cached_ref(self, phys):
        return self.ref[phys] == 1


def _prompt(*tokens):
    return np.asarray(tokens, np.int32)


# ------------------------------------------------------------ trie units
def test_match_always_leaves_a_suffix_token():
    cache, pool = PrefixCache(BS), _FakePool()
    p8 = _prompt(*range(8))
    assert cache.insert(p8, [10, 11], pool) == 2
    # exact-length prompt: cap (8-1)//4 = 1 — the final block is cached
    # but NOT matched, so its last position is freshly prefetched
    assert cache.match(p8) == [10]
    # one extra token: both full blocks now matchable
    assert cache.match(_prompt(*range(8), 99)) == [10, 11]
    # prompt of exactly one block: nothing to match (cap 0)
    assert cache.match(_prompt(*range(4))) == []
    # divergence inside the second span stops the walk after span 0
    assert cache.match(_prompt(0, 1, 2, 3, 7, 7, 7, 7, 9)) == [10]


def test_insert_dedup_keeps_incumbent_block():
    cache, pool = PrefixCache(BS), _FakePool()
    p = _prompt(*range(8), 1)
    assert cache.insert(p, [10, 11], pool) == 2
    # a twin finishing later (or a preemption replay re-inserting the
    # very blocks it adopted) must be a no-op: the incumbent block stays,
    # the duplicate gains no cache reference
    assert cache.insert(p, [20, 21], pool) == 0
    assert cache.match(p) == [10, 11]
    assert pool.ref[10] == pool.ref[11] == 1
    assert pool.ref[20] == pool.ref[21] == 0
    assert len(cache) == 2
    # partial overlap: only the diverging span is fresh
    q = _prompt(0, 1, 2, 3, 8, 8, 8, 8, 1)
    assert cache.insert(q, [10, 30], pool) == 1
    assert len(cache) == 3 and pool.ref[30] == 1


def test_reclaim_is_lru_and_leaf_first():
    cache, pool = PrefixCache(BS), _FakePool()
    chain = _prompt(*range(8), 1)     # nodes A(1) -> B(2)
    lone = _prompt(*range(50, 54), 1)  # node C(3)
    cache.insert(chain, [1, 2], pool)
    cache.insert(lone, [3], pool)
    cache.match(chain)  # chain is now most recently used
    assert cache.reclaim(pool, 1) == 1
    assert pool.ref[3] == 0 and cache.match(lone) == []   # C went first
    # the chain drains leaf-first: B frees, exposing A as the next leaf
    assert cache.reclaim(pool, 2) == 2
    assert pool.ref[1] == pool.ref[2] == 0 and len(cache) == 0


def test_reclaim_skips_slot_referenced_blocks():
    cache, pool = PrefixCache(BS), _FakePool()
    cache.insert(_prompt(*range(8), 1), [1, 2], pool)
    pool.ref[2] += 1  # a slot adopted the leaf (root path => A pinned too)
    assert cache.reclaim(pool, 5) == 0  # nothing reclaimable; terminates
    assert len(cache) == 2
    pool.ref[2] -= 1  # slot evicted; cache is sole holder again
    assert cache.reclaim(pool, 5) == 2
    assert len(cache) == 0 and cache.n_reclaimed_blocks == 2


def test_reset_releases_every_cached_block():
    cache, pool = PrefixCache(BS), _FakePool()
    cache.insert(_prompt(*range(12), 1), [1, 2, 3], pool)
    cache.insert(_prompt(*range(40, 44), 1), [4], pool)
    assert len(cache) == 4
    cache.reset(pool)
    assert len(cache) == 0
    assert all(v == 0 for v in pool.ref.values())


# ------------------------------------------------------------ end-to-end
def _sched(model, params, *, prefix_cache, num_blocks, pad_to, slots=2):
    return Scheduler(
        model, params, slots=slots, pad_to=pad_to, max_new_cap=6,
        paged=True, block_size=BS, num_blocks=num_blocks,
        chunked=True, prefill_budget=8, prefix_cache=prefix_cache,
    )


def _shared_trace(vocab, *, pad_to, n=6, seed=3, temperature=0.0):
    """n requests sharing a 2-block prefix, distinct suffixes."""
    r = np.random.default_rng(seed)
    shared = r.integers(0, vocab, size=2 * BS)
    reqs = []
    for i in range(n):
        suffix = r.integers(0, vocab, size=pad_to - 2 * BS)
        reqs.append(ServeRequest(
            rid=i, prompt=np.concatenate([shared, suffix]),
            max_new=int(r.integers(2, 7)), temperature=temperature,
            top_p=0.9 if temperature else 1.0,
        ))
    return reqs


def _tokens(done):
    return {d.rid: list(d.tokens) for d in done}


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_warm_hits_are_token_identical_to_cold(llama, temperature):
    model, params = llama
    vocab, pad_to = model.config.vocab_size, 12

    cold = _sched(model, params, prefix_cache=False, num_blocks=24,
                  pad_to=pad_to)
    ref = _tokens(cold.run(_shared_trace(vocab, pad_to=pad_to,
                                         temperature=temperature)))

    warm = _sched(model, params, prefix_cache=True, num_blocks=24,
                  pad_to=pad_to)
    reserved = warm.pool.reserved_bytes
    # pass 1 populates the trie; pass 2 serves the same rids fully warm
    warm.run(_shared_trace(vocab, pad_to=pad_to, temperature=temperature))
    got = _tokens(warm.run(_shared_trace(vocab, pad_to=pad_to,
                                         temperature=temperature)))
    assert got == ref, "cache hits must be bit-identical to cold prefill"
    # pass 2: every request matches the shared 2-block prefix
    assert warm.n_prefix_hits >= 6
    assert warm.n_prefix_tokens_skipped >= 6 * 2 * BS
    # the trie is host state: zero new device KV bytes
    assert warm.pool.reserved_bytes == reserved == cold.pool.reserved_bytes


def _system_prompt_trace(vocab, temperature):
    """20 requests over 2 system prompts of 4 full blocks each, reused
    Zipf-style, each with a fresh 1..8-token suffix, all at t=0."""
    from repro.launch import serve

    return serve.shared_prefix_trace(
        20, n_prefixes=2, prefix_len=4 * BS, pad_to=24, max_new_cap=8,
        vocab_size=vocab, arrival_rate=0.0, seed=0, temperature=temperature,
        top_p=0.9 if temperature else 1.0,
    )


def _system_prompt_sched(model, params, prefix_cache):
    return Scheduler(
        model, params, slots=4, pad_to=24, max_new_cap=8, paged=True,
        block_size=BS, num_blocks=48, chunked=True, prefill_budget=8,
        prefix_cache=prefix_cache,
    )


def test_system_prompt_traffic_serves_most_prompt_tokens_from_cache(llama):
    """On shared-system-prompt traffic served once, at least half of all
    prompt tokens come out of cached blocks instead of prefill, with the
    cold scheduler's tokens and reserved bytes."""
    model, params = llama
    vocab = model.config.vocab_size
    cold = _system_prompt_sched(model, params, prefix_cache=False)
    ref = _tokens(cold.run(_system_prompt_trace(vocab, 0.0)))
    warm = _system_prompt_sched(model, params, prefix_cache=True)
    reqs = _system_prompt_trace(vocab, 0.0)
    total = sum(len(r.prompt) for r in reqs)
    got = _tokens(warm.run(reqs))
    assert got == ref and len(got) == 20
    assert warm.n_prefix_tokens_skipped >= 0.5 * total, (
        warm.n_prefix_tokens_skipped, total)
    assert warm.pool.reserved_bytes == cold.pool.reserved_bytes


def test_cache_hits_compile_nothing_new(llama):
    """Adopting cached blocks reuses the executables cold serving compiled:
    warm runs after cold ones, at temperature 0 and 0.8, add nothing to
    any serving jit cache."""
    from repro.analysis import trace_audit

    model, params = llama
    vocab = model.config.vocab_size
    for temperature in (0.0, 0.8):
        _system_prompt_sched(model, params, prefix_cache=False).run(
            _system_prompt_trace(vocab, temperature))
    jits = trace_audit.serving_jits()
    before = trace_audit._cache_sizes(jits)
    for temperature in (0.0, 0.8):
        warm = _system_prompt_sched(model, params, prefix_cache=True)
        warm.run(_system_prompt_trace(vocab, temperature))
        assert warm.n_prefix_hits > 0
    grew = {name: (before[name], n)
            for name, n in trace_audit._cache_sizes(jits).items()
            if n != before[name]}
    assert not grew, f"cache hits compiled new executables: {grew}"


def test_preemption_replay_stays_identical_under_tight_blocks(llama):
    """Tight pool: decode growth forces preemption, and preempted warm
    requests replay through a trie that may hold their OWN pre-preemption
    blocks (refcount self-collision). Tokens must still match the
    cache-less arm exactly."""
    model, params = llama
    vocab, pad_to, nb = model.config.vocab_size, 16, 10

    def trace():
        return _shared_trace(vocab, pad_to=pad_to, n=6, seed=5,
                             temperature=0.8)

    cold = _sched(model, params, prefix_cache=False, num_blocks=nb,
                  pad_to=pad_to)
    ref = _tokens(cold.run(trace()))

    warm = _sched(model, params, prefix_cache=True, num_blocks=nb,
                  pad_to=pad_to)
    warm.run(trace())
    got = _tokens(warm.run(trace()))
    assert got == ref
    assert warm.n_preemptions > 0, "geometry should force preemption"


def test_reclaim_relieves_block_pressure(llama):
    """Distinct (unshareable) prompts fill the trie with dead cached
    blocks; later admissions must reclaim them LRU instead of starving
    or preempting. Every request still completes."""
    model, params = llama
    vocab, pad_to, nb = model.config.vocab_size, 12, 14
    r = np.random.default_rng(9)

    def batch(rids):
        return [ServeRequest(rid=i,
                             prompt=r.integers(0, vocab, size=pad_to),
                             max_new=3)
                for i in rids]

    sched = _sched(model, params, prefix_cache=True, num_blocks=nb,
                   pad_to=pad_to)
    done = sched.run(batch(range(4)))
    assert len(done) == 4
    assert sched.pool.n_reclaimable_blocks > 0  # dead cached blocks
    done = [d for d in sched.run(batch(range(4, 8))) if d.rid >= 4]
    assert len(done) == 4 and all(len(d.tokens) == 3 for d in done)
    assert sched.n_prefix_reclaimed > 0
    # conservation: every block is free, owned, or cached — exactly once
    pool = sched.pool
    assert pool.n_cached_blocks == len(sched._pcache)
