"""Cross-replica determinism suite for core/router.py.

The acceptance property mirrors tests/test_serve.py's, one level up: a
ReplicaRouter (N data-parallel Scheduler pools behind one shared queue)
produces TOKEN-IDENTICAL outputs to a single pool — at any replica
count, over both pool kinds (contiguous slots and paged blocks), at
temperature 0 AND under temperature/top-p sampling, and across
mid-decode preemption replays that land on a different replica than the
original admission. Routing is a pure scheduling decision because every
committed token is sampled under a per-(rid, stream, token-index) key
folded from the router-wide shared base_key.

The property test drives the router loop by hand (_place / step_begin /
step_finish / _reclaim / _harvest) over randomized traces and asserts
the accounting invariants: no request lost or double-served, free
slot/block conservation per replica against a dense mirror, and fully
freed pools at drain.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from _hyp import given, settings, hst

from repro.configs import SMOKE_CONFIGS
from repro.core.router import ReplicaRouter
from repro.core.scheduler import Scheduler, ServeRequest
from repro.distributed import sharding
from repro.launch import serve
from repro.models import get_model

KEY = jax.random.PRNGKey(0)
PAD_TO = 8
MAX_NEW_CAP = 16
SLOTS = 2
BLOCK_SIZE = 4
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def llama():
    cfg = SMOKE_CONFIGS["llama3.2-1b"].replace(dtype="float32")
    model = get_model(cfg)
    return model, model.init(KEY)


def _requests(cfg, n, seed=0, temperature=0.0, top_p=1.0, max_new=None,
              arrival_rate=0.0):
    rng = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for i in range(n):
        if arrival_rate > 0:
            t += rng.exponential(1.0 / arrival_rate)
        reqs.append(ServeRequest(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(3, PAD_TO + 1))),
            max_new=max_new or int(rng.integers(4, MAX_NEW_CAP + 1)),
            t_arrival=t if arrival_rate > 0 else 0.0,
            temperature=temperature,
            top_p=top_p,
        ))
    return reqs


def _pool_kwargs(paged, num_blocks=None):
    if not paged:
        return dict(paged=False)
    return dict(paged=True, block_size=BLOCK_SIZE,
                num_blocks=num_blocks or 16)


def _single_tokens(model, params, reqs, *, seed=0, **pool):
    sched = Scheduler(
        model, params, slots=SLOTS, pad_to=PAD_TO, max_new_cap=MAX_NEW_CAP,
        base_key=jax.random.PRNGKey(seed), **pool,
    )
    done = sched.run(reqs)
    return {r.rid: list(r.tokens) for r in done}


def _router_tokens(model, params, reqs, *, n_replicas, seed=0, **pool):
    router = ReplicaRouter(
        model, params, replicas=n_replicas, slots=SLOTS, pad_to=PAD_TO,
        max_new_cap=MAX_NEW_CAP, base_key=jax.random.PRNGKey(seed),
        devices=[None] * n_replicas, **pool,
    )
    done = router.run(reqs)
    return router, {r.rid: list(r.tokens) for r in done}


# ------------------------------------------------- token identity
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("n_replicas", [1, 2, 3])
def test_router_greedy_identical_to_single_pool(llama, paged, n_replicas):
    """Greedy tokens must not depend on replica count or pool kind: the
    router is a scheduling layer, not a numerics change."""
    model, params = llama
    cfg = model.config
    want = _single_tokens(model, params, _requests(cfg, 6),
                          **_pool_kwargs(paged))
    router, got = _router_tokens(model, params, _requests(cfg, 6),
                                 n_replicas=n_replicas, **_pool_kwargs(paged))
    assert got == want
    assert router.n_routed >= 6
    assert sorted(router.placements) == list(range(6))


@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("temperature,top_p", [(0.8, 0.9), (20.0, 1.0)])
def test_router_sampled_identical_to_single_pool(llama, paged, temperature,
                                                 top_p):
    """Sampled decoding: per-(rid, stream, token-index) keys make tokens
    placement-independent. The (20.0, 1.0) arm is genuinely stochastic on
    the untrained smoke model (see the negative control below), so the
    identity there is the real cross-replica key invariant at work."""
    model, params = llama
    cfg = model.config
    reqs = lambda: _requests(cfg, 6, seed=3, temperature=temperature,
                             top_p=top_p)
    want = _single_tokens(model, params, reqs(), seed=7,
                          **_pool_kwargs(paged))
    _, got = _router_tokens(model, params, reqs(), n_replicas=2, seed=7,
                            **_pool_kwargs(paged))
    assert got == want
    assert all(len(t) >= 1 for t in got.values())


def _backlog_steps(model, params, n_replicas):
    """Serve one all-at-t=0 paged backlog; return the router's tokens and
    the decode steps of its busiest replica (the fleet's wall in steps)."""
    router, tokens = _router_tokens(
        model, params, _requests(model.config, 16, seed=5),
        n_replicas=n_replicas, **_pool_kwargs(True))
    return tokens, max(s.n_decode_steps for s in router.replicas)


def test_router_backlog_splits_steps_across_replicas(llama):
    """Fleet scaling in steps: on a backlog, two replicas finish the same
    tokens with the busiest one running at most 1/1.6 of one replica's
    steps."""
    model, params = llama
    tokens_one, steps_one = _backlog_steps(model, params, 1)
    tokens_two, steps_two = _backlog_steps(model, params, 2)
    assert tokens_two == tokens_one and len(tokens_one) == 16
    assert steps_one >= 1.6 * steps_two, (steps_one, steps_two)


def test_router_replays_the_single_pool_executables(llama):
    """Replicas of one geometry reuse the single pool's compiled steps: a
    router run after a single-pool run compiles nothing."""
    from repro.analysis import trace_audit

    model, params = llama
    cfg = model.config
    pool = _pool_kwargs(True)
    for temperature in (0.0, 0.8):
        _single_tokens(model, params, _requests(cfg, 6, seed=2,
                                                temperature=temperature),
                       **pool)
    jits = trace_audit.serving_jits()
    before = trace_audit._cache_sizes(jits)
    for temperature in (0.0, 0.8):
        _router_tokens(model, params, _requests(cfg, 6, seed=4,
                                                temperature=temperature),
                       n_replicas=2, **pool)
    grew = {name: (before[name], n)
            for name, n in trace_audit._cache_sizes(jits).items()
            if n != before[name]}
    assert not grew, f"the router compiled new executables: {grew}"


def test_router_base_key_must_be_shared(llama):
    """Negative control: DIFFERENT base keys change sampled tokens, so
    identity above genuinely exercises the shared-key invariant. The
    untrained smoke model's logits are peaked enough that mild sampling
    settings are near-deterministic (logit gaps of tens of nats) — only
    a very high temperature makes the draw actually key-sensitive."""
    model, params = llama
    cfg = model.config
    reqs = lambda: _requests(cfg, 4, seed=5, temperature=20.0, top_p=1.0)
    _, a = _router_tokens(model, params, reqs(), n_replicas=2, seed=7)
    _, b = _router_tokens(model, params, reqs(), n_replicas=2, seed=8)
    assert a != b


def test_router_preemption_replay_identical(llama):
    """Mid-decode preemption on a tight replica pool: the preempted
    request is requeued at the SHARED queue front and its replay (on
    whichever replica has room) recomputes the same tokens the roomy
    single pool produces — preemption count > 0 proves the path ran."""
    model, params = llama
    cfg = model.config
    reqs = lambda: _requests(cfg, 4, seed=9, temperature=0.8, top_p=0.9,
                             max_new=MAX_NEW_CAP)
    want = _single_tokens(model, params, reqs(), seed=9,
                          **_pool_kwargs(True, num_blocks=16))
    # 8 blocks/replica - sink = 7 usable: two slots decoding to
    # pad_to + max_new = 24 tokens (6 blocks each) MUST collide
    router, got = _router_tokens(model, params, reqs(), n_replicas=2,
                                 seed=9, **_pool_kwargs(True, num_blocks=8))
    assert router.n_preemptions >= 1
    assert router.n_requeues >= 1
    assert got == want
    # replays re-admit: some rid has more than one placement entry
    assert any(len(p) > 1 for p in router.placements.values())


# ------------------------------------------------- load-aware placement
def test_router_load_aware_placement_and_spill(llama):
    """A long-prompt request pins replica 0's blocks; the following
    traffic must route to replica 1 (most-free-capacity first), at least
    one admission must spill past a refusing top choice, and placement
    must never stall while ANY replica could admit the head-of-line
    candidate."""
    model, params = llama
    cfg = model.config
    rng = np.random.default_rng(0)
    # rid 0: an 8-token prompt (2 blocks of 4) + long decode holds rep 0
    reqs = [ServeRequest(rid=0, prompt=rng.integers(0, cfg.vocab_size, 8),
                         max_new=MAX_NEW_CAP)]
    reqs += [ServeRequest(rid=i, prompt=rng.integers(0, cfg.vocab_size, 2),
                          max_new=4) for i in range(1, 6)]
    router = ReplicaRouter(
        model, params, replicas=2, slots=SLOTS, pad_to=PAD_TO,
        max_new_cap=MAX_NEW_CAP, base_key=KEY, devices=[None, None],
        paged=True, block_size=BLOCK_SIZE, num_blocks=10,
    )
    router.submit(reqs)
    routed_round = {}
    rounds = 0
    while router.waiting or any(s.has_work for s in router.replicas):
        now = router._now()  # one read: a request arriving between
        router._place(now)   # _place and the assert is not a stall
        # the no-stall invariant: whoever is still queued must be
        # inadmissible EVERYWHERE (head-of-line blocking only)
        _, cand = router._next_candidate(now)
        if cand is not None:
            assert not any(s.admissible(cand) for s in router.replicas)
        for rid, path in router.placements.items():
            routed_round.setdefault(rid, rounds)
        live = [s for s in router.replicas if s.has_work]
        assert live, "router wedged with work queued"
        router._round(live)
        for s in live:
            if s.waiting:
                router._reclaim(s)
        router._harvest()
        rounds += 1
    assert router.placements[0] == [0]  # the pinning request lands first
    served_by_1 = [rid for rid, p in router.placements.items()
                   if rid != 0 and p[-1] == 1]
    assert len(served_by_1) >= 2  # load signal steered traffic off rep 0
    assert router.n_spills >= 1
    assert len(router.finished) == len(reqs)


# ------------------------------------------------- accounting property
def _dense_mirror(router):
    """Recompute each replica's free slots/blocks from its resident state
    the slow way; the pool's O(1) counters must agree every round."""
    for s in router.replicas:
        resident = len(s.active) + sum(
            len(g.slots) for g in dict.fromkeys(s.groups.values())
        )
        if s.chunk_mgr is not None:
            resident += len(s.chunk_mgr)
        assert s.pool.n_free == s.pool.slots - resident
        if s.paged:
            # CoW-shared blocks appear in several tables but are one
            # physical block; the free heap must mirror exactly the set
            # of blocks no table references (0 is the sink, never owned)
            held = np.unique(s.pool.block_tables)
            held = held[held != 0]
            assert s.pool.n_free_blocks == (
                (s.pool.num_blocks - 1) - len(held)
            )


def _run_property_trace(llama, seed):
    model, params = llama
    cfg = model.config
    rng = np.random.default_rng(seed)
    n = int(rng.integers(3, 8))
    paged = bool(rng.integers(0, 2))
    n_replicas = int(rng.integers(1, 4))
    reqs = _requests(cfg, n, seed=seed, temperature=0.8, top_p=0.9,
                     arrival_rate=float(rng.choice([0.0, 300.0])))
    router = ReplicaRouter(
        model, params, replicas=n_replicas, slots=SLOTS, pad_to=PAD_TO,
        max_new_cap=MAX_NEW_CAP, base_key=jax.random.PRNGKey(seed),
        devices=[None] * n_replicas,
        **_pool_kwargs(paged, num_blocks=int(rng.integers(8, 14))),
    )
    router.submit(reqs)
    guard = 0
    while router.waiting or any(s.has_work for s in router.replicas):
        guard += 1
        assert guard < 2000, "router failed to drain"
        now = router._now()  # one read — see the load-aware test
        router._place(now)
        _dense_mirror(router)
        _, cand = router._next_candidate(now)
        if cand is not None:
            assert not any(s.admissible(cand) for s in router.replicas)
        live = [s for s in router.replicas if s.has_work]
        if not live:
            if router.waiting:  # idle until the next arrival, like run()
                import time
                time.sleep(1e-4)
            continue
        router._round(live)
        for s in live:
            if s.waiting:
                router._reclaim(s)
        router._harvest()
    # exactly-once: every rid served once, none lost, none duplicated
    rids = sorted(r.rid for r in router.finished)
    assert rids == list(range(n))
    assert sorted(router.placements) == list(range(n))
    assert router.n_routed == n + router.n_requeues
    # drained fleet: every slot and every non-sink block back on the
    # free lists
    for s in router.replicas:
        assert s.pool.n_free == s.pool.slots
        if s.paged:
            assert s.pool.n_free_blocks == s.pool.num_blocks - 1


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_router_accounting_fixed_seeds(llama, seed):
    """Fixed-seed fallback of the property test — runs with or without
    hypothesis installed."""
    _run_property_trace(llama, seed)


@settings(max_examples=8)
@given(seed=hst.integers(4, 2 ** 16))
def test_router_accounting_property(llama, seed):
    """Randomized submit/spill/preempt/drain sequences preserve the
    accounting invariants (free-list conservation vs a dense mirror,
    exactly-once service, fully freed pools)."""
    _run_property_trace(llama, seed)


# ------------------------------------------------- seams & metrics
def test_replica_devices_round_robin():
    devs = list("abc")
    assert sharding.replica_devices(5, devs) == ["a", "b", "c", "a", "b"]
    with pytest.raises(ValueError):
        sharding.replica_devices(2, [])
    # real devices: auto pool wraps over jax.devices()
    pins = sharding.replica_devices(3)
    assert len(pins) == 3 and pins[0] is jax.devices()[0]


def test_place_replica_none_is_identity():
    tree = {"w": np.ones((2, 2))}
    assert sharding.place_replica(tree, None) is tree
    placed = sharding.place_replica({"w": jax.numpy.ones((2,))},
                                    jax.devices()[0])
    assert placed["w"].devices() == {jax.devices()[0]}


def test_router_rejects_bad_geometry(llama):
    model, params = llama
    with pytest.raises(ValueError):
        ReplicaRouter(model, params, replicas=0, slots=SLOTS, pad_to=PAD_TO,
                      max_new_cap=MAX_NEW_CAP)
    with pytest.raises(ValueError):
        ReplicaRouter(model, params, replicas=2, slots=SLOTS, pad_to=PAD_TO,
                      max_new_cap=MAX_NEW_CAP, devices=[None])


def test_serve_metrics_per_class_breakdown(llama):
    """serve_metrics now reports per-request-class TTFT/TPOT percentiles;
    run_scheduler with replicas=N merges them plus the fleet fields."""
    model, params = llama
    cfg = model.config
    reqs = _requests(cfg, 4, seed=2)
    for r in reqs[2:]:
        r.temperature, r.top_p = 0.8, 0.9
    m = serve.run_scheduler(
        model, params, reqs, slots=SLOTS, pad_to=PAD_TO,
        max_new_cap=MAX_NEW_CAP, replicas=2, devices=[None, None],
    )
    assert sorted(m["per_class"]) == ["greedy", "sampling"]
    for cls in m["per_class"].values():
        assert cls["n_requests"] == 2
        for k in ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
                  "tpot_p99_ms"):
            assert cls[k] >= 0.0
    assert m["replicas"] == 2
    assert m["steps_max"] <= m["decode_steps"]
    assert m["aggregate_tokens_per_s"] > 0
    assert len(m["per_replica"]) == 2
    for rep in m["per_replica"]:
        assert rep["busy_s"] > 0 or rep["n_requests"] == 0


# --------------------------------------------------------------------------
# warm-up covers every replica: 2 forced host devices, one replica each
# --------------------------------------------------------------------------

_WARMUP_SCRIPT = """
import jax
assert jax.device_count() == 2, jax.device_count()
from repro.analysis import trace_audit
from repro.configs import SMOKE_CONFIGS
from repro.launch import serve
from repro.models import get_model
from repro.training import data as data_mod

cfg = SMOKE_CONFIGS["llama3.2-1b"].replace(dtype="float32")
model = get_model(cfg)
params = model.init(jax.random.PRNGKey(0))
fns = trace_audit.serving_jits()
for geometry in (dict(), dict(paged=True, block_size=4, chunked=True,
                              prefill_budget=4)):
    geometry.update(slots=2, pad_to=8, max_new_cap=8)
    serve.warmup(model, params, replicas=2, **geometry)
    warm = {name: fn._cache_size() for name, fn in fns.items()}
    reqs = serve.poisson_trace(
        data_mod.PAPER_PROFILES["seamless_s2t"], 4, pad_to=8, max_new_cap=8,
        vocab_size=cfg.vocab_size, arrival_rate=0.0, seed=0)
    m = serve.run_scheduler(model, params, reqs, replicas=2, **geometry)
    assert all(rep["n_requests"] for rep in m["per_replica"]), m
    grew = {name: (warm[name], fn._cache_size()) for name, fn in fns.items()
            if fn._cache_size() != warm[name]}
    assert not grew, f"compiled in the served run: {grew}"
print("WARMUP_ALL_REPLICAS_OK")
"""


def test_warmup_compiles_every_replica():
    """``serve.warmup(replicas=N)`` compiles each replica's executables on
    that replica's own device, so the served run on a 2-device host
    compiles nothing, on the contiguous and the paged+chunked pool."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    r = subprocess.run(
        [sys.executable, "-c", _WARMUP_SCRIPT],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "WARMUP_ALL_REPLICAS_OK" in r.stdout
