"""Tensor-parallel serving tests (distributed/tp_pool.py + the TP spec
paths in distributed/sharding.py).

Single-device tier: spec-tree rules on shape-only FakeMeshes (head-axis
KV sharding with the seq fallback, serving param specs, replica device
GROUPS for DP x TP placement), the --mix-classes trace generator, and a
TP=1 in-process run that must be token-identical to the plain scheduler
(the dispatch seam itself, with no sharding in play).

Subprocess tier (forced host devices): a 2-device run asserting
sharded-vs-single-device token parity on the toy config plus the
physically-split KV pool (per-device reserved bytes ~ 1/TP), the packed
mixed step on a 2-device mesh, and one 4-device run that holds TP=2 to
one device's tokens, compiles and host syncs across the plain paged,
speculative and prefix-cache paths and as DP x TP replicas.
"""
import json
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import CONFIGS, SMOKE_CONFIGS
from repro.core import profiles
from repro.distributed import sharding as sh
from repro.models import get_model
from repro.training import data as data_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    """Shape-only stand-in so spec rules can be tested at production size
    without real devices (same idiom as tests/test_sharding.py)."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESH16 = FakeMesh({"data": 16, "model": 16})
MESH8 = FakeMesh({"data": 2, "model": 8})
MESH2 = FakeMesh({"data": 1, "model": 2})
MESH13 = FakeMesh({"data": 1, "model": 13})  # divides nothing


def _smoke():
    cfg = SMOKE_CONFIGS["llama3.2-1b"].replace(dtype="float32")
    return cfg, get_model(cfg)


# --------------------------------------------------------------------------
# cache spec rules: seq-sharded and TP (head-sharded with seq fallback)
# --------------------------------------------------------------------------

def test_cache_specs_seqsharded_unscanned():
    cfg, model = _smoke()
    cache = model.abstract_cache(4, 64)
    seq = sh.cache_specs_seqsharded(cfg, cache, MESH2, 4)
    # unscanned K/V are [B, S, H, D]: seq axis is dim 1
    assert seq["layers"][0]["k"] == P(None, "model", None, None)
    assert seq["layers"][0]["v"] == P(None, "model", None, None)
    # bookkeeping stays with the batch rule (replicated on this mesh)
    assert seq["lengths"] == P(None)


def test_cache_specs_seqsharded_nondivisible_stays_base():
    cfg, model = _smoke()
    cache = model.abstract_cache(4, 64)  # 64 % 13 != 0
    seq = sh.cache_specs_seqsharded(cfg, cache, MESH13, 4)
    assert seq["layers"][0]["k"] == P(None, None, None, None)


def test_cache_specs_tp_head_axis_unscanned():
    cfg, model = _smoke()
    cache = model.abstract_cache(4, 64)
    tp = sh.cache_specs_tp(cfg, cache, MESH2, 4)
    # 2 kv heads % 2 == 0: the head axis (dim 2) carries "model" — the
    # pool is physically split across devices, 1/TP heads each
    assert tp["layers"][0]["k"] == P(None, None, "model", None)
    assert tp["layers"][1]["v"] == P(None, None, "model", None)
    # host bookkeeping is replicated: block tables / lengths stay whole
    assert tp["lengths"] == P(None)


def test_cache_specs_tp_head_axis_scanned():
    cfg = CONFIGS["llama3.2-1b"].replace(scan_layers=True)
    model = get_model(cfg)
    cache = model.abstract_cache(128, 32768)
    tp = sh.cache_specs_tp(cfg, cache, MESH8, 128)
    # scanned K/V are [L, B, S, H, D]: head axis is dim 3 (8 kv heads % 8)
    assert tp["scanned"]["k"] == P(None, ("data",), None, "model", None)


def test_cache_specs_tp_seq_fallback():
    cfg = CONFIGS["llama3.2-1b"].replace(scan_layers=True)
    model = get_model(cfg)
    cache = model.abstract_cache(128, 32768)
    tp = sh.cache_specs_tp(cfg, cache, MESH16, 128)
    # 8 kv heads % 16 != 0 but 32768 % 16 == 0: fall back to the seq axis
    # (the seqsharded rule) rather than leaving the pool replicated
    assert tp["scanned"]["k"] == P(None, ("data",), "model", None, None)


def test_cache_specs_tp_nondivisible_stays_base():
    cfg, model = _smoke()
    cache = model.abstract_cache(4, 64)  # 2 heads, 64 seq: 13 divides neither
    tp = sh.cache_specs_tp(cfg, cache, MESH13, 4)
    assert tp["layers"][0]["k"] == P(None, None, None, None)


# --------------------------------------------------------------------------
# serving param specs: enable_tp bypasses the big-model gate
# --------------------------------------------------------------------------

def test_param_specs_enable_tp_smoke_model():
    cfg, model = _smoke()
    ps = model.abstract_params()
    # default: the smoke model is far below TP_MIN_PARAMS -> replicated
    plain = sh.param_specs(cfg, ps, MESH2)
    assert all(s == P() for s in jax.tree.leaves(
        plain, is_leaf=lambda x: isinstance(x, P)))
    # serving opt-in: Megatron column/row pattern regardless of size
    tp = sh.param_specs(cfg, ps, MESH2, enable_tp=True)
    flat = {sh._path_str(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(
                tp, is_leaf=lambda x: isinstance(x, P))[0]}
    wq = next(s for k, s in flat.items() if "wq" in k)
    wo = next(s for k, s in flat.items() if "wo" in k)
    w1 = next(s for k, s in flat.items() if "ffn" in k and "w1" in k)
    w2 = next(s for k, s in flat.items() if "ffn" in k and "w2" in k)
    assert wq == P(None, "model")   # column: heads split across devices
    assert wo == P("model", None)   # row: psum joins the head shards
    assert w1 == P(None, "model")
    assert w2 == P("model", None)


# --------------------------------------------------------------------------
# replica device groups: DP x TP placement must hand out disjoint submeshes
# --------------------------------------------------------------------------

def test_replica_devices_groups_disjoint():
    devs = ["d0", "d1", "d2", "d3"]
    assert sh.replica_devices(2, devs, group_size=2) == [
        ("d0", "d1"), ("d2", "d3")]


def test_replica_devices_groups_wrap_whole():
    # more replicas than groups: whole groups wrap — a group is never
    # split, so two replicas either share ALL devices or NONE
    devs = ["d0", "d1", "d2", "d3"]
    assert sh.replica_devices(3, devs, group_size=2) == [
        ("d0", "d1"), ("d2", "d3"), ("d0", "d1")]


def test_replica_devices_groups_too_few_devices():
    with pytest.raises(ValueError):
        sh.replica_devices(1, ["d0"], group_size=2)


def test_replica_devices_group_size_one_keeps_round_robin():
    devs = ["d0", "d1", "d2"]
    assert sh.replica_devices(4, devs) == ["d0", "d1", "d2", "d0"]
    assert sh.replica_devices(2, devs, group_size=1) == ["d0", "d1"]


# --------------------------------------------------------------------------
# the --mix-classes heterogeneous trace generator (launch/serve.py)
# --------------------------------------------------------------------------

def _req_class(r):
    if isinstance(r.profile, profiles.SpeculativeProfile):
        return "speculative"
    if isinstance(r.profile, profiles.BeamProfile):
        return "beam"
    if isinstance(r.profile, profiles.ContrastiveProfile):
        return "cfg"
    return "greedy" if r.temperature == 0.0 else "sampling"


def test_mix_class_trace_covers_classes():
    from repro.launch import serve

    prof = data_mod.PAPER_PROFILES["seamless_s2t"]
    reqs = serve.mix_class_trace(
        prof, 40, pad_to=16, max_new_cap=16, vocab_size=512,
        arrival_rate=100.0, seed=0)
    assert len(reqs) == 40
    kinds = {_req_class(r) for r in reqs}
    assert kinds == {"greedy", "sampling", "beam", "cfg", "speculative"}
    # bursty but time-ordered arrivals, ready for Scheduler.submit
    arrivals = [r.t_arrival for r in reqs]
    assert arrivals == sorted(arrivals)
    # greedy requests really are greedy; sampling ones carry the knobs
    for r in reqs:
        if _req_class(r) == "greedy":
            assert r.temperature == 0.0
        if _req_class(r) == "sampling":
            assert r.temperature > 0 and r.top_p < 1.0


def test_mix_class_trace_rejects_unknown_class():
    from repro.launch import serve

    prof = data_mod.PAPER_PROFILES["seamless_s2t"]
    with pytest.raises(ValueError):
        serve.mix_class_trace(
            prof, 4, pad_to=16, max_new_cap=16, vocab_size=512,
            arrival_rate=100.0, classes=("greedy", "nope"))


# --------------------------------------------------------------------------
# the dispatch seam: Scheduler(tp_mesh=1-device mesh) is the TP executable
# family with no sharding in play — tokens must match the plain scheduler
# --------------------------------------------------------------------------

def test_tp1_inprocess_token_identity():
    from repro.core.scheduler import Scheduler
    from repro.distributed import tp_pool
    from repro.launch import serve

    cfg, model = _smoke()
    params = model.init(jax.random.PRNGKey(0))
    prof = data_mod.PAPER_PROFILES["seamless_s2t"]

    def trace():
        return serve.poisson_trace(
            prof, 8, pad_to=16, max_new_cap=16, vocab_size=cfg.vocab_size,
            arrival_rate=0.0, seed=0, temperature=0.8, top_p=0.9)

    def run(tp_mesh):
        sched = Scheduler(
            model, params, slots=4, pad_to=16, max_new_cap=16,
            paged=True, block_size=16, num_blocks=10,
            chunked=True, prefill_budget=4, tp_mesh=tp_mesh)
        done = sched.run(trace())
        return {r.rid: list(r.tokens) for r in done}

    tokens_tp = run(tp_pool.make_tp_mesh(1))
    tokens_plain = run(None)
    assert tokens_tp == tokens_plain
    assert len(tokens_plain) == 8


def test_scheduler_rejects_mesh_plus_device_pin():
    from repro.core.scheduler import Scheduler
    from repro.distributed import tp_pool

    cfg, model = _smoke()
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        Scheduler(model, params, slots=2, pad_to=16, max_new_cap=16,
                  paged=True, block_size=16, num_blocks=8,
                  tp_mesh=tp_pool.make_tp_mesh(1),
                  device=jax.devices()[0])


def test_make_tp_mesh_needs_enough_devices():
    from repro.distributed import tp_pool

    with pytest.raises(ValueError):
        tp_pool.make_tp_mesh(jax.device_count() + 1)


# --------------------------------------------------------------------------
# the real thing: 2 forced host devices, sharded vs single-device parity
# --------------------------------------------------------------------------

_TP2_SCRIPT = """
import jax
assert jax.device_count() == 2, jax.device_count()
from repro.configs import SMOKE_CONFIGS
from repro.launch import serve
from repro.models import get_model
from repro.training import data as data_mod

cfg = SMOKE_CONFIGS["llama3.2-1b"].replace(dtype="float32")
model = get_model(cfg)
params = model.init(jax.random.PRNGKey(0))
prof = data_mod.PAPER_PROFILES["seamless_s2t"]


def trace(temperature):
    return serve.poisson_trace(
        prof, 8, pad_to=16, max_new_cap=16, vocab_size=cfg.vocab_size,
        arrival_rate=0.0, seed=0, temperature=temperature,
        top_p=0.9 if temperature > 0 else 1.0)


def run(tp, temperature):
    m, done = serve.run_scheduler(
        model, params, trace(temperature), slots=4, pad_to=16,
        max_new_cap=16, policy="continuous", seed=0, paged=True,
        block_size=16, num_blocks=10, chunked=True, prefill_budget=4,
        tp=tp, return_requests=True)
    return m, {r.rid: list(r.tokens) for r in done}


for temperature in (0.0, 0.8):
    ms, toks_single = run(None, temperature)
    mt, toks_tp = run(2, temperature)
    assert toks_tp == toks_single, f"tokens diverged at t={temperature}"
    assert len(toks_single) == 8
ratio = mt["kv_reserved_per_device_bytes"] / ms["kv_reserved_bytes"]
assert ratio <= 0.6, f"per-device KV not split: {ratio:.3f}x"
print(f"TP2_PARITY_OK ratio={ratio:.3f}")
"""


@pytest.mark.slow
def test_tp2_subprocess_parity():
    """Sharded-vs-single-device numeric parity on the toy config: token
    identity at temperature 0 and 0.8, and the KV pool physically split
    (per-device reserved bytes ~ 1/2 the single-device pool)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    r = subprocess.run(
        [sys.executable, "-c", _TP2_SCRIPT],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "TP2_PARITY_OK" in r.stdout


_TP2_MIXED_SCRIPT = """
import jax
import jax.numpy as jnp
import numpy as np
assert jax.device_count() == 2, jax.device_count()
from repro.configs import qwen2_5_3b
from repro.core import engine
from repro.core.slot_pool import BlockPool
from repro.distributed import tp_pool
from repro.models import get_model

model = get_model(qwen2_5_3b.SMOKE.replace(dtype="float32"))
params = model.init(jax.random.PRNGKey(0))
pool = BlockPool(model, 4, 25, block_size=4)
for s in range(4):
    assert pool.acquire() == s and pool.ensure(s, 23)
pool.sync()
leaves, tree = jax.tree_util.tree_flatten(pool.cache["layers"])
keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
pool.cache["layers"] = jax.tree_util.tree_unflatten(
    tree, [jax.random.normal(k, x.shape, x.dtype) for k, x in zip(keys, leaves)])
copy = lambda: jax.tree.map(jnp.copy, pool.cache)
# three chunks beside a decode row, at the packer's bound for prompts of 4
toks = jnp.asarray(np.random.default_rng(2).integers(0, 512, (4, 8)), jnp.int32)
t_new = jnp.asarray([2, 2, 3, 1], jnp.int32)
lengths = jnp.asarray([0, 2, 0, 6], jnp.int32)
want, want_cache = engine.mixed_step(model, params, copy(), toks, t_new,
                                     lengths, 4)
ctx = tp_pool.TPContext(model, params, tp_pool.make_tp_mesh(2),
                        cache_like=pool.cache, max_len=25)
got, got_cache = ctx.executables().mixed_step(
    model, ctx.params, ctx.place_cache(copy()), toks, t_new, lengths, 4)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                           atol=1e-4)
for g, w in zip(jax.tree.leaves(got_cache["layers"]),
                jax.tree.leaves(want_cache["layers"])):
    np.testing.assert_allclose(np.asarray(g)[1:], np.asarray(w)[1:],
                               rtol=1e-5, atol=1e-5)
print("TP2_MIXED_OK")
"""


def test_tp2_packed_mixed_step_matches_one_device():
    """The packed mixed step on a 2-device TP mesh (heads split) gives the
    one-device step's logits and K/V, with three chunks beside a decode
    row."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    r = subprocess.run(
        [sys.executable, "-c", _TP2_MIXED_SCRIPT],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "TP2_MIXED_OK" in r.stdout


# --------------------------------------------------------------------------
# the rest of the serving surface on 4 forced host devices: TP=2 beside one
# device on the plain paged, speculative and prefix-cache paths, compile
# and host-sync parity, and two TP=2 replicas on disjoint submeshes
# --------------------------------------------------------------------------

_TP2_SURFACE_SCRIPT = """
import json
import jax
assert jax.device_count() == 4, jax.device_count()
from repro.analysis import trace_audit
from repro.configs import SMOKE_CONFIGS
from repro.distributed import sharding
from repro.launch import serve
from repro.models import get_model
from repro.training import data as data_mod

cfg = SMOKE_CONFIGS["llama3.2-1b"].replace(dtype="float32")
model = get_model(cfg)
params = model.init(jax.random.PRNGKey(0))
prof = data_mod.PAPER_PROFILES["seamless_s2t"]
N = 8
CHUNKED = dict(slots=4, pad_to=16, max_new_cap=16, block_size=16,
               num_blocks=14, chunked=True, prefill_budget=4)
PAGED = dict(slots=4, pad_to=16, max_new_cap=16, block_size=16,
             num_blocks=14)
PREFIX = dict(slots=4, pad_to=24, max_new_cap=8, block_size=4, num_blocks=48,
              chunked=True, prefill_budget=8, prefix_cache=True)


def trace(temperature, seed=0):
    return serve.poisson_trace(
        prof, N, pad_to=16, max_new_cap=16, vocab_size=cfg.vocab_size,
        arrival_rate=0.0, seed=seed, temperature=temperature,
        top_p=0.9 if temperature > 0 else 1.0)


def spec_trace():
    return serve.mix_class_trace(
        prof, N, pad_to=16, max_new_cap=16, vocab_size=cfg.vocab_size,
        arrival_rate=0.0, classes=("greedy", "speculative"), seed=0,
        temperature=0.8)


def prefix_trace(temperature):
    return serve.shared_prefix_trace(
        N, n_prefixes=2, prefix_len=16, pad_to=24, max_new_cap=8,
        vocab_size=cfg.vocab_size, arrival_rate=0.0, seed=0,
        temperature=temperature, top_p=0.9 if temperature > 0 else 1.0)


def run(reqs, tp=None, replicas=None, **geometry):
    m, done = serve.run_scheduler(
        model, params, reqs, policy="continuous", seed=0, paged=True, tp=tp,
        replicas=replicas, return_requests=True, **geometry)
    return m, {r.rid: list(r.tokens) for r in done}


def same(got, want):
    return got == want and len(want) == N


gates = {}
gates["paged_t0.0"] = same(run(trace(0.0), tp=2, **PAGED)[1],
                           run(trace(0.0), **PAGED)[1])
gates["speculative_t0.8"] = same(run(spec_trace(), tp=2, **CHUNKED)[1],
                                 run(spec_trace(), **CHUNKED)[1])
for t in (0.0, 0.8):
    m_one, want = run(prefix_trace(t), **PREFIX)
    m_tp, got = run(prefix_trace(t), tp=2, **PREFIX)
    gates[f"prefix_t{t}"] = (same(got, want) and m_tp["prefix_hits"]
                             == m_one["prefix_hits"] > 0)

run(trace(0.8), tp=2, **CHUNKED)
jits = trace_audit.serving_jits()
before = trace_audit._cache_sizes(jits)
run(trace(0.8, seed=1), tp=2, **CHUNKED)
gates["no_recompiles"] = trace_audit._cache_sizes(jits) == before

real_get, counts = jax.device_get, [0]


def counting_get(x):
    counts[0] += 1
    return real_get(x)


jax.device_get = counting_get
try:
    m_one, _ = run(trace(0.0), **CHUNKED)
    syncs_one, counts[0] = counts[0], 0
    m_tp, _ = run(trace(0.0), tp=2, **CHUNKED)
    syncs_tp = counts[0]
finally:
    jax.device_get = real_get
gates["sync_parity"] = (syncs_tp == syncs_one > 0
                        and m_tp["decode_steps"] == m_one["decode_steps"])

groups = sharding.replica_devices(2, group_size=2)
flat = [d for g in groups for d in g]
for t in (0.0, 0.8):
    want = run(trace(t), **CHUNKED)[1]
    got = run(trace(t), tp=2, replicas=2, **CHUNKED)[1]
    gates[f"dp_x_tp_t{t}"] = len(set(flat)) == len(flat) == 4 and same(got, want)
print("TP2_GATES " + json.dumps(gates))
"""

TP2_GATES = ["paged_t0.0", "speculative_t0.8", "prefix_t0.0", "prefix_t0.8",
             "no_recompiles", "sync_parity", "dp_x_tp_t0.0", "dp_x_tp_t0.8"]


@pytest.fixture(scope="module")
def tp2_gates():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run(
        [sys.executable, "-c", _TP2_SURFACE_SCRIPT],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    line = next(x for x in r.stdout.splitlines() if x.startswith("TP2_GATES "))
    return json.loads(line.split(" ", 1)[1])


@pytest.mark.parametrize("gate", TP2_GATES)
def test_tp2_serves_like_one_device(tp2_gates, gate):
    """A TP=2 pool serves what one device serves on every path: plain
    paged decode, speculative windows under sampling, prefix-cache hits
    (the same hit count), a second trace that compiles nothing, the same
    host syncs and steps, and as two replicas on disjoint submeshes (DP x
    TP)."""
    assert tp2_gates[gate], tp2_gates
