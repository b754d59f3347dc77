"""Tests for the repro.analysis static-analysis pass (AST lint layer +
lowered-HLO trace audits).

Each AST rule gets a known-bad fixture (must fire), a known-good fixture
(must stay silent), and a suppression/baseline path. The trace-audit
tests plant deliberately bad jits (undonated buffer, materialized
transient, bf16->f32 upcast) and assert the audit catches exactly those.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import astlint, trace_audit
from repro.analysis.hotpath import hot_path

REPO_ROOT = Path(__file__).resolve().parents[1]


def rules(findings):
    return sorted(f.rule for f in findings)


# --------------------------------------------------------------------------
# HS001 — host syncs in hot-path functions
# --------------------------------------------------------------------------

HS_BAD = """
import numpy as np
from repro.analysis.hotpath import hot_path

@hot_path
def step(pool, logits):
    toks = np.asarray(logits)
    return toks
"""

HS_GOOD = """
import jax
from repro.analysis.hotpath import hot_path

@hot_path
def step(pool, out):
    feed, done = jax.device_get((out.feed, out.done))
    n = len(pool.slots)
    return int(feed[0]), bool(done.all()), n
"""


def test_hs001_fires_on_asarray_in_hot_path():
    findings = astlint.lint_source(HS_BAD)
    assert "HS001" in rules(findings)
    f = next(f for f in findings if f.rule == "HS001")
    assert "asarray" in f.snippet


def test_hs001_silent_on_single_device_get_sync():
    # the sanctioned idiom: ONE device_get batching the step's outputs;
    # casts of the host results (and len() on host lists) are free
    assert astlint.lint_source(HS_GOOD) == []


def test_hs001_registry_hotness_without_decorator():
    src = "import numpy as np\ndef decode_step(model, cache):\n"\
          "    return np.asarray(cache)\n"
    findings = astlint.lint_source(
        src, "src/repro/core/engine.py", "repro.core.engine")
    assert "HS001" in rules(findings)
    # the same function in a non-hot module stays silent
    assert astlint.lint_source(src, "x.py", "somewhere.else") == []


def test_hs001_item_and_cast_fire():
    src = """
from repro.analysis.hotpath import hot_path

@hot_path
def step(cache, logits):
    a = logits.item()
    b = int(logits[0])
    return a, b
"""
    assert rules(astlint.lint_source(src)) == ["HS001", "HS001"]


def test_hs001_suppression_comment():
    src = """
import numpy as np
from repro.analysis.hotpath import hot_path

@hot_path
def step(slots):
    sl = np.asarray(slots)  # repro-lint: disable=HS001 — host list
    return sl
"""
    assert astlint.lint_source(src) == []


# --------------------------------------------------------------------------
# DN001 — jit sites missing donation for cache/KV-typed params
# --------------------------------------------------------------------------

DN_BAD = """
import jax

@jax.jit
def decode(params, cache, token):
    return cache
"""

DN_GOOD = """
import functools
import jax

@functools.partial(jax.jit, donate_argnums=(1,))
def decode(params, cache, token):
    return cache
"""


def test_dn001_fires_on_undonated_cache_param():
    findings = astlint.lint_source(DN_BAD)
    assert rules(findings) == ["DN001"]
    assert "cache" in findings[0].message


def test_dn001_silent_when_donated():
    assert astlint.lint_source(DN_GOOD) == []


def test_dn001_donate_argnames_counts():
    src = """
import functools
import jax

@functools.partial(jax.jit, donate_argnames=("kv_pool",))
def decode(params, kv_pool):
    return kv_pool
"""
    assert astlint.lint_source(src) == []


def test_dn001_standalone_suppression_above_decorator():
    src = """
import jax

# repro-lint: disable=DN001 — deliberately undonated baseline arm
@jax.jit
def reorder(params, cache):
    return cache
"""
    assert astlint.lint_source(src) == []


# --------------------------------------------------------------------------
# TB001 — Python branching / casts on traced values inside jit
# --------------------------------------------------------------------------

TB_BAD = """
import jax

@jax.jit
def f(x):
    if x > 0:
        return x
    return int(x)
"""


def test_tb001_fires_on_traced_branch_and_cast():
    assert rules(astlint.lint_source(TB_BAD)) == ["TB001", "TB001"]


def test_tb001_static_args_exempt():
    src = """
import functools
import jax

@functools.partial(jax.jit, static_argnums=(1,))
def f(x, n):
    if n > 2:
        return x + n
    return x
"""
    assert astlint.lint_source(src) == []


def test_tb001_presence_test_exempt():
    src = """
import jax

@jax.jit
def f(x, extra):
    if extra is None:
        return x
    return x + extra
"""
    assert astlint.lint_source(src) == []


# --------------------------------------------------------------------------
# fingerprints + baseline
# --------------------------------------------------------------------------

def test_fingerprints_stable_under_line_drift():
    a = astlint.lint_source(DN_BAD, "m.py", "m")
    b = astlint.lint_source("\n\n\n" + DN_BAD, "m.py", "m")
    assert [f.fingerprint for f in a] == [f.fingerprint for f in b]


def test_baseline_roundtrip_and_matching(tmp_path):
    findings = astlint.lint_source(DN_BAD, "m.py", "m")
    path = tmp_path / "baseline.json"
    astlint.write_baseline(findings, path)
    baseline = astlint.load_baseline(path)

    new, stale = astlint.apply_baseline(findings, baseline)
    assert new == [] and stale == set()

    # fixing the violation leaves its fingerprint stale in the baseline
    new, stale = astlint.apply_baseline(
        astlint.lint_source(DN_GOOD, "m.py", "m"), baseline)
    assert new == [] and stale == baseline and stale

    # a fresh violation is NOT absorbed by an unrelated baseline entry
    new, _ = astlint.apply_baseline(
        astlint.lint_source(TB_BAD, "m.py", "m"), baseline)
    assert rules(new) == ["TB001", "TB001"]


def test_missing_baseline_is_empty(tmp_path):
    assert astlint.load_baseline(tmp_path / "nope.json") == set()


def test_repo_is_lint_clean_against_checked_in_baseline():
    findings = astlint.lint_paths(REPO_ROOT)
    baseline = astlint.load_baseline(
        REPO_ROOT / "src/repro/analysis/baseline.json")
    new, stale = astlint.apply_baseline(findings, baseline)
    assert new == [], [f.fingerprint for f in new]
    assert stale == set()


def test_hot_path_decorator_is_a_runtime_noop():
    def fn():
        return 7

    marked = hot_path(fn)
    assert marked is fn and fn.__repro_hot_path__ and fn() == 7


# --------------------------------------------------------------------------
# trace audits over deliberately planted jits
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def big():
    # 64KB leaf: over audit_donation/audit_dtypes' 32KB thresholds
    return jnp.zeros((256, 64), jnp.float32)


def test_audit_donation_flags_planted_undonated_jit(big):
    def f(params, cache):
        return cache + params

    fails = trace_audit.audit_donation(
        jax.jit(f).lower(big, big), exempt_args=(0,), label="t")
    assert fails and "not donated" in fails[0]

    ok = trace_audit.audit_donation(
        jax.jit(f, donate_argnums=(1,)).lower(big, big),
        exempt_args=(0,), label="t")
    assert ok == []


def test_audit_donation_exempts_params_arg(big):
    def f(params, token):
        return params * token

    fails = trace_audit.audit_donation(
        jax.jit(f).lower(big, jnp.float32(2.0)),
        exempt_args=(0,), label="t")
    assert fails == []


def test_audit_no_growth_catches_materialized_transient():
    x = jnp.zeros((256,), jnp.float32)  # 1KB signature

    def outer(x):
        return jnp.sum(x[:, None] * x[None, :])  # 256KB transient

    low = jax.jit(outer).lower(x)
    fails = trace_audit.audit_no_growth(low, label="t")
    assert fails and "exceeds" in fails[0]
    assert trace_audit.audit_no_growth(
        jax.jit(lambda x: x * 2).lower(x), label="t") == []


def test_audit_no_growth_forbidden_patterns():
    x = jnp.zeros((4, 96, 8), jnp.float32)
    low = jax.jit(lambda x: x + 1).lower(x)
    fails = trace_audit.audit_no_growth(
        low, forbidden=("tensor<4x96x",), label="t")
    assert fails and "forbidden" in fails[0]


def test_audit_dtypes_catches_widening_and_honors_allow():
    x = jnp.zeros((256, 256), jnp.bfloat16)  # f32 image: 256KB
    low = jax.jit(lambda x: x.astype(jnp.float32)).lower(x)
    fails = trace_audit.audit_dtypes(low, label="t")
    assert fails and "widening" in fails[0]
    assert trace_audit.audit_dtypes(
        low, allow=("tensor<256x256xf32>",), label="t") == []
    # staying narrow is clean
    assert trace_audit.audit_dtypes(
        jax.jit(lambda x: x * 2).lower(x), label="t") == []


def test_donation_summary_counts(big):
    def f(params, cache):
        return cache + params

    s = trace_audit.donation_summary(
        jax.jit(f, donate_argnums=(1,)).lower(big, big))
    assert s["arg_leaves"] == 2 and s["donated_leaves"] == 1
    assert s["aliased_outputs"] >= 1


def test_paged_growth_patterns_shapes():
    assert trace_audit.paged_growth_patterns(4, 6, 16) == [
        "tensor<4x96x", "tensor<4x6x16x"]


def test_paged_decode_step_materialises_no_full_gather():
    """The paged decode step walks the block table in tiles: where a
    slot's table spans several tiles (66 blocks of 16, 1,056 keys, against
    tiles of at most ``TILE_KEYS`` = 512), no full gathered ``[slots,
    max_blocks*block_size, ...]`` K/V transient appears and no
    intermediate exceeds the static envelope. (Below one tile's keys, the
    one tile IS the whole table.)"""
    from repro.configs import SMOKE_CONFIGS
    from repro.models import attention, get_model

    cfg = SMOKE_CONFIGS[trace_audit.ARCH].replace(dtype="float32")
    model = get_model(cfg)
    slots, block_size, max_new_cap = 4, 16, 1024
    lowered = trace_audit.lower_serving(
        model, model.init(jax.random.PRNGKey(0)), paged=True, slots=slots,
        max_new_cap=max_new_cap, block_size=block_size,
        num_blocks=4 * 66 + 1)
    pool = lowered.pop("_pool")
    assert pool.max_blocks == 66
    assert pool.max_blocks * block_size > attention.TILE_KEYS
    forbidden = trace_audit.paged_growth_patterns(
        slots, pool.max_blocks, block_size)
    assert trace_audit.audit_no_growth(
        lowered["decode_step"], forbidden=forbidden,
        label="paged/decode_step") == []
