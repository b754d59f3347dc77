"""Hypothesis property tests over the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from _hyp import given, settings, hst

from repro.kernels import ops, ref

dims = hst.integers(1, 4)


@given(
    b=hst.integers(1, 3),
    t=hst.integers(1, 40),
    hkv=hst.sampled_from([1, 2, 4]),
    g=hst.sampled_from([1, 2, 4]),
    d=hst.sampled_from([8, 16, 32]),
    blk=hst.sampled_from([4, 8, 16]),
    seed=hst.integers(0, 2 ** 16),
)
def test_flash_equals_materialized_softmax(b, t, hkv, g, d, blk, seed):
    """Online softmax == full materialized softmax for arbitrary shapes."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, t, hkv * g, d))
    k = jax.random.normal(ks[1], (b, t, hkv, d))
    v = jax.random.normal(ks[2], (b, t, hkv, d))
    want = ref.attention_ref(q, k, v, causal=True)
    got = ops.flash_attention(q, k, v, causal=True, impl="xla", block_k=blk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=1e-3)


@given(
    t=hst.integers(1, 48),
    chunk=hst.sampled_from([4, 8, 16]),
    seed=hst.integers(0, 2 ** 16),
)
def test_ssd_chunked_equals_sequential(t, chunk, seed):
    """SSD chunked scan == naive sequential recurrence, any T/chunk split."""
    b, h, p, g, n = 2, 2, 8, 1, 4
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B_ = jax.random.normal(ks[3], (b, t, g, n))
    C = jax.random.normal(ks[4], (b, t, g, n))
    D = jax.random.normal(ks[5], (h,))
    want_y, want_s = ref.ssd_ref(x, dt, A, B_, C, D)
    got_y, got_s = ops.ssd_scan(x, dt, A, B_, C, D, chunk=chunk, impl="xla")
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=1e-3, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=1e-3, rtol=1e-2)


@given(
    s=hst.integers(2, 64),
    nsplit=hst.integers(1, 4),
    seed=hst.integers(0, 2 ** 16),
)
def test_lse_combine_split_invariance(s, nsplit, seed):
    """Flash-decode partials combine to the same result for ANY split of
    the KV cache (the property that makes the tiled paged decode walk
    exact)."""
    b, hq, hkv, d = 2, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1)
    want = ref.decode_attention_ref(q, k, v, lengths)
    bounds = sorted(
        set([0, s] + list(np.random.default_rng(seed).integers(1, s, nsplit)))
    )
    parts = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        kv_valid = jnp.arange(lo, hi)[None, :] < lengths[:, None]
        parts.append(ops.decode_attention_partial(q, k[:, lo:hi], v[:, lo:hi], kv_valid))
    accs, ms, ls = (jnp.stack(x) for x in zip(*parts))
    got = ops.combine_partial_attention(accs, ms, ls)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=1e-3)


@given(
    w=hst.integers(2, 12),
    t=hst.integers(1, 30),
    seed=hst.integers(0, 2 ** 16),
)
def test_window_attention_only_sees_window(w, t, seed):
    """Perturbing any key OUTSIDE the window never changes the output."""
    b, h, d = 1, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, h, d))
    v = jax.random.normal(ks[2], (b, t, h, d))
    base = ops.flash_attention(q, k, v, causal=True, window=w, impl="xla")
    if t > w:
        k2 = k.at[:, 0].set(99.0)  # outside every query's window? only q_t with t-w>=...
        v2 = v.at[:, 0].set(99.0)
        got = ops.flash_attention(q, k2, v2, causal=True, window=w, impl="xla")
        # queries at positions >= w cannot see key 0
        np.testing.assert_allclose(
            np.asarray(got[:, w:]), np.asarray(base[:, w:]), atol=1e-5
        )


@given(seed=hst.integers(0, 2 ** 16), t=hst.integers(1, 20))
def test_rglru_associative_scan_equals_sequential(seed, t):
    """The parallel-prefix RG-LRU == an explicit sequential recurrence."""
    from repro.models.hybrid import _rg_lru
    from repro.models import layers as L

    b, w = 2, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = {
        "gate_a": {"w": jax.random.normal(ks[0], (w, w)) * 0.3, "b": jnp.zeros(w)},
        "gate_x": {"w": jax.random.normal(ks[1], (w, w)) * 0.3, "b": jnp.zeros(w)},
        "lam": jax.random.normal(ks[2], (w,)),
    }
    x = jax.random.normal(ks[3], (b, t, w))
    h_par, hT = _rg_lru(p, x, None)
    # sequential reference
    r = jax.nn.sigmoid(x @ p["gate_a"]["w"])
    i = jax.nn.sigmoid(x @ p["gate_x"]["w"])
    a = jnp.exp(-8.0 * jax.nn.softplus(p["lam"])[None, None] * r)
    gated = jnp.sqrt(jnp.maximum(1 - a * a, 1e-12)) * (i * x)
    hs = []
    hprev = jnp.zeros((b, w))
    for j in range(t):
        hprev = a[:, j] * hprev + gated[:, j]
        hs.append(hprev)
    np.testing.assert_allclose(
        np.asarray(h_par), np.asarray(jnp.stack(hs, 1)), atol=1e-5, rtol=1e-4
    )
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hprev), atol=1e-5)


@given(
    n=hst.integers(1, 64),
    e=hst.sampled_from([2, 4, 8]),
    k=hst.integers(1, 3),
    seed=hst.integers(0, 2 ** 16),
)
def test_moe_dispatch_conservation(n, e, k, seed):
    """With dropless capacity, MoE output == explicit per-token expert sum."""
    import dataclasses

    from repro.configs.base import MoEConfig, ModelConfig
    from repro.models import moe as M

    k = min(k, e)
    cfg = ModelConfig(
        name="t", family="moe", n_layers=1, d_model=16, n_heads=2, n_kv_heads=2,
        d_ff=32, vocab_size=64, dtype="float32",
        moe=MoEConfig(n_experts=e, top_k=k, d_ff_expert=8, capacity_factor=float(e) / k),
    )
    key = jax.random.PRNGKey(seed)
    p = M.init_moe(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, n, 16))
    out, aux = M.moe_ffn(cfg, p, x)
    # explicit dense reference
    xf = x.reshape(n, 16)
    logits = xf @ p["router"]["w"]
    probs = jax.nn.softmax(logits, -1)
    top_w, top_ids = jax.lax.top_k(probs, k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    want = jnp.zeros((n, 16))
    for j in range(n):
        acc = jnp.zeros((16,))
        for kk in range(k):
            eid = int(top_ids[j, kk])
            h = jax.nn.silu(xf[j] @ p["w1"][eid]) * (xf[j] @ p["w3"][eid])
            acc = acc + top_w[j, kk] * (h @ p["w2"][eid])
        want = want.at[j].set(acc)
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(want), atol=1e-4, rtol=1e-3
    )
    assert bool(jnp.isfinite(aux))
