"""Per-kernel validation: sweep shapes/dtypes, assert_allclose every
implementation (xla fast path AND pallas interpret=True) against the
pure-jnp oracle in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-5, rtol=2e-4
    )


# ---------------------------------------------------------------- flash attn
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,t,hq,hkv,d,window",
    [
        (1, 16, 4, 4, 16, None),     # MHA
        (2, 67, 8, 2, 32, None),     # GQA, ragged T
        (2, 67, 8, 2, 32, 16),       # sliding window
        (1, 128, 4, 1, 64, None),    # MQA
        (2, 33, 6, 3, 48, 8),        # odd dims
    ],
)
@pytest.mark.parametrize("impl", ["xla", "xla_blockskip", "pallas"])
def test_flash_attention_sweep(b, t, hq, hkv, d, window, dtype, impl):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (b, t, hq, d), dtype)
    k = rand(ks[1], (b, t, hkv, d), dtype)
    v = rand(ks[2], (b, t, hkv, d), dtype)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    got = ops.flash_attention(
        q, k, v, causal=True, window=window, impl=impl, block_q=16, block_k=16
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


def test_flash_attention_k_valid_and_positions():
    ks = jax.random.split(KEY, 3)
    b, t, s, h, d = 2, 5, 40, 4, 16
    q = rand(ks[0], (b, t, h, d))
    k = rand(ks[1], (b, s, h, d))
    v = rand(ks[2], (b, s, h, d))
    qpos = jnp.array([[10, 11, 12, 13, 14], [3, 4, 5, 6, 7]])
    kpos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    kval = kpos < jnp.array([[15], [8]])
    want = ref.attention_ref(
        q, k, v, q_positions=qpos, k_positions=kpos, causal=True, k_valid=kval
    )
    for impl in ("xla", "pallas"):
        got = ops.flash_attention(
            q, k, v, q_positions=qpos, k_positions=kpos, causal=True,
            k_valid=kval, impl=impl, block_k=16,
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------------------- decode attn
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hq,hkv,d", [(2, 50, 8, 2, 32), (1, 17, 4, 4, 16), (3, 129, 8, 1, 64)]
)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_decode_attention_sweep(b, s, hq, hkv, d, dtype, impl):
    ks = jax.random.split(KEY, 4)
    q = rand(ks[0], (b, hq, d), dtype)
    k = rand(ks[1], (b, s, hkv, d), dtype)
    v = rand(ks[2], (b, s, hkv, d), dtype)
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1)
    want = ref.decode_attention_ref(q, k, v, lengths)
    got = ops.decode_attention(q, k, v, lengths, impl=impl, block_k=16)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


def test_decode_partial_combine_matches_full():
    """Flash-decode LSE-combine of partials over splits of the keys."""
    ks = jax.random.split(KEY, 4)
    b, s, hq, hkv, d = 2, 64, 8, 2, 32
    q = rand(ks[0], (b, hq, d))
    k = rand(ks[1], (b, s, hkv, d))
    v = rand(ks[2], (b, s, hkv, d))
    lengths = jnp.array([37, 64])
    want = ref.decode_attention_ref(q, k, v, lengths)
    parts = []
    for lo in range(0, s, 16):
        kv_valid = (jnp.arange(lo, lo + 16))[None, :] < lengths[:, None]
        parts.append(
            ops.decode_attention_partial(
                q, k[:, lo : lo + 16], v[:, lo : lo + 16], kv_valid
            )
        )
    accs, ms, ls = (jnp.stack(x) for x in zip(*parts))
    got = ops.combine_partial_attention(accs, ms, ls)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------------------------ rmsnorm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 64), (2, 7, 96), (1, 1, 128)])
def test_rmsnorm_sweep(shape, dtype):
    x = rand(KEY, shape, dtype)
    w = rand(jax.random.PRNGKey(1), shape[-1:], dtype)
    want = ref.rmsnorm_ref(x, w)
    got = ops.rmsnorm(x, w, impl="pallas")
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


# -------------------------------------------------------------- int8 matmul
@pytest.mark.parametrize("m,k,n", [(8, 32, 16), (37, 100, 53), (128, 256, 64)])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_int8_weight_only_sweep(m, k, n, impl):
    x = rand(KEY, (m, k))
    w = rand(jax.random.PRNGKey(1), (k, n))
    wq, ws = ops.quantize_int8(w, axis=0)
    want = ref.int8_matmul_ref(x, wq, ws)
    got = ops.int8_matmul_weight_only(x, wq, ws, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)
    # and the quantized result approximates the exact matmul
    exact = np.asarray(x @ w)
    rel = np.abs(np.asarray(got) - exact).max() / np.abs(exact).max()
    assert rel < 0.05


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_int8_dynamic(impl):
    x = rand(KEY, (19, 64))
    w = rand(jax.random.PRNGKey(1), (64, 24))
    wq, ws = ops.quantize_int8(w, axis=0)
    got = ops.int8_matmul_dynamic(x, wq, ws, impl=impl)
    exact = np.asarray(x @ w)
    rel = np.abs(np.asarray(got, np.float32) - exact).max() / np.abs(exact).max()
    assert rel < 0.05


# --------------------------------------------------------------------- SSD
@pytest.mark.parametrize(
    "b,t,h,p,g,n,chunk",
    [(1, 16, 2, 8, 1, 4, 8), (2, 37, 4, 16, 2, 8, 16), (1, 64, 8, 32, 1, 16, 32)],
)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_ssd_scan_sweep(b, t, h, p, g, n, chunk, impl):
    ks = jax.random.split(KEY, 6)
    x = rand(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(rand(ks[1], (b, t, h)))
    A = -jnp.exp(rand(ks[2], (h,)))
    B_ = rand(ks[3], (b, t, g, n))
    C = rand(ks[4], (b, t, g, n))
    D = rand(ks[5], (h,))
    init = rand(jax.random.PRNGKey(9), (b, h, p, n))
    want_y, want_s = ref.ssd_ref(x, dt, A, B_, C, D, initial_state=init)
    got_y, got_s = ops.ssd_scan(
        x, dt, A, B_, C, D, chunk=chunk, initial_state=init, impl=impl
    )
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=2e-4, rtol=2e-3)


def test_ssd_decode_step_chain():
    """Sequential decode steps equal a batched scan over the same tokens."""
    ks = jax.random.split(KEY, 6)
    b, t, h, p, g, n = 2, 6, 4, 8, 2, 4
    x = rand(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(rand(ks[1], (b, t, h)))
    A = -jnp.exp(rand(ks[2], (h,)))
    B_ = rand(ks[3], (b, t, g, n))
    C = rand(ks[4], (b, t, g, n))
    D = rand(ks[5], (h,))
    want_y, want_s = ref.ssd_ref(x, dt, A, B_, C, D)
    state = jnp.zeros((b, h, p, n))
    ys = []
    for i in range(t):
        y, state = ops.ssd_decode_step(x[:, i], dt[:, i], A, B_[:, i], C[:, i], D, state)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)), np.asarray(want_y), atol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_s), atol=1e-4)


# -------------------------------------------------------------------- HSTU
@pytest.mark.parametrize("mal", [None, 24])
@pytest.mark.parametrize("impl", ["pallas"])
def test_hstu_attention_sweep(mal, impl):
    ks = jax.random.split(KEY, 4)
    b, t, h, d = 2, 70, 4, 32
    q = rand(ks[0], (b, t, h, d), scale=0.3)
    k = rand(ks[1], (b, t, h, d), scale=0.3)
    v = rand(ks[2], (b, t, h, d))
    rb = rand(ks[3], (2 * 64 - 1,), scale=0.1)
    lens = jnp.array([40, 70])
    want = ref.hstu_attention_ref(q, k, v, rb, max_attn_len=mal, lengths=lens)
    got = ops.hstu_attention(q, k, v, rb, max_attn_len=mal, lengths=lens, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
