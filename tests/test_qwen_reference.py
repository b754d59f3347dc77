"""Qwen2.5 through the serving path against a plain float32 reference.

The reference below is the Qwen2.5 block written out in ``jax.numpy`` at
``Precision.HIGHEST``: pre-norm RMSNorm residual blocks, q/k/v projections
with their biases, rotate-half RoPE, causal grouped-query attention, the
output projection, a SwiGLU FFN, the final RMSNorm and the tied
embedding as the head. It takes the program's weights (with nonzero QKV
biases and norm scales drawn here) and nothing else of it.

The packed mixed step is also held to the unpacked computation of the
same step, the verify program over all ``B x C`` lanes, in every layout a
step can take."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import qwen2_5_3b
from repro.core import engine
from repro.core.scheduler import Scheduler, ServeRequest
from repro.core.slot_pool import BlockPool
from repro.models import get_model

HI = jax.lax.Precision.HIGHEST
CFG = qwen2_5_3b.SMOKE.replace(dtype="float32")


def _perturb(params, key):
    """Nonzero QKV biases and norm scales, so the comparison sees them."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, x), k in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if name.endswith("['b']"):
            x = 0.5 * jax.random.normal(k, x.shape, x.dtype)
        elif name.endswith("['scale']"):
            x = 1.0 + 0.2 * jax.random.normal(k, x.shape, x.dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module")
def qwen():
    model = get_model(CFG)
    params = _perturb(model.init(jax.random.PRNGKey(0)),
                      jax.random.PRNGKey(1))
    return model, params


# ---- the reference ------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, H, D] at positions 0..T-1."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def reference_logits(params, tokens):
    """Logits [T, V] of a whole sequence, every position."""
    c = CFG
    hq, hkv, dh, eps = c.n_heads, c.n_kv_heads, c.head_dim, c.rmsnorm_eps
    t = len(tokens)
    mm = lambda a, w: jnp.matmul(a, w, precision=HI)  # noqa: E731
    x = params["embed"]["table"][jnp.asarray(tokens)]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for lp in params["layers"]:
        a = lp["attn"]
        h = _rms(x, lp["attn_norm"]["scale"], eps)
        q = (mm(h, a["wq"]["w"]) + a["wq"]["b"]).reshape(t, hq, dh)
        k = (mm(h, a["wk"]["w"]) + a["wk"]["b"]).reshape(t, hkv, dh)
        v = (mm(h, a["wv"]["w"]) + a["wv"]["b"]).reshape(t, hkv, dh)
        q, k = _rope(q, c.rope_theta), _rope(k, c.rope_theta)
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, hq * dh)
        x = x + mm(o, a["wo"]["w"])
        f = lp["ffn"]
        h = _rms(x, lp["ffn_norm"]["scale"], eps)
        x = x + mm(jax.nn.silu(mm(h, f["w1"]["w"])) * mm(h, f["w3"]["w"]),
                   f["w2"]["w"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return mm(x, params["embed"]["table"].T)


# ---- the scheduler against it --------------------------------------------------

def test_reference_matches_the_full_forward(qwen):
    model, params = qwen
    toks = np.random.default_rng(0).integers(0, CFG.vocab_size, 12)
    want = reference_logits(params, toks)
    got, _, _ = model.forward(params, {"tokens": jnp.asarray(toks)[None]})
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def _served_logits(sched, reqs):
    """Serve ``reqs`` (each admitted as soon as a slot is free) and
    return, per request, the logits row each served token was picked
    from."""
    seen = {}
    sample = sched._sample

    def keep(logits):
        seen["rows"] = np.asarray(logits)
        return sample(logits)

    sched._sample = keep
    queue = list(reqs)
    for r in queue:
        sched.normalize(r)
    rows = {r.rid: [] for r in reqs}
    n_steps = n_mixed_with_decode = 0
    while queue or sched.has_work:
        while queue and sched.try_admit(queue[0], 0.0):
            queue.pop(0)
        who = {s: st.req for s, st in sched.active.items()}
        who.update({s: c.req for s, c in sched.chunk_mgr.cursors.items()})
        before = {r.rid: len(r.tokens) for r in who.values()}
        decoding, mixed = bool(sched.active), sched.n_mixed_steps
        sched.step()
        n_steps += 1
        n_mixed_with_decode += decoding and sched.n_mixed_steps > mixed
        for s, r in who.items():
            if len(r.tokens) > before[r.rid]:
                rows[r.rid].append(seen["rows"][s])
    assert sched.n_preemptions == 0
    return rows, n_mixed_with_decode


def test_scheduler_logits_match_the_reference(qwen):
    """Paged, chunked serving: prompts stream in over several mixed steps
    beside live decode rows, then decode; every served token's logits row
    equals the reference's at that position."""
    model, params = qwen
    rng = np.random.default_rng(3)
    lens, outs = [5, 23, 17, 9, 30], [12, 6, 9, 14, 5]
    reqs = [ServeRequest(rid=i, prompt=rng.integers(0, CFG.vocab_size, n),
                         max_new=m) for i, (n, m) in enumerate(zip(lens, outs))]
    sched = Scheduler(model, params, slots=4, pad_to=32, max_new_cap=16,
                      paged=True, block_size=4, chunked=True,
                      prefill_budget=8)
    rows, n_mixed_with_decode = _served_logits(sched, reqs)
    assert n_mixed_with_decode >= 3  # chunks really rode beside decode rows
    for r in reqs:
        assert len(r.tokens) == r.max_new
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1])])
        want = np.asarray(reference_logits(params, seq))[len(r.prompt) - 1:]
        np.testing.assert_allclose(np.stack(rows[r.rid]), want,
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"request {r.rid}")


# ---- the packed step against the unpacked one ----------------------------------

B, C, BS, PAD_TO = 4, 8, 4, 16  # slots, prefill budget, block size, prompt pad

# (t_new, lengths, prompt_len) per step; the blocks hold earlier K/V. The
# packer's bounds hold in each: chunk tokens <= C, at most
# max_chunks(C, prompt_len) rows with t_new >= 2, chunks inside the prompt.
LAYOUTS = {
    "idle_rows": ([0, 1, 0, 5], [0, 7, 3, 2], PAD_TO),
    "width_one_final_chunk": ([1, 4, 1, 0], [9, 0, 15, 0], PAD_TO),
    "one_chunk_fills_the_budget": ([1, 8, 0, 1], [3, 0, 0, 11], PAD_TO),
    "several_chunks": ([2, 2, 3, 1], [0, 2, 0, 6], 4),
    "prefix_adopted_mid_prompt": ([6, 1, 0, 2], [8, 2, 0, 12], PAD_TO),
    "preemption_replay": ([8, 0, 1, 0], [0, 0, 4, 0], PAD_TO),
    "whole_table_keys": ([2, 1, 3, 0], [20, 9, 5, 0], None),
}


def _pool(model, key):
    pool = BlockPool(model, B, PAD_TO + 8 + 1, block_size=BS)
    for s in range(B):
        assert pool.acquire() == s
        assert pool.ensure(s, PAD_TO + 7)
    pool.sync()
    leaves, tree = jax.tree_util.tree_flatten(pool.cache["layers"])
    ks = jax.random.split(key, len(leaves))
    # stale K/V everywhere: what earlier steps (or a preempted attempt)
    # left in the blocks
    pool.cache["layers"] = jax.tree_util.tree_unflatten(
        tree, [jax.random.normal(k, x.shape, x.dtype)
               for k, x in zip(ks, leaves)])
    return pool


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_packed_mixed_step_matches_unpacked(qwen, layout):
    model, params = qwen
    t_new, lengths, prompt_len = LAYOUTS[layout]
    t_new, lengths = jnp.asarray(t_new, jnp.int32), jnp.asarray(lengths,
                                                                jnp.int32)
    pool = _pool(model, jax.random.PRNGKey(7))
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, CFG.vocab_size, (B, C)), jnp.int32)
    copy = lambda: jax.tree.map(jnp.copy, pool.cache)  # noqa: E731
    got, got_cache = engine.mixed_step(model, params, copy(), toks, t_new,
                                       lengths, prompt_len)
    lane, want_cache = engine.verify_step(model, params, copy(), toks, t_new,
                                          lengths)
    want = lane[jnp.arange(B), jnp.maximum(t_new - 1, 0)]
    live = np.asarray(t_new) > 0
    # float32 sums in another order (the decode walk, fewer lanes): a few
    # units in the last place of logits that reach a few hundred
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(got_cache["lengths"], lengths + t_new)
    for g, w in zip(jax.tree.leaves(got_cache["layers"]),
                    jax.tree.leaves(want_cache["layers"])):
        # block 0 is the sink of padding lanes: it holds no token
        np.testing.assert_allclose(np.asarray(g)[1:], np.asarray(w)[1:],
                                   rtol=1e-5, atol=1e-5)


def test_packer_counts_lanes_and_the_step_computes_no_more(qwen):
    """``mixed_lane_trace`` gives each mixed step's real tokens and the
    ``B + C`` lanes it computes; no matmul of the compiled step has
    ``B x C`` rows."""
    model, params = qwen
    rng = np.random.default_rng(4)
    reqs = [ServeRequest(rid=i, prompt=rng.integers(0, CFG.vocab_size, n),
                         max_new=4) for i, n in enumerate([30, 3, 12, 7])]
    sched = Scheduler(model, params, slots=B, pad_to=32, max_new_cap=8,
                      paged=True, block_size=BS, chunked=True,
                      prefill_budget=C)
    sched.run(reqs)
    assert len(sched.mixed_lane_trace) == sched.n_mixed_steps > 0
    for valid, computed in sched.mixed_lane_trace:
        assert 0 < valid <= computed == B + C
    jaxpr = jax.make_jaxpr(engine.mixed_step, static_argnums=(0, 6))(
        model, params, sched.pool.cache, jnp.zeros((B, C), jnp.int32),
        jnp.ones((B,), jnp.int32), jnp.zeros((B,), jnp.int32), 32)
    rows = set()  # rows of every product with a weight matrix

    def subjaxprs(params):
        for v in params.values():
            for x in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(x, "eqns"):
                    yield x
                elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                    yield x.jaxpr

    def walk(jp):
        for eqn in jp.eqns:
            if (eqn.primitive.name == "dot_general"
                    and eqn.invars[1].aval.ndim == 2):
                rows.add(int(np.prod(eqn.invars[0].aval.shape[:-1])))
            for sub in subjaxprs(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert rows and max(rows) <= B + C
