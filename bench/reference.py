"""Plain reference of the benchmark's decoder (float32, matmuls at
``Precision.HIGHEST``), independent of the program: it imports nothing of
``repro`` and takes nothing the program made. It draws the weights again
from the run's key, layer by layer, by the recipe the configuration
states (see ``assumed.weights`` in each configuration file), so that a
model larger than the chip in float32 still fits; each layer runs over
fixed chunks of ``CHUNK_ROWS`` sequences, so that its temporaries do not
grow with the sample.

Block (as the program runs it; each configuration file lists where that
departs from the published model): pre-norm RMSNorm residual blocks,
q/k/v projections (optional bias), optional RMSNorm over each head's
values for q and k, rotate-half RoPE, causal grouped-query softmax
attention, output projection, SwiGLU FFN, final RMSNorm, untied head or
the tied embedding.

``quant=True`` is the control: the same computation with every matmul
operand rounded to int8 (symmetric, one scale per row of the activation
and per output column of the weight), the precision below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BQ = 128  # query rows per attention block; rows pad to a multiple of it
HEAD_ROWS = 256  # logit rows per head block
CHUNK_ROWS = 8  # sequences per layer call; the last chunk pads to it


class Dims(NamedTuple):
    d: int
    hq: int
    hkv: int
    dh: int
    ff: int
    n_layers: int
    vocab: int
    eps: float
    theta: float
    qk_norm: bool
    qkv_bias: bool
    tied: bool


def dims(c: Dict[str, Any]) -> Dims:
    return Dims(
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
        c["head_dim"], c["intermediate_size"], c["num_hidden_layers"],
        c["vocab_size"], float(c["rms_norm_eps"]), float(c["rope_theta"]),
        bool(c["qk_layernorm"]), bool(c["qkv_bias"]),
        bool(c["tie_word_embeddings"]),
    )


# ---- weights, drawn again from the key --------------------------------------

def _normal(key, shape, scale):
    w = jax.random.normal(key, shape, jnp.float32)
    if scale is not None:
        w = w * scale
    return w.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(0,))
def layer_weights(dm: Dims, key) -> Dict[str, jnp.ndarray]:
    """One layer's projections (bfloat16, as served); the key is the
    layer's own, ``split(run_key, n_layers + 3)[2 + i]``. Biases are zero
    and norm weights one, so they are left out."""
    ka, kf = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(ka, 4)
    k1, k3, k2 = jax.random.split(kf, 3)
    d, hq, hkv, dh, ff = dm.d, dm.hq, dm.hkv, dm.dh, dm.ff
    return {
        "wq": _normal(kq, (d, hq * dh), d ** -0.5),
        "wk": _normal(kk, (d, hkv * dh), d ** -0.5),
        "wv": _normal(kv, (d, hkv * dh), d ** -0.5),
        "wo": _normal(ko, (hq * dh, d), (hq * dh) ** -0.5),
        "w1": _normal(k1, (d, ff), d ** -0.5),
        "w3": _normal(k3, (d, ff), d ** -0.5),
        "w2": _normal(k2, (ff, d), ff ** -0.5),
    }


@functools.partial(jax.jit, static_argnums=(0,))
def embedding(dm: Dims, key):
    return _normal(key, (dm.vocab, dm.d), None)


@functools.partial(jax.jit, static_argnums=(0,))
def head_weights(dm: Dims, key):
    return _normal(key, (dm.d, dm.vocab), dm.d ** -0.5)


# ---- forward ----------------------------------------------------------------

def _q8(x, axis):
    """Round to int8 with one symmetric scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def _mm(x, w, quant):
    """x [..., k] @ w [k, n] in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x [N, T, H, D], pos [T]; rotate-half."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * freqs  # [T, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, quant):
    """Causal grouped-query attention, q [N, T, Hq, D], k/v [N, T, Hkv, D],
    in blocks of BQ query rows (keys up to each block's end)."""
    n, t, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    if quant:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 1)
    outs = []
    for q0 in range(0, t, BQ):
        q1 = min(q0 + BQ, t)
        qb = q[:, q0:q1].reshape(n, q1 - q0, hkv, g, dh) * dh ** -0.5
        kb, vb = k[:, :q1], v[:, :q1]
        s = jnp.einsum("nqhgd,nkhd->nhgqk", qb, kb, precision=HI)
        causal = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant:
            p = _q8(p, -1)
        o = jnp.einsum("nhgqk,nkhd->nqhgd", p, vb, precision=HI)
        outs.append(o.reshape(n, q1 - q0, hq * dh))
    return jnp.concatenate(outs, axis=1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def layer_forward(dm: Dims, w, x, quant: bool):
    """One pre-norm block on x [N, T, d] (float32)."""
    n, t, _ = x.shape
    pos = jnp.arange(t)
    h = _rmsnorm(x, dm.eps)
    q = _mm(h, w["wq"], quant).reshape(n, t, dm.hq, dm.dh)
    k = _mm(h, w["wk"], quant).reshape(n, t, dm.hkv, dm.dh)
    v = _mm(h, w["wv"], quant).reshape(n, t, dm.hkv, dm.dh)
    if dm.qk_norm:
        q, k = _rmsnorm(q, dm.eps), _rmsnorm(k, dm.eps)
    q, k = _rope(q, pos, dm.theta), _rope(k, pos, dm.theta)
    x = x + _mm(_attention(q, k, v, quant), w["wo"], quant)
    h = _rmsnorm(x, dm.eps)
    a = jax.nn.silu(_mm(h, w["w1"], quant)) * _mm(h, w["w3"], quant)
    return x + _mm(a, w["w2"], quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _head_logits(dm: Dims, head, rows, quant: bool):
    """Logits [R, V] of final hidden rows [R, d]; ``head`` is [d, V] (or
    the tied [V, d] table)."""
    h = _rmsnorm(rows, dm.eps)
    w = head.T if dm.tied else head
    return _mm(h, w, quant)


# ---- the comparison ---------------------------------------------------------

class Item(NamedTuple):
    """One served request: its prompt and the tokens the program served."""

    prompt: np.ndarray
    served: np.ndarray


def _sequences(items: Sequence[Item], decoding: Dict[str, Any]):
    """Rows to run (prompt + served tokens but the last) and, per served
    token, the row(s) and position whose logits chose it."""
    seqs, reads = [], []  # reads: (cond row, uncond row or -1, pos, token)
    cfg = decoding["profile"] == "contrastive"
    for it in items:
        n_p = len(it.prompt)
        body = np.concatenate([it.prompt, it.served[:-1]]).astype(np.int32)
        c = len(seqs)
        seqs.append(body)
        u = -1
        if cfg:
            u = len(seqs)
            seqs.append(np.concatenate(
                [np.full(n_p, decoding["uncond_token"], np.int32),
                 it.served[:-1].astype(np.int32)]))
        for j, tok in enumerate(it.served):
            reads.append((c, u, n_p - 1 + j, int(tok)))
    return seqs, reads


def _combine(c_logits, u_logits, decoding, image_offset):
    """The logits the program's decoding profile picks from."""
    if decoding["profile"] != "contrastive":
        return c_logits
    g = decoding["guidance"]
    mixed = u_logits + g * (c_logits - u_logits)
    if decoding.get("mask") == "image":
        ids = jnp.arange(mixed.shape[-1])
        mixed = jnp.where(ids[None] >= image_offset, mixed, -jnp.inf)
    return mixed


def hidden_states(c: Dict[str, Any], key, seqs: List[np.ndarray],
                  quants: Sequence[bool]) -> Dict[bool, jnp.ndarray]:
    """Final-layer hidden states [N, T, d] of ``seqs`` (right-padded to a
    multiple of BQ) for each precision in ``quants``, one layer's weights
    on the device at a time, each layer run over chunks of CHUNK_ROWS
    sequences (the last padded with rows of token 0, so that one
    program serves every chunk)."""
    dm = dims(c)
    n = len(seqs)
    t = -(-max(len(s) for s in seqs) // BQ) * BQ
    toks = np.zeros((-(-n // CHUNK_ROWS) * CHUNK_ROWS, t), np.int32)
    for i, s in enumerate(seqs):
        toks[i, : len(s)] = s
    ks = jax.random.split(key, dm.n_layers + 3)
    table = embedding(dm, ks[0])
    xs = {q: [jnp.take(table, jnp.asarray(toks[r : r + CHUNK_ROWS]), axis=0)
              .astype(jnp.float32) for r in range(0, len(toks), CHUNK_ROWS)]
          for q in quants}
    del table
    for i in range(dm.n_layers):
        w = layer_weights(dm, ks[2 + i])
        for q, chunks in xs.items():
            for j, x in enumerate(chunks):
                # one chunk in flight: dispatch would otherwise run ahead
                # and hold every layer's weights and outputs at once
                chunks[j] = layer_forward(dm, w, x, q).block_until_ready()
        del w
    out = {}
    for q in quants:  # one precision's chunks at a time beside its copy
        chunks = xs.pop(q)
        chunks[-1] = chunks[-1][: n - (len(chunks) - 1) * CHUNK_ROWS]
        out[q] = jnp.concatenate(chunks)
    return out


def logit_gaps(c: Dict[str, Any], key, items: Sequence[Item],
               decoding: Dict[str, Any], image_offset: Optional[int],
               control: bool = False) -> Dict[str, np.ndarray]:
    """For each served token, how far the reference's logit of it lies
    below the reference's best (``served``); with ``control``, also the
    same gap of the token the int8 control puts first (``control``)."""
    dm = dims(c)
    seqs, reads = _sequences(items, decoding)
    quants = (False, True) if control else (False,)
    xs = hidden_states(c, key, seqs, quants)
    ks = jax.random.split(key, dm.n_layers + 3)
    head = embedding(dm, ks[0]) if dm.tied else head_weights(dm, ks[1])
    out: Dict[str, List[np.ndarray]] = {"served": [], "control": []}
    for r0 in range(0, len(reads), HEAD_ROWS):
        blk = reads[r0 : r0 + HEAD_ROWS]
        ci = jnp.asarray([r[0] for r in blk])
        ui = jnp.asarray([max(r[1], 0) for r in blk])
        pos = jnp.asarray([r[2] for r in blk])
        tok = jnp.asarray([r[3] for r in blk])
        mixed = {}
        for q, x in xs.items():
            lc = _head_logits(dm, head, x[ci, pos], q)
            lu = _head_logits(dm, head, x[ui, pos], q)
            mixed[q] = _combine(lc, lu, decoding, image_offset)
        ref = mixed[False]
        best = ref.max(axis=-1)
        pick = jnp.take_along_axis(ref, tok[:, None], axis=1)[:, 0]
        out["served"].append(np.asarray(best - pick))
        if control:
            top = jnp.argmax(mixed[True], axis=-1)
            ctl = jnp.take_along_axis(ref, top[:, None], axis=1)[:, 0]
            out["control"].append(np.asarray(best - ctl))
    return {k: np.concatenate(v) for k, v in out.items() if v}
