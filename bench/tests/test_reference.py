"""The plain reference against the program at a size a CPU test holds:
the same weights drawn from the key, and logits that agree."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, spec
from bench.tests import tiny

CONFIGS = {"chameleon-34b-l6": tiny.CHAMELEON, "dense": tiny.DENSE}


def _program(c):
    from repro.models.registry import get_model

    return get_model(spec.model_config(c))


@pytest.mark.parametrize("name", list(CONFIGS))
def test_reference_draws_the_programs_weights(name):
    c = tiny.tiny_config(CONFIGS[name])
    key = jnp.asarray(harness.key_data(2**45 + 3))
    params = _program(c).init(key)
    dm = reference.dims(c)
    ks = jax.random.split(key, dm.n_layers + 3)
    assert np.array_equal(reference.embedding(dm, ks[0]),
                          params["embed"]["table"])
    for i, lp in enumerate(params["layers"]):
        w = reference.layer_weights(dm, ks[2 + i])
        for k in ("wq", "wk", "wv", "wo"):
            assert np.array_equal(w[k], lp["attn"][k]["w"]), k
        for k in ("w1", "w2", "w3"):
            assert np.array_equal(w[k], lp["ffn"][k]["w"]), k


@pytest.mark.parametrize("name", list(CONFIGS))
def test_reference_logits_match_the_program(name):
    c = tiny.tiny_config(CONFIGS[name])
    key = jnp.asarray(harness.key_data(7))
    model = _program(c)
    params = model.init(key)
    toks = np.random.default_rng(0).integers(0, c["vocab_size"], (2, 40))
    want, _, _ = model.forward(params, {"tokens": jnp.asarray(toks)},
                               mode="train", impl="xla")
    dm = reference.dims(c)
    xs = reference.hidden_states(c, key, list(toks.astype(np.int32)), (False,))
    ks = jax.random.split(key, dm.n_layers + 3)
    head = (reference.embedding(dm, ks[0]) if dm.tied
            else reference.head_weights(dm, ks[1]))
    got = reference._head_logits(dm, head, xs[False][:, :40].reshape(-1, dm.d),
                                 False).reshape(2, 40, -1)
    scale = float(jnp.abs(got).max())
    assert float(jnp.abs(got - want).max()) < 0.05 * scale


@pytest.mark.parametrize("quant", [False, True], ids=["reference", "control"])
def test_chunked_layers_match_one_call(monkeypatch, quant):
    """Layers run over chunks of CHUNK_ROWS sequences, the last one padded,
    give the hidden states of one call over every row."""
    c = tiny.tiny_config(tiny.CHAMELEON)
    key = jnp.asarray(harness.key_data(2**40 + 11))
    rng = np.random.default_rng(5)
    n = reference.CHUNK_ROWS + 3  # not a multiple of the chunk
    seqs = [rng.integers(0, c["vocab_size"], int(k)).astype(np.int32)
            for k in rng.integers(20, 150, n)]
    got = reference.hidden_states(c, key, seqs, (quant,))[quant]
    monkeypatch.setattr(reference, "CHUNK_ROWS", n)
    want = reference.hidden_states(c, key, seqs, (quant,))[quant]
    assert got.shape == want.shape == (n, 2 * reference.BQ, c["hidden_size"])
    err = float(jnp.abs(got - want).max())
    assert err <= 1e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("quant", [False, True], ids=["reference", "control"])
def test_chunk_program_fits_at_full_length(quant):
    """The per-chunk layer program at the T-I cell's widths and its longest
    sequence (22 prompt tokens + 1025 served, padded to 1152) holds its
    arguments, temporaries and output in under 8 GB (CPU compile)."""
    c = spec.load_json(spec.BENCH_DIR / "configs" / "chameleon-34b-l6.json")
    dm = reference.dims(c)
    w = jax.eval_shape(functools.partial(reference.layer_weights, dm),
                       jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((reference.CHUNK_ROWS, 9 * reference.BQ, dm.d),
                             jnp.float32)
    ma = reference.layer_forward.lower(dm, w, x, quant).compile() \
        .memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes)
    assert total < 8e9, total


def test_control_rounds_to_int8():
    x = jnp.asarray([[0.1, -1.27, 0.5]])
    q = reference._q8(x, -1)
    assert np.allclose(q, jnp.round(x / 0.01) * 0.01)
