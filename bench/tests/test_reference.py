"""The plain reference against the program at a size a CPU test holds:
the same weights drawn from the key, and logits that agree."""
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


def test_control_rounds_to_int8():
    x = jnp.asarray([[0.1, -1.27, 0.5]])
    q = reference._q8(x, -1)
    assert np.allclose(q, jnp.round(x / 0.01) * 0.01)
