"""§Perf lever plumbing tests (single-device: spec/struct level)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import CONFIGS, SMOKE_CONFIGS
from repro.core.quantization import quantize_params
from repro.distributed import sharding as sh
from repro.models import get_model


class FakeMesh:
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESH16 = FakeMesh({"data": 16, "model": 16})
# 405B with its 126 layers scanned into one stack: shapes only, never built
CFG405 = CONFIGS["llama3-405b"].replace(scan_layers=True)


def _param_structs(quant=None):
    """ShapeDtypeStruct params of CFG405, AutoQuant'd when ``quant``."""
    params = get_model(CFG405).abstract_params()
    if quant:
        params = jax.eval_shape(lambda p: quantize_params(p, quant), params)
    return params


def test_quantized_step_spec_struct():
    """--quant wo produces int8 weight stacks (3-D scanned aware)."""
    params = _param_structs("wo")
    assert params["scanned"]["ffn"]["w1"]["w_q_wo"].dtype == jnp.int8
    assert params["scanned"]["ffn"]["w1"]["w_q_wo"].shape[0] == 126  # full stack
    total = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
    bf16_total = sum(
        l.size * l.dtype.itemsize for l in jax.tree.leaves(_param_structs())
    )
    assert total < 0.55 * bf16_total  # ~halved weight bytes


def test_quantized_params_still_sharded():
    specs = sh.param_specs(CFG405, _param_structs("wo"), MESH16)
    assert specs["scanned"]["ffn"]["w1"]["w_q_wo"] == P(None, None, "model")


def test_sort_and_cumsum_ranking_identical():
    from repro.models.moe import _position_in_expert

    for seed in range(5):
        flat_e = jax.random.randint(jax.random.PRNGKey(seed), (257,), 0, 8)
        a = _position_in_expert(flat_e, 8, "cumsum")
        b = _position_in_expert(flat_e, 8, "sort")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scale_after_dot_equals_dequant_first():
    from repro.kernels import ops, ref

    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    wq, ws = ops.quantize_int8(w, axis=0)
    a = ops.int8_matmul_weight_only(x, wq, ws, impl="xla")
    b = ref.int8_matmul_ref(x, wq, ws)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ssd_training_gradient_finite():
    """Regression: exp-overflow in the masked SSD triangle NaN'd grads."""
    from repro.training import optimizer as opt
    from repro.training.train_loop import make_train_step

    cfg = SMOKE_CONFIGS["mamba2-130m"]
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ocfg = opt.OptimizerConfig(total_steps=5)
    state = opt.init_state(params, ocfg)
    step = jax.jit(make_train_step(model, ocfg))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, cfg.vocab_size),
    }
    for _ in range(3):
        params, state, metrics = step(params, state, batch)
        assert bool(jnp.isfinite(metrics["loss"])), "SSD loss NaN"
        assert bool(jnp.isfinite(metrics["grad_norm"])), "SSD grad NaN"
