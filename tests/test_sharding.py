"""Sharding-rule unit tests (single device: specs only)."""
import jax
from jax.sharding import PartitionSpec as P

from repro.configs import CONFIGS
from repro.distributed import sharding as sh
from repro.models import get_model


def _mesh():
    # 1-device (1,1) mesh: spec construction logic is device-count-free
    return jax.make_mesh((1, 1), ("data", "model"))


class FakeMesh:
    """Shape-only stand-in so spec rules can be tested at production size
    without 512 devices."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESH16 = FakeMesh({"data": 16, "model": 16})
MESHPOD = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_param_specs_llama405b():
    cfg = CONFIGS["llama3-405b"].replace(scan_layers=True)
    model = get_model(cfg)
    ps = model.abstract_params()
    specs = sh.param_specs(cfg, ps, MESH16)
    assert specs["embed"]["table"] == P("model", None)
    assert specs["scanned"]["attn"]["wq"]["w"] == P(None, None, "model")
    assert specs["scanned"]["attn"]["wo"]["w"] == P(None, "model", None)
    assert specs["scanned"]["ffn"]["w1"]["w"] == P(None, None, "model")
    assert specs["scanned"]["ffn"]["w2"]["w"] == P(None, "model", None)
    assert specs["final_norm"]["scale"] == P()
    # GQA KV proj: 8 kv heads * 128 = 1024 % 16 == 0 -> sharded
    assert specs["scanned"]["attn"]["wk"]["w"] == P(None, None, "model")


def test_param_specs_moe_expert_parallel():
    cfg = CONFIGS["qwen3-moe-30b-a3b"].replace(scan_layers=True)
    model = get_model(cfg)
    specs = sh.param_specs(cfg, model.abstract_params(), MESH16)
    # scanned stacks are [L, E, d, f]: expert axis is dim 1
    assert specs["scanned"]["moe"]["w1"] == P(None, "model", None, None)
    assert specs["scanned"]["moe"]["w2"] == P(None, "model", None, None)
    assert specs["scanned"]["moe"]["router"]["w"] == P()


def test_small_models_stay_replicated():
    cfg = CONFIGS["whisper-base"]
    model = get_model(cfg)
    specs = sh.param_specs(cfg, model.abstract_params(), MESH16)
    assert all(s == P() for s in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))


def test_non_divisible_dims_not_sharded():
    # yi-34b: 56 heads * 128 = 7168 % 16 == 0 -> sharded; but a fake mesh
    # with model=13 must refuse every dim that does not divide.
    cfg = CONFIGS["yi-34b"].replace(scan_layers=True)
    model = get_model(cfg)
    specs = sh.param_specs(cfg, model.abstract_params(), FakeMesh({"data": 2, "model": 13}))
    for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        assert s == P()  # nothing divides by 13


def test_batch_spec_divisibility():
    assert sh.batch_spec(MESH16, 256) == P(("data",), None)
    assert sh.batch_spec(MESHPOD, 256) == P(("pod", "data"), None)
    assert sh.batch_spec(MESH16, 1) == P(None, None)  # long_500k
    assert sh.batch_spec(MESHPOD, 33, rank=3) == P(None, None, None)


def test_cache_specs_batch_and_seq_sharding():
    cfg = CONFIGS["llama3.2-1b"].replace(scan_layers=True)
    model = get_model(cfg)
    cache = model.abstract_cache(128, 32768)
    base = sh.cache_specs(cfg, cache, MESH16, 128)
    assert base["scanned"]["k"] == P(None, ("data",), None, None, None)
    assert base["lengths"] == P(("data",))
    seq = sh.cache_specs_seqsharded(cfg, cache, MESH16, 128)
    assert seq["scanned"]["k"] == P(None, ("data",), "model", None, None)


def test_fsdp_upgrade_shards_big_leaves():
    cfg = CONFIGS["llama3-405b"].replace(scan_layers=True)
    model = get_model(cfg)
    ps = model.abstract_params()
    specs = sh.param_specs(cfg, ps, MESH16)
    up = sh.fsdp_upgrade(cfg, ps, specs, MESH16)
    # w1 [L, d, ff]: model on ff, fsdp adds data on d (16384 % 16 == 0)
    assert up["scanned"]["ffn"]["w1"]["w"] == P(None, "data", "model")
    # small leaves unchanged
    assert up["final_norm"]["scale"] == P()


def test_opt_state_specs_follow_params():
    from repro.training import optimizer as opt

    cfg = CONFIGS["llama3-405b"].replace(scan_layers=True)
    model = get_model(cfg)
    ps = model.abstract_params()
    os_ = jax.eval_shape(lambda: opt.init_state(ps, opt.OptimizerConfig()))
    specs = sh.opt_state_specs(cfg, os_, MESH16)
    assert specs.step == P()
    assert specs.mu["scanned"]["ffn"]["w1"]["w"] == P(None, None, "model")
