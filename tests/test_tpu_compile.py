"""Compile the Pallas attention kernels of the serving path for a TPU v5e.

The interpret-mode tests in tests/test_kernels.py check what the kernels
compute; Mosaic, the TPU kernel compiler, also checks block shapes,
tiling and fast-memory use, which the interpreter never sees. The TPU
compiler installed with JAX compiles for a chip that is described and not
attached, so these tests lower each kernel with ``interpret=False`` at
Llama-3.2-1B's widths (32 query heads, 8 KV heads, head dim 64, bf16) and
assert that the compiled program holds the kernel (``tpu_custom_call``).

Shapes: a prefill, a chunked-prefill mixed step (16 queries against 64
blocks of 16 gathered keys), the packed mixed step's chunk rows at
Qwen2.5-3B's heads (16 query heads per 2 KV heads), contiguous decode (8
slots, 1024 cached tokens), and both kernels on a 4-chip tensor-parallel
mesh, where the kernels run under shard_map on each chip's own heads
(kernels/ops.py ``_on_own_heads``).

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU compiler's library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas

HQ, HKV, D = 32, 8, 64  # configs/llama3_2_1b.py
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "b,tq,tk,heads",
    [
        (1, 256, 256, (HQ, HKV, D)),  # prefill of a 256-token prompt
        # mixed step: 16-token chunk over 64 blocks of 16
        (8, 16, 64 * 16, (HQ, HKV, D)),
        # the packed mixed step's chunk rows at Qwen2.5-3B's heads (16
        # query heads per 2 KV heads of 128): 3 rows of 512 queries over
        # the 27 prompt blocks, and 2 rows over a whole 652-block table
        (3, 512, 27 * 16, (16, 2, 128)),
        (2, 512, 652 * 16, (16, 2, 128)),
    ],
    ids=["prefill", "chunk", "packed_chunk_rows", "packed_whole_table"],
)
def test_flash_attention_compiles_for_v5e(one_chip, b, tq, tk, heads):
    hq, hkv, d = heads

    def s(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, k, v, qpos, kpos, kval):
        return flash_attention_pallas(
            q, k, v, q_positions=qpos, k_positions=kpos, k_valid=kval,
            causal=True, block_q=512, block_k=512, interpret=False,
        )

    text = _compiled_text(
        fn, s((b, tq, hq, d)), s((b, tk, hkv, d)), s((b, tk, hkv, d)),
        s((b, tq), I32), s((b, tk), I32), s((b, tk), jnp.bool_),
    )
    assert "tpu_custom_call" in text


def test_decode_attention_compiles_for_v5e(one_chip):
    def s(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, k, v, lengths):
        return decode_attention_pallas(q, k, v, lengths, block_k=1024,
                                       interpret=False)

    text = _compiled_text(
        fn, s((8, HQ, D)), s((8, 1024, HKV, D)), s((8, 1024, HKV, D)),
        s((8,), I32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["flash", "decode"])
def test_kernels_compile_on_tp_mesh(topo, monkeypatch, op):
    """On a 4-chip ("model",) mesh each chip runs the kernel on its own 8
    query / 2 KV heads: the program holds the kernel and gathers no K/V."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("model",))
    b, s_len = 8, 1024

    def s(shape, spec, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    heads4, heads3, rep = P(None, None, "model"), P(None, "model"), P()
    k = s((b, s_len, HKV, D), heads4)
    if op == "flash":
        def fn(q, k, v, qpos, kpos, kval):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return ops.flash_attention(
                    q, k, v, q_positions=qpos, k_positions=kpos,
                    k_valid=kval, impl="pallas")

        args = (s((b, 16, HQ, D), heads4), k, k, s((b, 16), rep, I32),
                s((b, s_len), rep, I32), s((b, s_len), rep, jnp.bool_))
    else:
        def fn(q, k, v, lengths):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return ops.decode_attention(q, k, v, lengths, impl="pallas")

        args = (s((b, HQ, D), heads3), k, k, s((b,), rep, I32))
    text = _compiled_text(fn, *args)
    assert "tpu_custom_call" in text
    assert "all-gather" not in text
