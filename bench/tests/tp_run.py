"""The T-I cell at tensor parallelism 4, at a size a CPU test holds, on
four host devices (run in a process of its own: ``python
bench/tests/tp_run.py [--drop-exchange]``; prints the run's result
line)."""
import os
import sys
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [os.path.join(os.path.dirname(__file__), "..", ".."),
                os.path.join(os.path.dirname(__file__), "..", "..", "src")]

import dataclasses  # noqa: E402
import json  # noqa: E402

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402


def drop_exchange(sched):
    """The exchange between chips left out: every sharded weight keeps
    device 0's shard and zeros elsewhere, so each layer's reduction over
    chips returns one chip's partial result."""
    import jax
    import jax.numpy as jnp

    def one_chip(x):
        shards = x.addressable_shards
        if len({str(s.index) for s in shards}) == 1:
            return x  # replicated
        dev0 = shards[0].device
        bufs = [s.data if s.device == dev0 else jnp.zeros_like(s.data)
                for s in shards]
        return jax.make_array_from_single_device_arrays(x.shape, x.sharding,
                                                        bufs)

    sched.params = jax.tree.map(one_chip, sched.params)


def main(argv):
    harness.enable_compile_cache = lambda: None
    cell = tiny.tiny_cell("chameleon-34b-l6.t2i", num_attention_heads=8,
                          num_key_value_heads=4, head_dim=8, tensor_parallel=4)
    cell = dataclasses.replace(cell, chips=4)
    out = harness.run(cell, 5, 2.0, False, t_start=time.perf_counter(),
                      require_tpu=False, peaks=tiny.PEAKS, log=lambda m: None,
                      fault=drop_exchange if "--drop-exchange" in argv else None)
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
