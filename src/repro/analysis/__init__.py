"""repro.analysis — static analysis locking down the serving hot path.

The paper's central characterization is that auto-regressive generation
latency is dominated by accelerator *idle* time, not FLOPs (Obs #2), and
the serving stack built in PRs 2-5 holds that idle time down with three
disciplines that no functional test can see breaking:

1. **one executable, replayed forever** (§4.1.2): every decode step must
   hit the jit cache — a silent retrace (shape drift, a weak cache key)
   turns one step into a multi-second compile stall;
2. **donated KV buffers**: an undonated cache-sized jit argument doubles
   peak KV memory, which directly shrinks how many requests the block
   pool can hold resident (Fig 1: KV capacity bounds the decode batch);
3. **no stray host syncs in the per-token loop**: every `.item()` /
   `np.asarray` / `bool()` on a device value inside the step loop blocks
   the host on the device stream and re-opens the idle bubbles
   continuous batching exists to close.

"Inference Optimization of Foundation Models on AI Accelerators"
(PAPERS.md) calls the same compilation/memory discipline a first-order
lever on accelerators. This package enforces all three as *machine
checks* so future PRs (multi-host, speculative decoding, Pallas kernels)
land against invariants instead of re-discovering these bug classes at
benchmark time.

Two complementary layers, both run by ``python -m repro.analysis``:

**Layer 1 — AST lint** (:mod:`repro.analysis.astlint`): a rule-based
walker over ``src/repro``. Rules, each keyed by an ID that a
``# repro-lint: disable=<ID>`` comment (same line, or a standalone
comment on the line above) suppresses with justification:

- ``HS001`` — host sync inside a serving hot-path function: calls to
  ``np.asarray``/``np.array``, ``.item()``, ``.block_until_ready()``,
  or ``float()``/``int()``/``bool()`` casts inside a function marked
  hot (the ``@hot_path`` decorator or the
  :data:`repro.analysis.hotpath.HOT_PATHS` registry — Scheduler.step
  internals, ``engine.decode_step``/``mixed_step``/``run_profile``).
  ``jax.device_get`` is the one sanctioned sync idiom: it is explicit,
  batches an arbitrary pytree into ONE transfer, and is what the
  scheduler's single per-step sync uses — the fix for an HS001 is
  almost always "fold this into the existing device_get".
- ``DN001`` — a ``jax.jit`` call site whose wrapped function takes a
  KV/cache-typed parameter (name matching ``cache|pool|kv|buf``) that
  ``donate_argnums``/``donate_argnames`` does not cover. Undonated
  cache-sized buffers are invariant #2 above.
- ``TB001`` — Python-level control flow on traced values inside a
  jitted function: an ``if``/``while`` whose test reads a non-static
  parameter, or a ``bool()``/``int()``/``float()`` cast. These either
  crash (ConcretizationTypeError) or — worse — silently bake one
  branch into the executable and make the jit cache key lie.
  ``x is None`` tests are exempt (argument *presence* is static).

Findings are matched against a checked-in baseline
(``src/repro/analysis/baseline.json``): pre-existing findings don't
block CI, new ones fail it. The baseline's goal state is empty — every
justified exception belongs in a suppression comment next to the code
it excuses, not in the baseline.

**Layer 2 — trace audit** (:mod:`repro.analysis.trace_audit`): imports
the real smoke configs, lowers the serving executables (``prefill``,
``decode_step``, ``mixed_step``, contiguous and paged) and asserts
machine-checkable invariants on the lowered artifact:

- **donation coverage** — every non-exempt argument buffer above a size
  threshold is donated AND actually aliased to an output in the lowered
  module (``tf.aliasing_output``); params are the one exempt argument;
- **no shape growth** — no intermediate tensor larger than the largest
  signature (input/output) tensor, plus the paged-specific ban on the
  full gathered ``[slots, max_blocks*block_size, ...]`` K/V transient;
- **stable jit cache keys** — serving a second, different trace through
  an already-warm scheduler adds ZERO new executables: the recompile
  counter equals the number of distinct executables the config needs;
- **no dtype widening** — no ``f64`` anywhere, and no
  ``stablehlo.convert`` that widens a cache-sized bf16/f16/int8 tensor
  to f32 (small deliberate upcasts — logits, LSE accumulators — sit
  below the threshold).

Run it locally before sending a serving-path PR::

    PYTHONPATH=src python -m repro.analysis            # both layers
    PYTHONPATH=src python -m repro.analysis --ast-only
    PYTHONPATH=src python -m repro.analysis --write-baseline

CI runs the same entry point (the ``analysis`` job) and fails on any
non-baseline lint finding or trace-audit violation.
"""
from __future__ import annotations

__all__ = ["astlint", "hotpath", "trace_audit"]
