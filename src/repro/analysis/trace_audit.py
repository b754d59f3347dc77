"""Layer 2: lower the real serving executables and audit the artifact.

The AST lint (Layer 1) reads source; this module reads what XLA will
actually run. It lowers the serving executables — ``engine.prefill``,
``engine.decode_step``, ``engine.mixed_step``, plus the speculative
pair ``layerskip.draft_window`` / ``engine.verify_step``, contiguous
and paged — for the smoke serving configuration below, and asserts four
invariants on the lowered StableHLO:

- **donation coverage** (:func:`audit_donation`): every non-exempt
  argument leaf at least ``min_bytes`` big is donated AND the module
  carries at least that many ``tf.aliasing_output`` argument attributes
  (donation that XLA silently dropped is a finding, not a pass);
- **no shape growth** (:func:`audit_no_growth`): no dynamic dims, no
  intermediate tensor larger than ``slack`` x the largest *signature*
  (argument) tensor, and none of the caller's forbidden shape patterns
  — :func:`paged_growth_patterns` bans the full gathered
  ``[slots, max_blocks*block_size, ...]`` K/V transient, which the
  general envelope alone cannot see (the embed table out-sizes it);
- **no dtype widening** (:func:`audit_dtypes`): no ``f64`` anywhere and
  no ``stablehlo.convert`` producing an f32 tensor at least
  ``widen_min_bytes`` big from a bf16/f16/int8 source — cache-sized
  upcasts double KV bytes, while small deliberate ones (logits, LSE
  accumulators) sit below the threshold;
- **stable jit cache keys** (:func:`audit_recompiles`): serving a
  second, different trace through a second scheduler of the same
  geometry compiles ZERO new executables — the cache-size counters of
  every serving jit are unchanged.

All audit functions return a list of human-readable failure strings
(empty = clean); :func:`run_trace_audit` runs the whole matrix and is
what ``python -m repro.analysis`` and CI call.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# The smoke serving geometry the audit lowers (llama3.2-1b smoke widths,
# a 4-slot pool of 16-token blocks).
ARCH = "llama3.2-1b"
SLOTS = 4
PAD_TO = 16
MAX_NEW_CAP = 64
BLOCK_SIZE = 16
NUM_BLOCKS = 14
PREFILL_BUDGET = 4
# speculative-step geometry (SpeculativeProfile defaults; the smoke arch
# has 2 layers, so exit_layer=1 is the only valid early exit)
EXIT_LAYER = 1
N_DRAFT = 4

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i1": 1,
}
_TENSOR_RE = re.compile(r"tensor<([^>]+)>")
_CONVERT_RE = re.compile(
    r"stablehlo\.convert\s+[^:\n]+:\s*\(tensor<([^>]+)>\)\s*->\s*tensor<([^>]+)>"
)
_NARROW = ("bf16", "f16", "i8", "ui8")


def _parse_tensor(spec: str) -> Optional[Tuple[Tuple[str, ...], str]]:
    """``"4x8xf32"`` -> (("4", "8"), "f32"); None for non-numeric specs."""
    parts = spec.split("x")
    dtype = parts[-1]
    if dtype not in _DTYPE_BYTES:
        return None
    return tuple(parts[:-1]), dtype


def _tensor_bytes(dims: Sequence[str], dtype: str) -> Optional[int]:
    """Byte size, or None when any dim is dynamic (``?``)."""
    n = _DTYPE_BYTES[dtype]
    for d in dims:
        if not d.isdigit():
            return None
        n *= int(d)
    return n


def _arg_trees(lowered) -> List:
    """Top-level argument trees of a Lowered (static args already
    dropped); index i here is what ``exempt_args`` refers to."""
    info = lowered.args_info
    if isinstance(info, tuple) and len(info) == 2 and isinstance(info[1], dict):
        info = info[0]
    return list(info)


def _leaf_nbytes(leaf) -> int:
    return int(np.prod(leaf.shape, dtype=np.int64)) * np.dtype(leaf.dtype).itemsize


# --------------------------------------------------------------------------
# audits over one lowered executable
# --------------------------------------------------------------------------

def audit_donation(lowered, *, exempt_args: Sequence[int] = (0,),
                   min_bytes: int = 1 << 15, label: str = "") -> List[str]:
    """Every non-exempt argument leaf >= ``min_bytes`` must be donated,
    and the lowered text must alias at least that many arguments to
    outputs (``tf.aliasing_output``). ``exempt_args`` indexes the
    NON-STATIC argument tuple (params — read-only by design — is arg 0
    for every serving executable here)."""
    import jax.tree_util as jtu

    fails: List[str] = []
    required = 0
    for i, tree in enumerate(_arg_trees(lowered)):
        if i in exempt_args:
            continue
        for leaf in jtu.tree_leaves(tree):
            if _leaf_nbytes(leaf) < min_bytes:
                continue
            required += 1
            if not leaf.donated:
                fails.append(
                    f"{label}: arg {i} leaf {leaf.shape}/{leaf.dtype} "
                    f"({_leaf_nbytes(leaf)}B) is not donated"
                )
    text = lowered.as_text()
    # single-device modules carry pre-resolved input->output aliases
    # (tf.aliasing_output); sharded modules defer the pairing to compile
    # time and mark donors with jax.buffer_donor instead — both mean the
    # argument's buffer is surrendered to the executable
    aliased = (text.count("tf.aliasing_output")
               + text.count("jax.buffer_donor"))
    if aliased < required:
        fails.append(
            f"{label}: only {aliased} arguments aliased to outputs in the "
            f"lowered module but {required} large leaves require donation "
            f"(XLA dropped a donation — shape/dtype mismatch with the "
            f"output it should alias?)"
        )
    return fails


def donation_summary(lowered) -> Dict[str, int]:
    """Leaf-count summary of one lowered executable: how many argument
    leaves it takes, how many are donated, and how many donations the
    compiled text actually aliases to outputs. A drop in
    ``donated_leaves``/``aliased_outputs`` means a donation silently
    regressed even if sizes stayed under the audit_donation
    threshold."""
    import jax.tree_util as jtu

    leaves = [l for t in _arg_trees(lowered) for l in jtu.tree_leaves(t)]
    return {
        "arg_leaves": len(leaves),
        "donated_leaves": sum(1 for l in leaves if l.donated),
        "aliased_outputs": lowered.as_text().count("tf.aliasing_output"),
    }


def audit_no_growth(lowered, *, slack: float = 1.25,
                    forbidden: Sequence[str] = (),
                    label: str = "") -> List[str]:
    """No dynamic dims, no intermediate above ``slack`` x the largest
    argument tensor, and no ``forbidden`` shape pattern (substring of a
    ``tensor<...>`` type) anywhere in the lowered text."""
    import jax.tree_util as jtu

    fails: List[str] = []
    text = lowered.as_text()
    sig = max(
        (_leaf_nbytes(l) for t in _arg_trees(lowered)
         for l in jtu.tree_leaves(t)),
        default=0,
    )
    worst: Tuple[int, str] = (0, "")
    for m in _TENSOR_RE.finditer(text):
        parsed = _parse_tensor(m.group(1))
        if parsed is None:
            continue
        dims, dtype = parsed
        nbytes = _tensor_bytes(dims, dtype)
        if nbytes is None:
            fails.append(
                f"{label}: dynamic shape tensor<{m.group(1)}> in the "
                f"lowered module — the executable's signature can drift"
            )
            continue
        if nbytes > worst[0]:
            worst = (nbytes, m.group(1))
    if sig and worst[0] > slack * sig:
        fails.append(
            f"{label}: intermediate tensor<{worst[1]}> ({worst[0]}B) "
            f"exceeds {slack}x the largest signature tensor ({sig}B) — "
            f"a materialized transient the static envelope did not budget"
        )
    for pat in forbidden:
        if pat in text:
            fails.append(
                f"{label}: forbidden shape pattern {pat!r} appears in the "
                f"lowered module (full gathered K/V transient)"
            )
    return fails


def audit_dtypes(lowered_or_text, *, widen_min_bytes: int = 1 << 15,
                 allow: Sequence[str] = (), label: str = "") -> List[str]:
    """No f64 anywhere; no cache-sized f32 widening of a narrow dtype.

    ``allow`` holds substring patterns of convert DESTINATIONS that are
    sanctioned deliberate numerics (e.g. the unembed's logits-in-f32
    table upcast). Each caller-supplied pattern should carry a comment
    at the call site saying why the widening is intended."""
    text = (lowered_or_text if isinstance(lowered_or_text, str)
            else lowered_or_text.as_text())
    fails: List[str] = []
    for m in _TENSOR_RE.finditer(text):
        parsed = _parse_tensor(m.group(1))
        if parsed and parsed[1] == "f64":
            fails.append(
                f"{label}: f64 tensor<{m.group(1)}> in the lowered module "
                f"(accelerators pay 2x bytes and often emulate f64)"
            )
            break
    for m in _CONVERT_RE.finditer(text):
        src, dst = _parse_tensor(m.group(1)), _parse_tensor(m.group(2))
        if not src or not dst:
            continue
        if src[1] in _NARROW and dst[1] == "f32":
            if any(pat in f"tensor<{m.group(2)}>" for pat in allow):
                continue
            nbytes = _tensor_bytes(dst[0], dst[1])
            if nbytes is not None and nbytes >= widen_min_bytes:
                fails.append(
                    f"{label}: {src[1]}->f32 widening of tensor<"
                    f"{m.group(2)}> ({nbytes}B >= {widen_min_bytes}B) — a "
                    f"cache-sized upcast doubles the bytes the narrow "
                    f"path exists to save"
                )
    return fails


def paged_growth_patterns(slots: int, max_blocks: int,
                          block_size: int) -> List[str]:
    """Shape patterns of the full gathered per-slot K/V transient a paged
    DECODE step must never materialize — neither the flat
    [B, MB*bs, ...] form nor its pre-reshape [B, MB, bs, ...] form.
    (The mixed step legitimately gathers via ``paged_gather`` for its
    chunk lanes, so this ban applies to the decode executable only.)"""
    return [f"tensor<{slots}x{max_blocks * block_size}x",
            f"tensor<{slots}x{max_blocks}x{block_size}x"]


# --------------------------------------------------------------------------
# recompile stability across real traces
# --------------------------------------------------------------------------

def _cache_sizes(fns: Dict[str, object]) -> Dict[str, int]:
    return {name: fn._cache_size() for name, fn in fns.items()}


def serving_jits() -> Dict[str, object]:
    """The jitted executables whose cache sizes a serving trace may
    legitimately grow while warming — and must NOT grow afterwards."""
    from repro.core import engine, kv_cache, layerskip

    return {
        "engine.prefill": engine.prefill,
        "engine.decode_step": engine.decode_step,
        "engine.mixed_step": engine.mixed_step,
        "engine.verify_step": engine.verify_step,
        "layerskip.draft_window": layerskip.draft_window,
        # the tensor-parallel twins (distributed/tp_pool.py): warmed only
        # when a Scheduler(tp_mesh=...) serves, zero-size otherwise —
        # held to the same no-recompile bar either way
        "engine.tp_prefill": engine.tp_prefill,
        "engine.tp_decode_step": engine.tp_decode_step,
        "engine.tp_mixed_step": engine.tp_mixed_step,
        "engine.tp_verify_step": engine.tp_verify_step,
        "layerskip.tp_draft_window": layerskip.tp_draft_window,
        "kv_cache.write_slot": kv_cache.write_slot,
        "kv_cache.reset_slots": kv_cache.reset_slots,
        "kv_cache.append_block": kv_cache.append_block,
        "kv_cache.copy_block": kv_cache.copy_block,
        "kv_cache.set_slot_length": kv_cache.set_slot_length,
        "kv_cache.reorder_donated": kv_cache.reorder_donated,
    }


def audit_recompiles(model, params, *, slots: int = SLOTS,
                     pad_to: int = PAD_TO, max_new_cap: int = MAX_NEW_CAP,
                     block_size: int = BLOCK_SIZE,
                     num_blocks: int = NUM_BLOCKS,
                     prefill_budget: int = PREFILL_BUDGET,
                     n_requests: int = 8) -> List[str]:
    """Serve one paged+chunked smoke trace to warm every executable, then
    a second, different trace (new lengths, arrivals, prompts) through a
    FRESH scheduler of the same geometry — if jit cache keys are stable,
    the second trace compiles nothing: every per-executable cache size
    stays exactly where warming left it. The trace alternates plain and
    speculative requests so the draft/verify pair is held to the same
    zero-recompile bar as the rest of the hot path."""
    from repro.launch import serve
    from repro.training import data as data_mod

    prof = data_mod.PAPER_PROFILES["seamless_s2t"]

    def run(seed: int) -> None:
        reqs = serve.poisson_trace(
            prof, n_requests, pad_to=pad_to, max_new_cap=max_new_cap,
            vocab_size=model.config.vocab_size, arrival_rate=200.0,
            seed=seed,
        )
        serve.apply_profile_mix(
            reqs, "greedy,speculative",
            exit_layer=EXIT_LAYER, n_draft=N_DRAFT,
        )
        serve.run_scheduler(
            model, params, reqs, slots=slots, pad_to=pad_to,
            max_new_cap=max_new_cap, policy="continuous", paged=True,
            block_size=block_size, num_blocks=num_blocks, chunked=True,
            prefill_budget=prefill_budget, seed=seed,
        )

    fns = serving_jits()
    run(seed=0)  # warm: every distinct executable compiles here
    warm = _cache_sizes(fns)
    run(seed=1)  # different trace, same geometry: must replay, not compile
    cold = _cache_sizes(fns)
    fails = [
        f"recompile: {name} compiled {cold[name] - warm[name]} new "
        f"executable(s) on a second same-geometry trace (cache {warm[name]} "
        f"-> {cold[name]}) — its jit cache key is unstable"
        for name in fns if cold[name] != warm[name]
    ]
    return fails


# --------------------------------------------------------------------------
# the config-matrix entry point
# --------------------------------------------------------------------------

def lower_serving(model, params, *, paged: bool, slots: int = SLOTS,
                  pad_to: int = PAD_TO, max_new_cap: int = MAX_NEW_CAP,
                  block_size: int = BLOCK_SIZE, num_blocks: int = NUM_BLOCKS,
                  prefill_budget: int = PREFILL_BUDGET) -> Dict[str, object]:
    """Lower the serving executables for one pool configuration; returns
    ``{name: Lowered}``. The cache argument comes from a real pool, so
    the lowered signatures are exactly what serving replays."""
    import jax.numpy as jnp

    from repro.core import engine, layerskip
    from repro.core.slot_pool import BlockPool, SlotPool

    max_len = pad_to + max_new_cap + 1
    if paged:
        pool = BlockPool(model, slots, max_len, block_size=block_size,
                         num_blocks=num_blocks)
    else:
        pool = SlotPool(model, slots, max_len)
    out = {
        "prefill": engine.prefill.lower(
            model, params, jnp.zeros((1, pad_to), jnp.int32),
            jnp.ones((1,), jnp.int32), max_len, None,
        ),
        "decode_step": engine.decode_step.lower(
            model, params, pool.cache, jnp.zeros((slots,), jnp.int32),
        ),
    }
    if paged:
        out["mixed_step"] = engine.mixed_step.lower(
            model, params, pool.cache,
            jnp.zeros((slots, prefill_budget), jnp.int32),
            jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots,), jnp.int32),
        )
    # the speculative step pair serves BOTH pool kinds (contiguous
    # verify is a masked window scatter, paged reuses the mixed-step
    # write/gather machinery)
    out["draft_window"] = layerskip.draft_window.lower(
        model, EXIT_LAYER, N_DRAFT, params, pool.cache,
        jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.int32),
        jnp.zeros((slots,), jnp.int32),
    )
    out["verify_step"] = engine.verify_step.lower(
        model, params, pool.cache,
        jnp.zeros((slots, N_DRAFT + 1), jnp.int32),
        jnp.zeros((slots,), jnp.int32),
        jnp.zeros((slots,), jnp.int32),
    )
    out["_pool"] = pool
    return out


def lower_serving_tp(model, params, *, tp: int = 2, slots: int = SLOTS,
                     pad_to: int = PAD_TO, max_new_cap: int = MAX_NEW_CAP,
                     block_size: int = BLOCK_SIZE,
                     num_blocks: int = NUM_BLOCKS,
                     prefill_budget: int = PREFILL_BUDGET
                     ) -> Dict[str, object]:
    """Lower the tensor-parallel step family over a real ``tp``-device
    mesh with COMMITTED sharded params + pool cache — the lowered
    signatures (and their donation/aliasing) are exactly what a
    ``Scheduler(tp_mesh=...)`` replays. Requires >= ``tp`` devices."""
    import jax.numpy as jnp

    from repro.core import engine, layerskip
    from repro.core.slot_pool import BlockPool
    from repro.distributed import tp_pool

    max_len = pad_to + max_new_cap + 1
    mesh = tp_pool.make_tp_mesh(tp)
    pool = BlockPool(model, slots, max_len, block_size=block_size,
                     num_blocks=num_blocks)
    ctx = tp_pool.TPContext(model, params, mesh,
                            cache_like=pool.cache, max_len=max_len)
    cache = ctx.place_cache(pool.cache)
    out = {
        "tp_prefill": engine.tp_prefill.lower(
            model, ctx.params, jnp.zeros((1, pad_to), jnp.int32),
            jnp.ones((1,), jnp.int32), max_len, None,
            row_shardings=ctx.row_static,
        ),
        "tp_decode_step": engine.tp_decode_step.lower(
            model, ctx.params, cache, jnp.zeros((slots,), jnp.int32),
            shardings=ctx.cache_static,
        ),
        "tp_mixed_step": engine.tp_mixed_step.lower(
            model, ctx.params, cache,
            jnp.zeros((slots, prefill_budget), jnp.int32),
            jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots,), jnp.int32),
            shardings=ctx.cache_static,
        ),
        "tp_draft_window": layerskip.tp_draft_window.lower(
            model, EXIT_LAYER, N_DRAFT, ctx.params, cache,
            jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots,), jnp.int32),
            shardings=ctx.cache_static,
        ),
        "tp_verify_step": engine.tp_verify_step.lower(
            model, ctx.params, cache,
            jnp.zeros((slots, N_DRAFT + 1), jnp.int32),
            jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots,), jnp.int32),
            shardings=ctx.cache_static,
        ),
    }
    out["_pool"] = pool
    return out


def run_trace_audit(verbose: bool = False,
                    include_recompiles: bool = True) -> List[str]:
    """Run the whole audit matrix on the smoke serving config.
    Returns failure strings; empty means the serving hot path holds all
    four invariants."""
    import jax

    from repro.configs import SMOKE_CONFIGS
    from repro.models import get_model

    def say(msg: str) -> None:
        if verbose:
            print(f"[trace-audit] {msg}")

    fails: List[str] = []
    cfg = SMOKE_CONFIGS[ARCH].replace(dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    for paged in (False, True):
        pool_kind = "paged" if paged else "contiguous"
        lowered = lower_serving(model, params, paged=paged)
        pool = lowered.pop("_pool")
        for name, low in lowered.items():
            label = f"{pool_kind}/{name}"
            say(f"lowered {label}")
            # prefill allocates its cache internally (nothing to donate:
            # its big args are read-only params + a small prompt); the
            # step executables must donate the pool cache
            exempt = (0,)
            fails += audit_donation(low, exempt_args=exempt, label=label)
            forbidden = ()
            if paged and name == "decode_step":
                forbidden = paged_growth_patterns(
                    SLOTS, pool.max_blocks, BLOCK_SIZE
                )
            fails += audit_no_growth(low, forbidden=forbidden, label=label)
            fails += audit_dtypes(low, label=label)

    # widening leg: the bf16 model's decode step must stay bf16-sized —
    # any cache-scale f32 convert means the narrow path upcasts
    cfg16 = SMOKE_CONFIGS[ARCH].replace(dtype="bfloat16")
    model16 = get_model(cfg16)
    params16 = model16.init(jax.random.PRNGKey(0))
    lowered16 = lower_serving(model16, params16, paged=True)
    pool16 = lowered16.pop("_pool")
    # Sanctioned deliberate widenings:
    # - L.unembed computes logits in f32 by upcasting the
    #   [vocab, d_model] table (softmax/sampling numerics; the standard
    #   logits-in-f32 discipline) — allowed in every executable;
    # - the MIXED and VERIFY steps' multi-token lanes gather each slot's
    #   pages ([slots, table_width*block_size]) and flash attention
    #   accumulates its online softmax in f32 per KV block
    #   (kernels/ops.py), so that gather shape shows up as a transient
    #   bf16->f32 convert. Allowed for those two ONLY: the decode
    #   executable must never touch a full-gather-shaped tensor at all
    #   (enforced separately by paged_growth_patterns).
    # Everything else — above all any KV-pool-shaped convert — must
    # stay narrow.
    unembed_f32 = f"tensor<{cfg16.vocab_size}x{cfg16.d_model}xf32>"
    gather_f32 = f"tensor<{SLOTS}x{pool16.max_blocks * BLOCK_SIZE}x"
    for name, low in lowered16.items():
        label = f"bf16/{name}"
        say(f"lowered {label}")
        allow16 = (unembed_f32, gather_f32) if name in (
            "mixed_step", "verify_step",
        ) else (unembed_f32,)
        fails += audit_dtypes(low, allow=allow16, label=label)

    # tensor-parallel leg: the SAME donation / static-envelope / dtype
    # bars over the sharded lowerings (global shapes in the pre-partition
    # module, so the thresholds carry over unchanged). Skipped gracefully
    # on single-device hosts — CI forces 4 host devices for this job.
    if jax.device_count() >= 2:
        lowered_tp = lower_serving_tp(model, params, tp=2)
        lowered_tp.pop("_pool")
        for name, low in lowered_tp.items():
            label = f"tp2/{name}"
            say(f"lowered {label}")
            fails += audit_donation(low, exempt_args=(0,), label=label)
            fails += audit_no_growth(low, label=label)
            fails += audit_dtypes(low, label=label)
    else:
        say("single device: skipping the tp2 lowering leg")

    if include_recompiles:
        say("serving two traces for the recompile audit")
        fails += audit_recompiles(model, params)

    say(f"{len(fails)} failure(s)")
    return fails
