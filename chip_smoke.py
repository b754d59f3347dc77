"""Bring-up check of the serving path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the multi-chip paths, four chips

Run from the root of the repository; one process drives every chip.

With no option, the script serves one greedy trace (8 requests into 8
slots, all arriving at t=0, up to 32 new tokens each) through
``repro.launch.serve.main`` at Llama-3.2-1B's full published widths, in
bf16, with random weights from seed 0, twice: on the default contiguous
continuous-batching path (prefill and decode steps) and with
``--paged --chunked`` (mixed step and paged decode). It then checks

- that every request finished with the number of tokens it asked for;
- that the compiled prefill, mixed step and contiguous decode step hold
  the Pallas attention kernels (``tpu_custom_call``), so that no kernel
  ran in the Pallas interpreter;
- that one prompt's prefill logits through those kernels agree with the
  XLA attention path (``impl="xla"``) within :data:`LOGIT_BOUND`.

``--four-chips`` runs the multi-chip paths and nothing else: the same
trace with ``--tp 4`` and with ``--replicas 2 --tp 2``, against the
one-chip pool on device 0. It checks that both served every request,
bounds the sharded prefill logits against the one-chip ones by
:data:`LOGIT_BOUND`, and prints the per-request greedy-token agreement
(not required to be complete: the sharded reductions run in another
order in bf16) and the KV bytes reserved per device.

Every failed check raises, so the script exits non-zero. Where JAX finds
no TPU it exits non-zero before it serves anything. The last line of
standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "llama3.2-1b"
SLOTS = 8
N_REQUESTS = 8
MAX_NEW = 32
BLOCK_SIZE = 16  # serve.py's --block-size default; also its chunk width
#: the greedy trace both paths serve: all arrivals at t=0, seed 0
TRACE = [
    "--arch", ARCH, "--n-requests", str(N_REQUESTS),
    "--batch-slots", str(SLOTS), "--max-new", str(MAX_NEW),
    "--temperature", "0", "--arrival-rate", "0", "--seed", "0",
]
#: what the compiled program holds for each Pallas kernel lowered by Mosaic
KERNEL_MARK = "tpu_custom_call"
#: largest admitted max |logits - reference| over max |reference|. Both
#: sides compute in bf16 with f32 accumulation; the kernel rounds its
#: scaled queries and softmax weights to bf16 before the MXU (bf16 keeps
#: 8 significant bits, a relative step of 2**-8 = 0.0039), and the
#: difference grows through the 16 layers' residual stream. The same
#: comparison measured 0.0049 on a TPU v5e and 0.0056 on a CPU with the
#: kernel in interpret mode.
LOGIT_BOUND = 0.05


def require_tpu(n_chips: int):
    """The first device, once JAX is seen to hold ``n_chips`` TPU chips."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX found platform {platform!r} "
            f"({len(devices)} device(s)). Nothing was run."
        )
    if len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke.py --four-chips needs {n_chips} TPU chips; JAX "
            f"found {len(devices)}."
        )
    return devices[0]


def say(label: str, msg: str) -> None:
    print(f"[chip run {label}] {msg}", flush=True)


def serve(label: str, extra_args) -> tuple:
    """One ``serve.main`` run of the trace; checks every request's count."""
    from repro.launch import serve as serve_mod

    m, done = serve_mod.main(TRACE + list(extra_args), return_requests=True)
    short = [(r.rid, len(r.tokens), r.max_new) for r in done
             if len(r.tokens) != r.max_new]
    if len(done) != N_REQUESTS or short:
        raise AssertionError(
            f"{label}: {len(done)} of {N_REQUESTS} requests done; "
            f"(rid, tokens, asked) short: {short}"
        )
    say(label, f"compile (warm-up) {m['warmup_s']:.3f} s | run "
               f"{m['wall_s']:.3f} s | metrics {json.dumps(m, default=str)}")
    return m, {r.rid: r for r in done}


def check_kernels_compiled(label: str, model, params, pad_to: int) -> None:
    """Compile the serving prefill, contiguous decode step and paged mixed
    step at the geometry ``serve`` used, and find the kernels in each."""
    from repro.analysis import trace_audit

    geometry = dict(slots=SLOTS, pad_to=pad_to, max_new_cap=MAX_NEW,
                    block_size=BLOCK_SIZE, num_blocks=None,
                    prefill_budget=BLOCK_SIZE)
    contiguous = trace_audit.lower_serving(
        model, params, paged=False, **geometry)
    paged = trace_audit.lower_serving(model, params, paged=True, **geometry)
    for name, lowered in (("prefill", contiguous["prefill"]),
                          ("decode_step", contiguous["decode_step"]),
                          ("mixed_step", paged["mixed_step"])):
        t0 = time.perf_counter()
        text = lowered.compile().as_text()
        n = text.count(KERNEL_MARK)
        say(label, f"{name}: compile {time.perf_counter() - t0:.3f} s | "
                   f"{n} {KERNEL_MARK} op(s)")
        if n == 0:
            raise AssertionError(f"compiled {name} holds no {KERNEL_MARK}")


def prefill_logits(model, params, tokens, max_len: int, impl: str):
    """Every position's prefill logits of ``tokens`` [1, T], through the
    attention implementation ``impl``; returns (logits, compile s, run s)."""
    import jax

    def fn(params, tokens):
        cache = model.init_cache(tokens.shape[0], max_len)
        logits, _, _ = model.forward(params, {"tokens": tokens}, cache=cache,
                                     mode="prefill", impl=impl)
        return logits

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(params, tokens).compile()
    t1 = time.perf_counter()
    out = np.asarray(compiled(params, tokens), np.float32)
    return out, t1 - t0, time.perf_counter() - t1


def compare(label: str, what: str, got, want) -> None:
    """Bound max |got - want| / max |want| by LOGIT_BOUND and report it
    with the share of positions whose greedy token agrees."""
    diff = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    say(label, f"{what}: max |diff| {diff:.6g} over max |ref| {scale:.6g} = "
               f"{diff / scale:.6g} (bound {LOGIT_BOUND}) | argmax agreement "
               f"{agree:.4f}")
    if not np.isfinite(got).all() or not diff <= LOGIT_BOUND * scale:
        raise AssertionError(f"{what}: logits outside the bf16 bound")


def one_chip(label: str) -> None:
    import jax

    from repro.configs import get_config
    from repro.models import get_model

    serve(label + " serve contiguous", [])
    _, done = serve(label + " serve paged+chunked", ["--paged", "--chunked"])

    model = get_model(get_config(ARCH))
    params = model.init(jax.random.PRNGKey(0))
    # serve pads prompts to the trace's longest one
    pad_to = max(len(r.prompt) for r in done.values())
    check_kernels_compiled(label, model, params, pad_to)

    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (1, pad_to), 0, model.config.vocab_size)
    max_len = pad_to + MAX_NEW + 1
    got, c_got, r_got = prefill_logits(model, params, tokens, max_len, "auto")
    want, c_want, r_want = prefill_logits(model, params, tokens, max_len, "xla")
    say(label, f"prefill logits [1, {pad_to}, {model.config.vocab_size}]: "
               f"default impl compile {c_got:.3f} s run {r_got:.3f} s | "
               f"xla compile {c_want:.3f} s run {r_want:.3f} s")
    compare(label, "default-impl vs xla prefill logits", got, want)


def four_chips(label: str) -> None:
    import jax

    from repro.configs import get_config
    from repro.core import engine
    from repro.distributed import tp_pool
    from repro.models import get_model

    _, ref = serve(label + " serve one chip (device 0)", [])
    runs = {
        "tp=4": (4, ["--tp", "4"]),
        "replicas=2 x tp=2": (2, ["--replicas", "2", "--tp", "2"]),
    }
    model = get_model(get_config(ARCH))
    params = model.init(jax.random.PRNGKey(0))
    pad_to = max(len(r.prompt) for r in ref.values())
    max_len = pad_to + MAX_NEW + 1
    first = ref[min(ref)]
    tokens = np.zeros((1, pad_to), np.int32)
    tokens[0, : len(first.prompt)] = first.prompt
    lengths = np.asarray([len(first.prompt)], np.int32)
    want = np.asarray(
        engine.prefill(model, params, tokens, lengths, max_len)[0], np.float32)
    for name, (tp, args) in runs.items():
        m, done = serve(f"{label} serve {name}", args)
        same = sum(done[i].tokens == ref[i].tokens for i in ref)
        match = sum(
            sum(a == b for a, b in zip(done[i].tokens, ref[i].tokens))
            for i in ref)
        total = sum(len(ref[i].tokens) for i in ref)
        say(label, f"{name}: greedy tokens identical in {same}/{len(ref)} "
                   f"requests, {match}/{total} positions | "
                   f"kv_reserved_per_device_bytes "
                   f"{m['kv_reserved_per_device_bytes']} (pool "
                   f"{m['kv_reserved_bytes']} logical)")
        ctx = tp_pool.TPContext(
            model, params, tp_pool.make_tp_mesh(tp),
            cache_like=model.abstract_cache(SLOTS, max_len), max_len=max_len)
        got = np.asarray(engine.tp_prefill(
            model, ctx.params, tokens, lengths, max_len, None,
            row_shardings=ctx.row_static)[0], np.float32)
        compare(label, f"tp={tp} vs one-chip first-step logits", got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel and DP x TP serving "
                         "paths against the one-chip pool (needs 4 chips)")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    dev = require_tpu(n_chips)

    import jax

    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    count = len(jax.devices())
    label = f"{dev.device_kind} x{count}"
    print(f"device_kind={dev.device_kind} device_count={count} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(label)
    say(label, f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
