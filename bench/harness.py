"""One run of one cell: set-up, the measured window, the metrics, and the
comparison with the plain reference that decides ``correct``.

Set-up (``setup_s``, from process start to the first timed step) makes
the weights on the device in one jitted call from the seed, sizes the
paged KV pool, and warms every shape the cell's traffic uses on a
scheduler of the same geometry, which is then thrown away. The window
runs a fresh scheduler for ``--seconds``. After it, the process's peak
memory is read, the program's state is freed, and the reference runs.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import flops, reference, spec, trace_reduce, traffic, window
from bench.peaks import Peaks, peaks_for

POOL_RESERVE = 0.05  # share of device memory kept free beyond the estimate


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def key_data(seed: int) -> np.ndarray:
    """A raw JAX key from any whole-number seed (JAX's own PRNGKey keeps
    only 32 bits of it)."""
    return np.random.SeedSequence(seed).generate_state(2, np.uint32)


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"cell needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)")
    if len(devs) < chips:
        raise NoAccelerator(f"cell needs {chips} devices, have {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``
    where that is set, else a fixed directory inside the checkout), keeping
    every program however fast it compiled: the warm-up's small eager
    programs too."""
    import jax
    from repro.launch import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads as they happen."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **kw):
        if "backend_compile" in name or "cache_retrieval" in name:
            self.n += 1


def jit_cache_sizes() -> int:
    from repro.analysis import trace_audit

    return sum(f._cache_size() for f in trace_audit.serving_jits().values())


# ---- set-up -------------------------------------------------------------------

def init_params(model, key, tp: int, devs):
    """Weights on the device from one jitted call; a TP cell's go straight
    into their tensor-parallel shardings."""
    import jax

    if tp == 1:
        with jax.default_device(devs[0]):
            return jax.block_until_ready(jax.jit(model.init)(key)), None
    from repro.distributed import sharding, tp_pool

    mesh = tp_pool.make_tp_mesh(tp, devs)
    abstract = jax.eval_shape(model.init, key)
    shard = sharding.to_shardings(
        mesh, sharding.param_specs(model.config, abstract, mesh, enable_tp=True))
    params = jax.jit(model.init, out_shardings=shard)(key)
    return jax.block_until_ready(params), mesh


def block_bytes(c: Dict[str, Any], block_size: int, tp: int) -> int:
    """KV bytes of one pool block on one chip (bfloat16 K and V)."""
    per_token = (c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
                 * c["head_dim"] * 2)
    return per_token * block_size // tp


def step_temp_bytes(model, params, kw: Dict[str, Any], streams: int,
                    mixed: bool, groups: bool) -> int:
    """The largest temporary footprint of the steps this traffic uses, from
    ``compiled.memory_analysis()`` at a probe pool (the temporaries do not
    scale with the pool's block count)."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine

    sds = jax.ShapeDtypeStruct
    slots, bs = kw["slots"], kw["block_size"]
    max_len = kw["pad_to"] + kw["max_new_cap"] + 1
    mb = -(-max_len // bs)
    cache = dict(jax.eval_shape(lambda: model.init_cache(streams * mb + 1, bs)))
    cache["lengths"] = sds((slots,), jnp.int32)
    cache["block_tables"] = sds((slots, mb), jnp.int32)
    vec = sds((slots,), jnp.int32)
    comp = [engine.decode_step.lower(model, params, cache, vec).compile()]
    if mixed:
        toks = sds((slots, kw["prefill_budget"]), jnp.int32)
        comp.append(engine.mixed_step.lower(
            model, params, cache, toks, vec, vec).compile())
    if groups:
        comp.append(engine.prefill.lower(
            model, params, sds((1, kw["pad_to"]), jnp.int32),
            sds((1,), jnp.int32), max_len, None).compile())
    temps = []
    for c in comp:
        ma = c.memory_analysis()
        temps.append(ma.temp_size_in_bytes + ma.output_size_in_bytes
                     - ma.alias_size_in_bytes)
    return max(temps)


def pool_blocks(model, params, c, kw, devs, tp: int, streams: int,
                mixed: bool, groups: bool, log) -> int:
    """Blocks of the paged pool: enough for every slot to hold the mix's
    longest request, or what the chip holds after the weights and the
    steps' own temporaries, whichever is fewer."""
    from repro.distributed import tp_pool

    bs = kw["block_size"]
    mb = -(-(kw["pad_to"] + kw["max_new_cap"] + 1) // bs)
    parity = kw["slots"] * mb + 1
    stats = devs[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    if limit is None:  # no memory figures (a CPU test): no cap
        return parity
    per_block = block_bytes(c, bs, tp)
    weights = tp_pool.max_per_device_bytes(params)
    if weights + parity * per_block <= 0.6 * limit:
        log(f"pool: {parity} blocks, every slot can hold the longest request")
        return parity
    if tp != 1:
        raise RuntimeError("a tensor-parallel pool must fit at parity")
    temp = step_temp_bytes(model, params, kw, streams, mixed, groups)
    fit = int((limit * (1 - POOL_RESERVE) - weights - temp) // per_block)
    log(f"pool: limit {limit} B, weights {weights} B, step temporaries "
        f"{temp} B -> {fit} blocks fit, parity {parity}")
    nb = min(parity, fit)
    if nb - 1 < streams * mb:
        raise RuntimeError(f"pool of {nb} blocks cannot hold one request")
    return nb


def serve_requests(reqs: List[traffic.Request], tr: Dict[str, Any],
                   image_offset: Optional[int]):
    from repro.core.profiles import ContrastiveProfile
    from repro.core.scheduler import ServeRequest

    dec = tr["decoding"]
    out = []
    for r in reqs:
        prof = None
        if dec["profile"] == "contrastive":
            prof = ContrastiveProfile(
                uncond_token=dec["uncond_token"], guidance=dec["guidance"],
                mask_offset=image_offset if dec.get("mask") == "image" else None)
        elif dec["profile"] != "greedy":
            raise ValueError(f"unknown decoding profile {dec['profile']!r}")
        out.append(ServeRequest(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                                t_arrival=r.due, profile=prof))
    return out


def warm_up(make_sched, reqs: List[traffic.Request], tr, image_offset):
    """Serve a few requests of the cell's own shapes on a scheduler of the
    window's geometry: a full-length one (its output buffers take the
    mix's longest size) and two that finish at once (eviction and slot
    reuse), through prompt chunks, decode steps and, for a group mix,
    group prefill and the group step."""
    longest = max(reqs, key=lambda r: (len(r.prompt), r.max_new))
    shapes = [
        traffic.Request(0, longest.prompt, longest.max_new, 0.0),
        traffic.Request(1, longest.prompt, 3, 0.0),
        traffic.Request(2, reqs[0].prompt, 3, 0.0),
    ]
    sched = make_sched()
    wreqs = serve_requests(shapes, tr, image_offset)
    for r in wreqs:
        sched.normalize(r)
    n_chunk_steps = -(-len(longest.prompt) // tr["serving"]["prefill_budget"])
    window.drive(sched, wreqs, 1e9, open_loop=False,
                 max_steps=2 * n_chunk_steps + 6)
    del sched
    gc.collect()


def served_tokens(sched, req) -> np.ndarray:
    """Tokens the program served a request: its record, or for a group
    still decoding, its profile's state."""
    import jax

    if req.t_done is None and req.profile is not None:
        for g in sched.groups.values():
            if g.req is req:
                toks = jax.device_get(g.pstate["tokens"])
                return np.asarray(toks)[0, : g.n_generated]
    return np.asarray(req.tokens, np.int32)


def pick_sample(items: List[Tuple[Any, np.ndarray]], seed: int,
                lim: Dict[str, Any]) -> List[Tuple[Any, np.ndarray]]:
    """The requests the reference checks: the one with the most served
    tokens, then others in an order drawn from the seed, until the sample
    holds ``min_tokens`` served tokens and ``min_requests`` requests (at
    most ``max_requests``)."""
    items = [it for it in items if len(it[1]) > 0]
    if not items:
        return []
    first = max(range(len(items)), key=lambda i: len(items[i][1]))
    rng = np.random.default_rng([seed % 2**63, 17])
    order = [first] + [int(i) for i in rng.permutation(len(items)) if i != first]
    out, n_tok = [], 0
    for i in order:
        if len(out) >= lim["max_requests"]:
            break
        if n_tok >= lim["min_tokens"] and len(out) >= lim["min_requests"]:
            break
        out.append(items[i])
        n_tok += len(items[i][1])
    return out


def load_reader(name: str):
    path = spec.BENCH_DIR / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def judge(gap: float, wrong_len: int, unserved: int,
          lim: Dict[str, Any]) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and the numbers it compares, each beside its limit."""
    checks = {
        "max_logit_gap": {"value": gap, "limit": lim["max_logit_gap"]},
        "wrong_length": {"value": wrong_len, "limit": 0},
        "unserved": {"value": unserved, "limit": 0},
    }
    return all(v["value"] <= v["limit"] for v in checks.values()), checks


# ---- the run ------------------------------------------------------------------

def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        peaks: Optional[Peaks] = None, keep_trace: Optional[str] = None,
        log=None, fault=None, control: bool = False) -> Dict[str, Any]:
    """One run; returns the result line's object (``checks`` last).
    ``fault``, for tests only, is called with the window's scheduler
    before the window opens. ``control`` (bench/control.py) also judges
    the int8 control: the tokens it puts first at each served position
    take the served tokens' place in the same checks, as ``control``."""
    import jax
    import jax.numpy as jnp

    log = log or (lambda msg: print(f"[bench] {msg}", file=sys.stderr,
                                    flush=True))
    devs = devices_for(cell.chips, require_tpu)
    peaks = peaks or peaks_for(devs[0].device_kind)
    enable_compile_cache()
    counter = CompileCounter()

    from repro.core.scheduler import Scheduler
    from repro.models.registry import get_model

    c, tr = cell.config, cell.traffic
    tp = int(c["tensor_parallel"])
    model = get_model(spec.model_config(c))
    img = spec.image_offset(c)
    key = jnp.asarray(key_data(seed))
    params, mesh = init_params(model, key, tp, devs)
    sv = tr["serving"]
    kw = dict(slots=sv["slots"], pad_to=traffic.pad_to(tr),
              max_new_cap=traffic.max_new_cap(tr), block_size=sv["block_size"],
              prefill_budget=sv["prefill_budget"])
    reqs = traffic.generate(tr, seed, seconds, vocab=c["vocab_size"],
                            image_offset=img)
    groups = tr["decoding"]["profile"] == "contrastive"
    streams = 2 if groups else 1
    nb = pool_blocks(model, params, c, kw, devs, tp, streams,
                     mixed=not groups, groups=groups, log=log)
    with jax.default_device(devs[0]):
        base_key = jax.random.PRNGKey(0)
    host = contextlib.nullcontext()
    if mesh is not None:  # build the pool on the host; placed shard by shard
        host = jax.default_device(jax.local_devices(backend="cpu")[0])

    def make_sched():
        with host:
            return Scheduler(model, params, paged=True, chunked=True,
                             num_blocks=nb, base_key=base_key, tp_mesh=mesh,
                             **kw)

    with jax.default_device(devs[0]):
        warm_up(make_sched, reqs, tr, img)
        sched = make_sched()
    sreqs = serve_requests(reqs, tr, img)
    for r in sreqs:
        sched.normalize(r)
    if fault is not None:
        fault(sched)

    tdir, tctl = None, None
    annotate = lambda name: contextlib.nullcontext()  # noqa: E731
    if trace:
        tdir = keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
        annotate = jax.profiler.TraceAnnotation
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans only: the tracer's cost stays low
        tctl = {"start": 0.4 * seconds, "length": min(2.0, 0.3 * seconds),
                "begin": lambda: jax.profiler.start_trace(
                    tdir, profiler_options=opts),
                "end": jax.profiler.stop_trace}
        if tr["open_loop"]:  # open the trace on a step that runs a chunk
            tctl["ready"] = lambda s: len(s.chunk_mgr) > 0
    n_comp, n_jit = counter.n, jit_cache_sizes()
    setup_s = time.perf_counter() - t_start
    with jax.default_device(devs[0]):
        res = window.drive(sched, sreqs, seconds, open_loop=tr["open_loop"],
                           annotate=annotate, trace=tctl)
    window_compiles = counter.n - n_comp
    window_jit = jit_cache_sizes() - n_jit
    mem = [d.memory_stats() or {} for d in devs]
    mem_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    lat = np.asarray(res.lateness) if res.lateness else np.zeros(1)
    log(f"window: {len(res.steps)} steps, compiles in window {window_compiles}"
        f" (serving jit cache entries added {window_jit}), generator late "
        f"p99 {np.percentile(lat, 99) * 1e3:.3f} ms max {lat.max() * 1e3:.3f} ms,"
        f" preemptions {sched.n_preemptions}, pool {nb} blocks, followed "
        f"{res.followed_s:.3f} s past the window")

    tsum = None
    if trace:
        tsum = trace_reduce.reduce_trace(trace_reduce.find_xplane(tdir))
        log(f"trace: {len(res.trace_steps)} steps, window {tsum.window_s:.6f} s,"
            f" busy {tsum.busy_s:.6f} s")

    # ---- what the program served, then free its state -----------------------
    due = [r for r in res.requests if r.t_arrival < seconds]
    if tr["open_loop"]:
        attempted = len(due)
        failed = sum(1 for r in due if r.t_first is None)
    else:
        attempted = sum(1 for r in due
                        if r.t_admit is not None and r.t_admit < seconds)
        failed = 0
    served = [(r, served_tokens(sched, r)) for r in res.requests
              if r.t_admit is not None]
    wrong_len = sum(1 for r, t in served
                    if r.t_done is not None and len(t) != r.max_new)
    sample = pick_sample(served, seed, cell.limits["sample"])
    items = [reference.Item(np.asarray(r.prompt, np.int32), t)
             for r, t in sample]
    runrec = SimpleNamespace(
        seconds=seconds, setup_s=setup_s, requests=res.requests,
        steps=res.steps, trace_steps=res.trace_steps, trace=tsum, config=c,
        chips=cell.chips, peaks=peaks)
    del sched, params, served, sample, res
    gc.collect()

    t_ref = time.perf_counter()
    gaps = reference.logit_gaps(c, key, items, tr["decoding"], img,
                                control=control)
    def widest(name: str) -> float:
        return float(gaps[name].max()) if items else math.inf

    correct, checks = judge(widest("served"), wrong_len, failed, cell.limits)
    log(f"compared {len(items)} requests, {sum(len(i.served) for i in items)}"
        f" served tokens, reference {time.perf_counter() - t_ref:.1f} s")
    ref_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)
    log(f"reference: {math.ceil(len(items) * streams / reference.CHUNK_ROWS)}"
        f" chunks of {reference.CHUNK_ROWS} sequences a layer, device peak "
        f"{ref_peak} B after it (window {mem_peak} B)")

    mlist = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in mlist:
        v = load_reader(m["name"]).read(runrec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    out: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if control:  # the int8 control's picks in place of the served tokens
        ok, cchecks = judge(widest("control"), wrong_len, failed, cell.limits)
        out["control"] = {"correct": ok, "checks": cchecks, "gaps": gaps}
    if tsum is not None:
        device["busy_s"] = tsum.busy_s
        device["window_s"] = tsum.window_s
        out["breakdown"] = trace_reduce.breakdown(tsum)
        if keep_trace is None:
            shutil.rmtree(tdir, ignore_errors=True)
        else:  # what the traced steps were, for checking the reduction
            with open(f"{keep_trace}/steps.json", "w") as f:
                json.dump([{"kind": s.kind, "flops": flops.step_flops(c, s)}
                           for s in runrec.trace_steps], f)
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    out["checks"] = checks
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace),
                  t_start=t_start)
    except NoAccelerator as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0
