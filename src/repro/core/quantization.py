"""AutoQuant (paper §4.2): per-layer int8 quantization with automatic
mode selection.

Two modes, mirroring torchao AutoQuant's menu:
- ``wo``  (weight-only): weights stored int8 + per-channel scale, dequantized
          at the MXU edge. Wins when the GEMM is memory-bound (decode:
          tokens/step << ridge point) — the benefit is halved weight traffic.
- ``dyn`` (dynamic): activations quantized per-row on the fly, int8×int8
          GEMM accumulated in int32. Wins when compute-bound (prefill/train).

The AutoQuant selector reproduces the paper's tuning flow: shape
calibration (record the token count each linear layer sees per step) then
either (a) analytic roofline choice — compare the layer's arithmetic
intensity against the hardware ridge point — or (b) measured timing of
both kernels (``calibrate="measure"``), picking the faster.

Param-tree mechanics: a quantized linear is the dict
``{"w_q": int8 [K,N], "w_scale": f32 [N], ("b")}`` plus the mode encoded in
the key (``w_q`` + presence of ``dyn`` flag array is avoided — mode is
*structural*, via dict key ``qmode_wo``/``qmode_dyn`` holding an empty
array, so jit specializes on it statically).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops

# v5e peaks (bench/peaks.py holds the benchmark's copy)
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
RIDGE_TOKENS = PEAK_FLOPS_BF16 / HBM_BW / 2  # ≈120 rows: bf16 GEMM ridge


def quantize_linear(p: Dict[str, jnp.ndarray], mode: str) -> Dict[str, jnp.ndarray]:
    """{"w": [K,N] or stacked [L,K,N], ...} -> quantized-linear dict.
    Quantizes along the contraction dim (-2): scanned-layer stacks keep
    per-layer per-channel scales; the lax.scan slice seen by qdense is the
    usual [K,N] int8 + [N] scale."""
    assert mode in ("wo", "dyn")
    w_q, w_scale = ops.quantize_int8(p["w"], axis=p["w"].ndim - 2)
    # mode is STRUCTURAL (encoded in the key) so jit specializes on it and
    # scanned-layer stacks carry no degenerate marker leaves
    out = {f"w_q_{mode}": w_q, "w_scale": w_scale}
    if "b" in p:
        out["b"] = p["b"]
    return out


def qdense(p: Dict[str, jnp.ndarray], x: jnp.ndarray) -> jnp.ndarray:
    """Forward through a (possibly) quantized linear param dict."""
    if "w_q_dyn" in p:
        y = ops.int8_matmul_dynamic(x, p["w_q_dyn"], p["w_scale"]).astype(x.dtype)
    elif "w_q_wo" in p:
        y = ops.int8_matmul_weight_only(x, p["w_q_wo"], p["w_scale"]).astype(x.dtype)
    else:
        y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def _is_linear(p: Any) -> bool:
    return (
        isinstance(p, dict)
        and "w" in p
        and hasattr(p["w"], "ndim")
        and p["w"].ndim in (2, 3)  # plain [K,N] or scanned stack [L,K,N]
    )


_SKIP_KEYS = ("embed", "router", "norm", "rel_bias")  # paper: linears only


def _walk(tree: Any, fn: Callable[[Tuple[str, ...], dict], dict], path=()):
    if _is_linear(tree) and not any(s in k for k in path for s in _SKIP_KEYS):
        return fn(path, tree)
    if isinstance(tree, dict):
        return {k: _walk(v, fn, path + (k,)) for k, v in tree.items()}
    if isinstance(tree, list):
        return [_walk(v, fn, path + (str(i),)) for i, v in enumerate(tree)]
    return tree


def quantize_params(params: Any, mode: str = "wo") -> Any:
    """Quantize every eligible linear layer with a single mode."""
    return _walk(params, lambda path, p: quantize_linear(p, mode))


def roofline_mode(tokens_per_step: int) -> str:
    """Analytic AutoQuant decision: below the GEMM ridge point the layer is
    weight-traffic-bound (weight-only wins); above it compute-bound
    (dynamic int8 doubles MXU throughput)."""
    return "wo" if tokens_per_step < RIDGE_TOKENS else "dyn"


def measure_mode(w: jnp.ndarray, tokens_per_step: int, n_iter: int = 20) -> str:
    """Measured AutoQuant decision (paper's timing calibration step)."""
    k, n = w.shape
    x = jnp.ones((tokens_per_step, k), jnp.bfloat16)
    cands = {}
    for mode in ("wo", "dyn"):
        qp = quantize_linear({"w": w}, mode)
        f = jax.jit(lambda x, qp=qp: qdense(qp, x))
        f(x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(n_iter):
            f(x).block_until_ready()
        cands[mode] = time.perf_counter() - t0
    return min(cands, key=cands.get)


def autoquant(
    params: Any,
    *,
    tokens_per_step: int,
    calibrate: str = "roofline",
) -> Tuple[Any, Dict[str, int]]:
    """AutoQuant a param tree. Returns (new_params, {'wo': n, 'dyn': n}).

    ``tokens_per_step`` is the calibrated activation row count (batch for
    decode; batch*seq for prefill) — the paper's "shape calibration"."""
    counts = {"wo": 0, "dyn": 0}

    def decide(path, p):
        if calibrate == "measure":
            mode = measure_mode(p["w"], tokens_per_step)
        else:
            mode = roofline_mode(tokens_per_step)
        counts[mode] += 1
        return quantize_linear(p, mode)

    return _walk(params, decide), counts


def quantization_error(params: Any, qparams: Any, x: jnp.ndarray) -> float:
    """Max relative logit error of a single quantized linear (test hook)."""
    y = x @ params["w"]
    yq = qdense(qparams, x)
    return float(
        jnp.max(jnp.abs(yq.astype(jnp.float32) - y.astype(jnp.float32)))
        / jnp.maximum(jnp.max(jnp.abs(y.astype(jnp.float32))), 1e-9)
    )
