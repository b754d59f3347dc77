"""The HBM bytes a step needs, counted like ``bench.flops``: from the
scheduler's per-step state (``flops.StepRecord``), valid tokens and valid
keys only, never the padded shapes the executables compute.

A step reads the weights once (every layer, the final norm and the
output head, or the tied table that serves as the head, plus the
embedding rows of its tokens), reads the keys and values its queries
attend (a decoding row at position p reads p + 1 positions; a chunk
starting at s of length t reads its s + t positions once) and writes the
keys and values of its new tokens, all in bfloat16.
"""
from __future__ import annotations

from typing import Any, Dict

from bench.flops import StepRecord, _dims

BF16 = 2


def layer_params(c: Dict[str, Any]) -> int:
    """Parameters of one decoder layer: the seven projections, the QKV
    biases where the configuration has them, and two norms."""
    d, hq, hkv, dh, ff, _, _ = _dims(c)
    n = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * ff + 2 * d
    if c["qkv_bias"]:
        n += hq * dh + 2 * hkv * dh
    return n


def weight_bytes(c: Dict[str, Any]) -> int:
    """Weights a step reads in full: every layer, the final norm and the
    head (the embedding table itself when the head is tied)."""
    d, *_, n_layers, vocab = _dims(c)
    return BF16 * (n_layers * layer_params(c) + d + vocab * d)


def kv_bytes_per_position(c: Dict[str, Any]) -> int:
    """K and V of one position in every layer."""
    _, _, hkv, dh, _, n_layers, _ = _dims(c)
    return BF16 * 2 * hkv * dh * n_layers


def step_bytes(c: Dict[str, Any], rec: StepRecord) -> float:
    """HBM bytes the step needed: the weights once, the embedding rows of
    its tokens, the valid keys and values read and the new ones written."""
    d = c["hidden_size"]
    tokens = len(rec.decode_pos) + sum(t for _, t in rec.chunks)
    read = sum(p + 1 for p in rec.decode_pos) + sum(s + t for s, t in rec.chunks)
    return float(weight_bytes(c) + BF16 * d * tokens
                 + kv_bytes_per_position(c) * (read + tokens))
