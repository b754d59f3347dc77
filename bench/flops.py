"""The work a step needs, counted from the scheduler's per-step state:
valid tokens and valid (query, key) pairs only, never the padded shapes
the executables compute. A later kernel that skips padding therefore can
not read above 100% of a peak.

Shapes come from the configuration file's own keys (the source's names).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple


@dataclass
class StepRecord:
    """One pool step as the scheduler saw it: ``decode_pos`` holds the
    position each decoding row wrote (it attends ``pos + 1`` keys),
    ``chunks`` the (start, length) of each prompt chunk, ``n_first`` the
    chunks that finished a prompt (each needs one row of logits)."""

    kind: str  # "decode" | "mixed"
    t0: float
    t1: float
    decode_pos: List[int] = field(default_factory=list)
    chunks: List[Tuple[int, int]] = field(default_factory=list)
    n_first: int = 0
    occupancy: float = 0.0
    block_use: float = 0.0


def _dims(c: Dict[str, Any]):
    return (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["intermediate_size"], c["num_hidden_layers"], c["vocab_size"])


def linear_flops_per_token(c: Dict[str, Any]) -> float:
    """Matmul FLOPs of one token through every layer (q, k, v, o and the
    three FFN projections)."""
    d, hq, hkv, dh, ff, n_layers, _ = _dims(c)
    per_layer = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * ff
    return 2.0 * n_layers * per_layer


def head_flops_per_row(c: Dict[str, Any]) -> float:
    d, *_, vocab = _dims(c)
    return 2.0 * d * vocab


def attention_pairs(rec: StepRecord) -> int:
    """Valid (query, key) pairs of one layer: a decoding row at position p
    sees p + 1 keys; chunk query j of a chunk starting at s sees s + j + 1."""
    pairs = sum(p + 1 for p in rec.decode_pos)
    for s, t in rec.chunks:
        pairs += t * s + t * (t + 1) // 2
    return pairs


def step_flops(c: Dict[str, Any], rec: StepRecord) -> float:
    """Model FLOPs the step needed: every valid token through the layers,
    one row of logits per decoding row and per finished prompt, and the
    attention over valid pairs (2 FLOPs a multiply-add, QK and PV)."""
    d, hq, hkv, dh, ff, n_layers, _ = _dims(c)
    tokens = len(rec.decode_pos) + sum(t for _, t in rec.chunks)
    rows = len(rec.decode_pos) + rec.n_first
    attn = 4.0 * hq * dh * n_layers * attention_pairs(rec)
    return (tokens * linear_flops_per_token(c)
            + rows * head_flops_per_row(c) + attn)

