"""Cells at a size a CPU test can hold: the T-I cell's own files with the
widths, depth, vocabulary, pool and lengths cut down, and the
generator's and harness's other paths, which no cell of BENCHMARK.json
runs yet: greedy requests in an open loop through prompt chunks
(``CODE``, the paper's HumanEval lengths) and image+text prompts
(``I2T``), on a dense model with a QKV bias and tied embeddings
(``DENSE``) and on the Chameleon configuration."""
from __future__ import annotations

import copy

from bench import spec
from bench.peaks import Peaks

PEAKS = Peaks(bf16_flops=1e12, hbm_bytes_per_s=1e11, hbm_bytes=1e9)

CHAMELEON = spec.load_json(spec.BENCH_DIR / "configs" / "chameleon-34b-l6.json")
T2I = spec.load_json(spec.BENCH_DIR / "traffic" / "t2i.json")
T2I_LIMITS = spec.load_json(spec.BENCH_DIR / "cells" / "chameleon-34b-l6.t2i.json")

#: a Qwen2-style decoder: QKV bias, tied embeddings, no qk-norm
DENSE = {
    "name": "dense", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "qk_layernorm": False, "qkv_bias": True,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "tensor_parallel": 1,
}

_SERVING = {"slots": 64, "block_size": 16, "prefill_budget": 512}
CODE = {
    "prompt": {"min": 44, "max": 430, "median": 154, "sigma": 0.6, "ids": "any"},
    "output": {"min": 55, "max": 10000, "median": 692, "sigma": 0.6},
    "arrivals": {"process": "poisson", "rate": 1.0},
    "stratum": 16, "decoding": {"profile": "greedy"},
    "serving": _SERVING, "open_loop": True,
}
I2T = {
    "prompt": {"min": 1030, "max": 1030, "median": 1030, "sigma": 0.6,
               "ids": "image+text", "image_tokens": 1024},
    "output": {"min": 30, "max": 30, "median": 30, "sigma": 0.6},
    "arrivals": {"process": "poisson", "rate": 1.0},
    "stratum": 16, "decoding": {"profile": "greedy"},
    "serving": _SERVING, "open_loop": True,
}
TRAFFIC = {"t2i": T2I, "code": CODE, "i2t": I2T}

#: workload -> (configuration, traffic)
CELLS = {
    "chameleon-34b-l6.t2i": (CHAMELEON, "t2i"),
    "dense.code": (DENSE, "code"),
    "chameleon-34b-l6.i2t": (CHAMELEON, "i2t"),
}


def tiny_config(base, **kw):
    c = copy.deepcopy(base)
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=2)
    if "image_vocab" in c:
        c.update(vocab_size=256, image_vocab=64, image_tokens_per_image=24)
    else:
        c.update(vocab_size=256)
    c.update(kw)
    return c


def tiny_cell(workload: str, *, slots: int = 8, **config_kw) -> spec.Cell:
    config, traffic = CELLS[workload]
    tr = copy.deepcopy(TRAFFIC[traffic])
    tr["serving"].update(slots=slots, prefill_budget=32)
    for d in (tr["prompt"], tr["output"]):
        d["max"] = min(d["max"], 40)
        d["min"] = min(d["min"], d["max"])
        d["median"] = min(d["median"], d["max"])
    if tr["prompt"].get("image_tokens"):
        tr["prompt"].update(image_tokens=24, min=30, max=30, median=30)
    if tr["arrivals"]["process"] == "backlog":
        tr["arrivals"]["n_requests"] = 12
    else:
        tr["arrivals"]["rate"] = 4.0
    tr["stratum"] = 4
    lim = copy.deepcopy(T2I_LIMITS)
    lim["sample"] = {"min_tokens": 30, "min_requests": slots,
                     "max_requests": slots}
    bm = spec.load_json(spec.ROOT / "BENCHMARK.json")
    applies = [m for m in bm["end_to_end"] + bm["per_layer"]
               if "workloads" not in m or workload in m["workloads"]]
    return spec.Cell(name=workload, config=tiny_config(config, **config_kw),
                     traffic=tr, limits=lim, chips=1,
                     end_to_end=[m for m in applies if m in bm["end_to_end"]],
                     per_layer=[m for m in applies if m in bm["per_layer"]])
