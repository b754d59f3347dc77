"""Find a cell's files by the names in BENCHMARK.json and turn them into
the objects a run needs: the model configuration, the traffic mix and the
cell's limits. Nothing here touches JAX's devices."""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Cell:
    name: str
    config: Dict[str, Any]  # bench/configs/<config>.json
    traffic: Dict[str, Any]  # bench/traffic/<traffic>.json
    limits: Dict[str, Any]  # bench/cells/<workload>.json
    chips: int
    end_to_end: List[Dict[str, Any]]  # metric entries that apply here
    per_layer: List[Dict[str, Any]]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: Optional[Dict[str, Any]] = None) -> Cell:
    """The workload ``name`` of BENCHMARK.json with its files loaded."""
    bm = benchmark or load_json(ROOT / "BENCHMARK.json")
    wl = {w["name"]: w for w in bm["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    cfgs = {c["name"]: c for c in bm["configs"]}
    return make_cell(name, ROOT / cfgs[w["config"]]["file"], w["traffic"],
                     int(w["chips"]), bm)


def make_cell(name: str, config_file: Path, traffic_name: str, chips: int,
              benchmark: Dict[str, Any]) -> Cell:
    """A cell from its files: the configuration, ``traffic/<traffic>.json``
    and ``cells/<name>.json``, with the metrics that apply to ``name``."""
    return Cell(
        name=name,
        config=load_json(config_file),
        traffic=load_json(BENCH_DIR / "traffic" / f"{traffic_name}.json"),
        limits=load_json(BENCH_DIR / "cells" / f"{name}.json"),
        chips=chips,
        end_to_end=[m for m in benchmark["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in benchmark["per_layer"] if _applies(m, name)],
    )


def model_config(c: Dict[str, Any]):
    """``repro`` ModelConfig from a configuration file. The file keeps the
    source's key names; this is the one place they map onto the
    program's."""
    from repro.configs.base import ModelConfig, VLMConfig

    vlm = None
    if "image_vocab" in c:
        vlm = VLMConfig(n_image_tokens=c["image_tokens_per_image"],
                        image_vocab=c["image_vocab"])
    return ModelConfig(
        name=c["name"],
        family="vlm" if vlm is not None else "dense",
        n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        d_head=c["head_dim"],
        rope_theta=float(c["rope_theta"]),
        rmsnorm_eps=float(c["rms_norm_eps"]),
        qkv_bias=bool(c["qkv_bias"]),
        qk_norm=bool(c["qk_layernorm"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        vlm=vlm,
        dtype=c["torch_dtype"],
    )


def image_offset(c: Dict[str, Any]) -> Optional[int]:
    """First image-token id (image tokens fill the vocabulary's tail), or
    None for a text-only model."""
    if "image_vocab" not in c:
        return None
    return c["vocab_size"] - c["image_vocab"]
