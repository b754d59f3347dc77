"""The one request generator. A traffic file (bench/traffic/<name>.json)
holds only parameters; this module turns them and a seed into requests.

Every seed gets the same set of sizes and gaps in another order: lengths
and inter-arrival gaps are quantiles of their distributions, taken in
strata of ``stratum`` consecutive requests (each stratum holds the
quantiles at (j + 0.5) / stratum, shuffled by the seed). So any window
that covers whole strata carries the same work whatever the seed, and
the seed changes which request is which and every token id.

The length profiles are those of the paper's Table 2
(``repro.training.data.PAPER_PROFILES``: a lognormal of sigma 0.6 around
the profile's mean, clipped to its range); the Poisson arrivals follow
``repro.launch.serve.poisson_trace``. Both are copied here so that the
yardstick does not move when the program does.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List, Optional

import numpy as np


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # int32 token ids
    max_new: int
    due: float  # seconds after the window opens


def length_quantile(d: Dict[str, Any], u: float) -> int:
    """Quantile ``u`` of a length distribution: a lognormal around
    ``median`` with ``sigma``, clipped to [min, max]."""
    if d["min"] == d["max"]:
        return int(d["min"])
    x = d["median"] * math.exp(d["sigma"] * NormalDist().inv_cdf(u))
    return int(min(max(round(x), d["min"]), d["max"]))


def _strata(rng, n: int, stratum: int, fn) -> List[Any]:
    """``fn(u)`` at the stratum's quantile points, shuffled per stratum."""
    out: List[Any] = []
    while len(out) < n:
        vals = [fn((j + 0.5) / stratum) for j in range(stratum)]
        out.extend(vals[i] for i in rng.permutation(stratum))
    return out[:n]


def n_requests(traffic: Dict[str, Any], seconds: float) -> int:
    """How many requests a window of ``seconds`` needs: the backlog's
    fixed depth, or every request an open loop makes due in the window
    (rounded up to whole strata, plus one stratum)."""
    arr = traffic["arrivals"]
    if arr["process"] == "backlog":
        return int(arr["n_requests"])
    k = traffic["stratum"]
    return k * (math.ceil(arr["rate"] * seconds / k) + 1)


def prompt_ids(rng, p: Dict[str, Any], n: int, vocab: int,
               image_offset: Optional[int]) -> np.ndarray:
    """Token ids of one prompt of length ``n``: ``any`` id of the
    vocabulary, ``text`` ids (below the image range), or ``image+text``
    (``image_tokens`` image ids, then text ids)."""
    kind = p["ids"]
    if kind == "any":
        return rng.integers(0, vocab, size=n).astype(np.int32)
    text_hi = image_offset if image_offset is not None else vocab
    if kind == "text":
        return rng.integers(1, text_hi, size=n).astype(np.int32)
    if kind == "image+text":
        n_img = min(p["image_tokens"], n)
        img = rng.integers(image_offset, vocab, size=n_img)
        txt = rng.integers(1, text_hi, size=n - n_img)
        return np.concatenate([img, txt]).astype(np.int32)
    raise ValueError(f"unknown prompt ids {kind!r}")


def generate(traffic: Dict[str, Any], seed: int, seconds: float, *,
             vocab: int, image_offset: Optional[int] = None) -> List[Request]:
    """The cell's requests for one run, in due order."""
    rng = np.random.default_rng(seed)
    n = n_requests(traffic, seconds)
    k = traffic["stratum"]
    p, o, arr = traffic["prompt"], traffic["output"], traffic["arrivals"]
    ins = _strata(rng, n, k, lambda u: length_quantile(p, u))
    outs = _strata(rng, n, k, lambda u: length_quantile(o, u))
    if arr["process"] == "backlog":
        dues = [0.0] * n
    elif arr["process"] == "poisson":
        gaps = _strata(rng, n, k, lambda u: -math.log(1.0 - u) / arr["rate"])
        dues = list(np.cumsum(gaps))
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    return [
        Request(rid=i, prompt=prompt_ids(rng, p, ins[i], vocab, image_offset),
                max_new=int(outs[i]), due=float(dues[i]))
        for i in range(n)
    ]


def pad_to(traffic: Dict[str, Any]) -> int:
    """Longest prompt the mix can send (the prefill pad and pool width)."""
    return int(traffic["prompt"]["max"])


def max_new_cap(traffic: Dict[str, Any]) -> int:
    """Longest output the mix can ask for (sets the pool's max length)."""
    return int(traffic["output"]["max"])
