"""Read the comparison's two readings for a cell, in one process:

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed it runs the cell as ``run.py`` does (weights and traffic
from that seed, a window of ``--seconds``) and prints one JSON line with
the program's widest logit gap (``served``: a lower reading) and its
``correct``, and the same for the int8 control (``control``: an upper
reading), whose tokens, the ones int8 puts first at each served position
of the same prompts, go through the same checks against the cell's
limits in the served tokens' place. ``run.py`` never runs the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness, spec  # noqa: E402


def _gap(checks) -> float:
    return checks["max_logit_gap"]["value"]


def _spread(gaps) -> dict:
    """How each side's gaps over the served positions are spread."""
    return {k: {"n": int(v.size), "nonzero": int((v > 0).sum()),
                "mean": float(v.mean()), "p99": float(np.percentile(v, 99))}
            for k, v in gaps.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        try:
            out = harness.run(cell, seed, args.seconds, False, t_start=t,
                              control=True)
        except harness.NoAccelerator as e:
            print(f"[bench] {e}", file=sys.stderr)
            return 2
        print(json.dumps({
            "seed": seed, "served": _gap(out["checks"]),
            "correct": out["correct"],
            "control": _gap(out["control"]["checks"]),
            "control_correct": out["control"]["correct"],
            "spread": _spread(out["control"]["gaps"]),
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "memory_peak_bytes": out["device"]["memory_peak_bytes"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
