"""Record the trace fixture that tests bench/trace_reduce.py, on a chip:

    python bench/tests/record_fixture.py <out_dir>

It runs a small model through the open-loop greedy path (prompt chunks through
the mixed step's Pallas flash kernel, then paged decode steps) for a
short window with the profiler on, and writes ``<out_dir>/trace.xplane.pb``
and ``<out_dir>/steps.json`` (the kind of every traced step).
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

from bench import harness, trace_reduce  # noqa: E402
from bench.tests import tiny  # noqa: E402


def main(out: str) -> None:
    cell = tiny.tiny_cell("dense.code", hidden_size=512,
                          num_attention_heads=4, num_key_value_heads=2,
                          head_dim=128, intermediate_size=1024,
                          vocab_size=1024)
    tmp = tempfile.mkdtemp(prefix="bench_fixture_")
    harness.run(cell, 5, 3.0, True, t_start=time.perf_counter(),
                keep_trace=tmp)
    Path(out).mkdir(parents=True, exist_ok=True)
    shutil.copy(trace_reduce.find_xplane(tmp), Path(out) / "trace.xplane.pb")
    shutil.copy(Path(tmp) / "steps.json", Path(out) / "steps.json")
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
