"""The code cell's readers through a whole run on the CPU: the tiny
``dense.code`` cell (greedy HumanEval-shaped requests in an open loop,
prompt chunks through the packed mixed step), traced. A CPU trace holds
the program's host spans but no TPU plane, so its device programs come
from the committed TPU fixture (``fixtures/code_tiny.xplane.pb``: mixed
and decode programs), laid over the CPU trace's own window."""
import dataclasses
import time
from pathlib import Path

import pytest

from bench import harness, host_spans, spec, trace_reduce
from bench.tests import tiny

FIX = Path(__file__).with_name("fixtures") / "code_tiny.xplane.pb"
CELL = "qwen2.5-3b.code"


def _applies(m):
    return "workloads" not in m or CELL in m["workloads"]


def _fixture_programs(real):
    fix = real(str(FIX))

    def reduce(path):
        win = host_spans.stretch(path).window_s
        return trace_reduce.TraceSummary(
            n_devices=1, window_s=win, busy_s=fix.busy_s / fix.window_s * win,
            modules=fix.modules, ops=fix.ops, idle_by_host=fix.idle_by_host)
    return reduce


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setattr("tempfile.tempdir", str(tmp_path_factory.mktemp("tmp")))
    mp.setattr(harness, "enable_compile_cache", lambda: None)
    mp.setattr(trace_reduce, "reduce_trace",
               _fixture_programs(trace_reduce.reduce_trace))
    bm = spec.load_json(spec.ROOT / "BENCHMARK.json")
    cell = dataclasses.replace(
        tiny.tiny_cell("dense.code"),
        end_to_end=[m for m in bm["end_to_end"] if _applies(m)],
        per_layer=[m for m in bm["per_layer"] if _applies(m)])
    try:
        out = harness.run(cell, 2718281831, 3.0, True,
                          t_start=time.perf_counter(), require_tpu=False,
                          peaks=tiny.PEAKS, log=lambda m: None)
    finally:
        mp.undo()
    return cell, out


def test_traced_run_is_correct(traced):
    _, out = traced
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_every_reader_of_the_cell_reads(traced):
    cell, out = traced
    assert set(out["metrics"]) == {m["name"] for m in cell.per_layer}
    for name in ("step_ms.mixed", "mixed_roofline", "host_ms.plan"):
        assert out["metrics"][name]["value"] > 0


def test_mixed_roofline_is_a_share(traced):
    _, out = traced
    assert 0 < out["metrics"]["mixed_roofline"]["value"] <= 100
