"""Metric arithmetic on synthetic request records."""
from types import SimpleNamespace

import pytest

from bench import harness
from bench.flops import StepRecord


def _req(due, first=None, tokens=()):
    return SimpleNamespace(t_arrival=due, t_first=first, t_tokens=list(tokens))


def _run(reqs, seconds=10.0, steps=()):
    return SimpleNamespace(requests=reqs, seconds=seconds, steps=list(steps),
                           setup_s=3.5, trace=None, trace_steps=[])


def read(name, run):
    return harness.load_reader(name).read(run)


def test_output_tokens_count_only_inside_the_window():
    r = _req(0.0, 1.0, tokens=[1.0, 2.0, 9.5, 10.0, 10.5, 11.0])
    assert read("output_tok_s", _run([r])) == pytest.approx(4 / 10.0)


def test_tpot_over_tokens_inside_the_window():
    a = _req(0.0, 0.0, tokens=[0.0, 1.0, 2.0, 11.0])  # 3 in window: 1.0 s
    b = _req(0.0, 0.0, tokens=[9.0])  # one token: not counted
    c = _req(0.0, 0.0, tokens=[0.0, 0.5])  # 0.5 s
    v = read("tpot_p90_ms", _run([a, b, c]))
    import numpy as np
    assert v == pytest.approx(np.percentile([1.0, 0.5], 90) * 1e3)


def test_counters():
    steps = [StepRecord("decode", 0, 1, occupancy=0.5, block_use=0.2),
             StepRecord("decode", 1, 2, occupancy=1.0, block_use=0.4)]
    run = _run([], steps=steps)
    assert read("slot_occupancy", run) == pytest.approx(75.0)
    assert read("kv_block_use", run) == pytest.approx(40.0)
    assert read("setup_s", run) == 3.5


@pytest.mark.parametrize("name", ["step_ms.decode", "idle_share", "mfu"])
def test_trace_metrics_read_nothing_without_a_trace(name):
    run = _run([])
    run.chips = 1
    assert read(name, run) is None


def test_no_request_no_tail():
    assert read("tpot_p90_ms", _run([])) is None
