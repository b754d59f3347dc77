"""bench/step_bytes.py and the readers of the mixed step on stubs: the
bytes a step needs, the roofline share, the packer's host time, and
nothing read where the program has no mixed step or no ``sched.plan``."""
from types import SimpleNamespace

import pytest

from bench import flops, harness, host_spans, spec, step_bytes
from bench.flops import StepRecord
from bench.tests import tiny

QWEN = spec.load_json(spec.BENCH_DIR / "configs" / "qwen2.5-3b.json")


def read(name, run):
    return harness.load_reader(name).read(run)


def test_qwen_sizes():
    assert step_bytes.layer_params(QWEN) == 77_076_992
    # 36 layers, the final norm and the head: 3.086 B parameters (the
    # embedding is read row by row)
    assert step_bytes.weight_bytes(QWEN) == 2 * 3_085_938_688
    assert step_bytes.kv_bytes_per_position(QWEN) == 36_864


@pytest.mark.parametrize("tied", [False, True])
def test_step_bytes_counts_weights_once_and_valid_kv(tied):
    c = dict(tiny.DENSE, tie_word_embeddings=tied)
    rec = StepRecord("mixed", 0.0, 1.0, decode_pos=[3, 9],
                     chunks=[(0, 5), (16, 4)])
    per_pos = step_bytes.kv_bytes_per_position(c)
    assert per_pos == 2 * 2 * 2 * 16 * 2
    want = (step_bytes.weight_bytes(c) + 2 * 64 * 11
            + per_pos * ((4 + 10) + (5 + 20) + 11))
    assert step_bytes.step_bytes(c, rec) == want
    idle = StepRecord("decode", 0.0, 1.0)
    assert step_bytes.step_bytes(c, idle) == step_bytes.weight_bytes(c)


def _trace(mixed_s, n):
    return SimpleNamespace(module_s=lambda needle: (
        (mixed_s, n) if needle == "mixed_step" else (0.0, 0)))


def test_mixed_readers_on_a_stub():
    recs = [StepRecord("mixed", 0, 1, decode_pos=[100] * 64,
                       chunks=[(0, 300), (0, 200)], n_first=2),
            StepRecord("decode", 1, 2, decode_pos=[101] * 64),
            StepRecord("mixed", 2, 3, decode_pos=[102] * 64,
                       chunks=[(300, 130)], n_first=1)]
    run = SimpleNamespace(trace=_trace(0.9, 2), trace_steps=recs,
                          config=QWEN, chips=1, peaks=tiny.PEAKS)
    assert read("step_ms.mixed", run) == pytest.approx(450.0)
    mixed = [recs[0], recs[2]]
    t_flops = sum(flops.step_flops(QWEN, r) for r in mixed) / 1e12
    t_bytes = sum(step_bytes.step_bytes(QWEN, r) for r in mixed) / 1e11
    want = 100 * max(t_flops, t_bytes) / 2 / 0.45
    assert read("mixed_roofline", run) == pytest.approx(want)


@pytest.mark.parametrize("name", ["step_ms.mixed", "mixed_roofline"])
def test_mixed_readers_read_nothing_without_mixed_programs(name):
    decode_only = SimpleNamespace(
        trace=_trace(0.0, 0), config=QWEN, chips=1, peaks=tiny.PEAKS,
        trace_steps=[StepRecord("decode", 0, 1, decode_pos=[5])])
    assert read(name, decode_only) is None
    assert read(name, SimpleNamespace(trace=None, trace_steps=[])) is None


def test_packer_time_a_mixed_step(monkeypatch):
    totals = {"sched.finish": (5, 2.0), "sched.plan": (2, 0.0004)}
    monkeypatch.setattr(host_spans, "for_run", lambda run: host_spans.Stretch(
        window_s=2.0, totals=totals))
    assert read("host_ms.plan", SimpleNamespace()) == pytest.approx(0.2)
    del totals["sched.plan"]  # a program without the span
    assert read("host_ms.plan", SimpleNamespace()) is None


def test_packer_time_reads_nothing_without_a_trace():
    assert read("host_ms.plan", SimpleNamespace(trace=None)) is None
