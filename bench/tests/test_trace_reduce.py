"""bench/trace_reduce.py against a trace recorded on a TPU v5e
(bench/tests/record_fixture.py: a small model through the open-loop greedy
path, 144 traced steps, 10 of them mixed steps with the Pallas kernel)."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce

FIX = Path(__file__).with_name("fixtures")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_trace(str(FIX / "code_tiny.xplane.pb"))


@pytest.fixture(scope="module")
def steps():
    return json.loads((FIX / "code_tiny.steps.json").read_text())


def test_one_device_busy_within_window(summary):
    assert summary.n_devices == 1
    assert 0 < summary.busy_s < summary.window_s < 5.0


def test_every_traced_step_is_one_program(summary, steps):
    for kind in ("decode", "mixed"):
        _, n = summary.module_s(f"{kind}_step")
        assert n == sum(1 for s in steps if s["kind"] == kind) > 0


def test_idle_and_busy_cover_the_window(summary):
    idle = sum(summary.idle_by_host.values())
    assert idle + summary.busy_s == pytest.approx(summary.window_s, rel=1e-6)
    top = max(summary.idle_by_host, key=summary.idle_by_host.get)
    assert top.startswith("bench.step before jit_")


def test_pallas_kernel_found_in_mixed_steps_only(summary):
    pallas = {k: s for k, s in summary.ops.items() if k.endswith("[pallas]")}
    assert pallas and all(k.startswith("jit_mixed_step:") for k in pallas)
    # every op of a program is inside the program's own device time
    prog_s, _ = summary.module_s("mixed_step")
    assert sum(pallas.values()) <= prog_s


def test_step_time_and_share_of_peak_are_sane(summary, steps):
    s, n = summary.module_s("decode_step")
    assert 0 < s / n < 0.01  # a small model's decode step: under 10 ms
    need = sum(st["flops"] for st in steps)
    mfu = need / (summary.window_s * 197e12)
    assert 0 < mfu < 1


def test_breakdown_names_programs_and_ops(summary):
    b = trace_reduce.breakdown(summary)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(":" in name for name, _ in b["device_ops"])
    assert b["device_ops"][0][0].startswith("jit_decode_step:")


def test_op_names():
    assert trace_reduce.op_name(
        "%fusion.12 = bf16[2]{0} fusion(bf16[2]{0} %a), kind=kOutput, "
        "calls=%f") == "%fusion[kOutput]"
    assert trace_reduce.op_name(
        '%mixed_step.3 = bf16[8]{0} custom-call(%x), '
        'custom_call_target="tpu_custom_call"') == "%mixed_step[pallas]"
    assert trace_reduce.program_name("jit_decode_step(123)") == "jit_decode_step"
