"""bench/host_spans.py and the readers of the program's host spans: reader
arithmetic on a stub, innermost-span gap attribution on synthetic
intervals, a CPU trace of the scheduler's own spans, and the committed TPU
fixture (which has no program spans) read as before."""
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness, host_spans, trace_reduce

FIX = Path(__file__).with_name("fixtures") / "code_tiny.xplane.pb"


def read(name, run):
    return harness.load_reader(name).read(run)


def _stub(monkeypatch, totals):
    s = host_spans.Stretch(window_s=2.0, totals=totals)
    monkeypatch.setattr(host_spans, "for_run", lambda run: s)


def test_readers_on_a_stub(monkeypatch):
    _stub(monkeypatch, {
        "sched.finish": (4, 2.0), "sched.sync": (4, 0.1),
        "sched.groups": (4, 1.8), "sched.group": (128, 1.7),
        "sched.group.sync": (128, 0.05), "sched.step": (4, 2.1)})
    run = SimpleNamespace()
    assert read("host_ms.groups", run) == pytest.approx(1.8 / 4 * 1e3)
    assert read("host_ms.sync", run) == pytest.approx(0.15 / 4 * 1e3)
    assert read("syncs_per_step", run) == pytest.approx(33.0)


@pytest.mark.parametrize("name", ["host_ms.groups", "host_ms.sync",
                                  "syncs_per_step"])
def test_readers_read_nothing_without_a_trace(name):
    assert read(name, SimpleNamespace(trace=None)) is None


@pytest.mark.parametrize("gap, owner", [
    ((3.2, 3.8), "sched.group.profile"),  # inside the innermost span
    ((3.9, 4.5), "sched.groups"),  # astride a boundary: the parent
    ((9.5, 10.5), "bench.step"),  # half covered: the widest overlap
    ((11.0, 12.0), "outside host spans"),
])
def test_gap_goes_to_the_innermost_covering_span(gap, owner):
    spans = [("bench.step", 0.0, 10.0), ("sched.finish", 1.0, 9.0),
             ("sched.groups", 2.0, 8.0), ("sched.group.profile", 3.0, 4.0)]
    assert host_spans.gap_owner(spans, *gap) == owner


def test_fixture_without_program_spans_reads_as_before():
    ts = trace_reduce.reduce_trace(str(FIX))
    s = host_spans.stretch(str(FIX))
    assert s.window_s == ts.window_s and s.steps == 0 and not s.totals
    gaps = host_spans.idle_gaps(str(FIX))
    assert sum(gaps.values()) == pytest.approx(sum(ts.idle_by_host.values()),
                                               rel=1e-9)
    assert sum(gaps.values()) + ts.busy_s == pytest.approx(ts.window_s,
                                                           rel=1e-6)
    assert max(gaps, key=gaps.get).startswith("bench.step before jit_")


def test_readers_find_this_runs_trace(tmp_path, monkeypatch):
    """A CPU trace of a scheduler's steps inside ``bench.step`` spans, in a
    ``bench_trace_*`` directory as the harness leaves it: the readers find
    it by its window and count one sync a step plus one a group."""
    from repro.configs import SMOKE_CONFIGS
    from repro.core import profiles
    from repro.core.scheduler import Scheduler, ServeRequest
    from repro.models import get_model, vlm

    cfg = SMOKE_CONFIGS["chameleon-34b"].replace(dtype="float32")
    model = get_model(cfg)
    off = vlm.image_token_offset(cfg)
    sched = Scheduler(model, model.init(jax.random.PRNGKey(0)), slots=4,
                      pad_to=8, max_new_cap=8, paged=True, chunked=True,
                      block_size=4, prefill_budget=8)
    rng = np.random.default_rng(1)
    for rid in range(2):
        r = ServeRequest(rid=rid, prompt=rng.integers(0, off, size=6),
                         max_new=8, profile=profiles.ContrastiveProfile(
                             uncond_token=0, guidance=3.0, mask_offset=off))
        sched.normalize(r)
        assert sched.try_admit(r, 0.0)
    sched.step()  # compile outside the trace
    tdir = tmp_path / "bench_trace_x"
    with jax.profiler.trace(str(tdir)):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                sched.step()
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    s = host_spans.stretch(trace_reduce.find_xplane(str(tdir)))
    assert s.steps == 3
    run = SimpleNamespace(trace=SimpleNamespace(window_s=s.window_s))
    assert read("syncs_per_step", run) == pytest.approx(1 + 2)
    assert 0 < read("host_ms.sync", run)
    assert 0 < read("host_ms.groups", run) < s.window_s / 3 * 1e3
    other = SimpleNamespace(trace=SimpleNamespace(window_s=s.window_s + 1e-3))
    assert read("syncs_per_step", other) is None  # not this run's trace
