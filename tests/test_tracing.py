"""Host spans (core/tracing.py) and the scheduler's use of them: totals
under a fake clock, one ``sched.finish`` and one ``sched.sync`` in every
step of a CFG group mix (plus one ``*.sync`` in a step where a group
finishes), ``busy_s`` read from the spans, and the
spans' ``TraceAnnotation`` events in a profiler trace."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import SMOKE_CONFIGS
from repro.core import profiles
from repro.core.scheduler import Scheduler, ServeRequest
from repro.core.tracing import Spans
from repro.models import get_model, vlm

KEY = jax.random.PRNGKey(0)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_nested_spans_count_seconds_and_self_time():
    clock = FakeClock()
    spans = Spans(clock)
    for _ in range(2):
        with spans("outer"):
            clock.t += 1.0
            with spans("inner", rid=3):
                clock.t += 2.0
                with spans("leaf.sync"):
                    clock.t += 0.5
            with spans("inner", rid=4):
                clock.t += 0.25
            clock.t += 0.125
    t = spans.totals()
    assert t["outer"] == {"count": 2, "seconds": 2 * 3.875,
                          "self_seconds": 2 * 1.125}
    assert t["inner"] == {"count": 4, "seconds": 2 * 2.75,
                          "self_seconds": 2 * 2.25}
    assert t["leaf.sync"] == {"count": 2, "seconds": 1.0, "self_seconds": 1.0}


def test_span_closes_on_error():
    clock = FakeClock()
    spans = Spans(clock)
    with pytest.raises(ValueError):
        with spans("outer"):
            with spans("inner"):
                clock.t += 1.0
                raise ValueError
    with spans("after"):
        clock.t += 1.0
    t = spans.totals()
    assert t["outer"]["seconds"] == 1.0 and t["outer"]["self_seconds"] == 0.0
    assert t["after"]["self_seconds"] == 1.0  # no span left open


@pytest.fixture(scope="module")
def chameleon():
    cfg = SMOKE_CONFIGS["chameleon-34b"].replace(dtype="float32")
    model = get_model(cfg)
    return model, model.init(KEY), vlm.image_token_offset(cfg)


def _cfg_mix(offset, n_groups=3):
    rng = np.random.default_rng(0)
    return [
        ServeRequest(
            rid=i, prompt=rng.integers(0, offset, size=5 + i),
            max_new=4 + 2 * i,
            profile=profiles.ContrastiveProfile(uncond_token=0, guidance=3.0,
                                                mask_offset=offset),
        )
        for i in range(n_groups)
    ]


def _scheduler(model, params):
    return Scheduler(model, params, slots=4, pad_to=8, max_new_cap=8,
                     paged=True, chunked=True, block_size=4,
                     prefill_budget=8)


def _syncs(totals):
    return sum(v["count"] for n, v in totals.items() if n.endswith(".sync"))


def _count(totals, name):
    return totals.get(name, {"count": 0})["count"]


def test_every_step_one_finish_and_one_sync_per_round_trip(chameleon):
    """Per step: one ``sched.finish`` and one ``sched.sync`` (the step's
    device_get: sampled tokens and every lane bank's outputs), plus one
    ``*.sync`` when any group finishes (the finished groups' outputs);
    two ``sched.groups`` spans (bank dispatch, group commit) while groups
    are resident, and one bank program."""
    model, params, offset = chameleon
    sched = _scheduler(model, params)
    waiting = _cfg_mix(offset)
    for r in waiting:
        sched.normalize(r)
    n_steps = 0
    while waiting or sched.has_work:
        while waiting and sched.try_admit(waiting[0], 0.0):
            waiting.pop(0)
        before = sched.spans.totals()
        n_groups, n_done = len(sched.groups), len(sched.finished)
        n_batches = sched.n_group_batches
        sched.step()
        after = sched.spans.totals()
        n_fin = len(sched.finished) - n_done

        def added(name):
            return _count(after, name) - _count(before, name)

        assert added("sched.finish") == added("sched.sync") == 1
        assert _syncs(after) - _syncs(before) == 1 + (n_fin > 0)
        assert added("sched.groups") == (2 if n_groups else 0)
        assert sched.n_group_batches - n_batches == (1 if n_groups else 0)
        if n_groups:
            assert sched.group_lane_trace[-1] == n_groups
        n_steps += 1
    t = sched.spans.totals()
    assert n_steps == sched.n_decode_steps == _count(t, "sched.step") > 0
    assert _count(t, "sched.admit") == 3
    assert _count(t, "sched.prefill") == 6  # cond and uncond prompt each
    assert len(sched.finished) == 3
    # every child lies inside its parent's time (a group's first profile
    # step runs at admission, with its transfer in ``sched.admit.sync``)
    for child, parent in [("sched.blocks", "sched.begin"),
                          ("sched.launch", "sched.begin"),
                          ("sched.groups", "sched.finish"),
                          ("sched.groups.sync", "sched.groups"),
                          ("sched.prefill", "sched.admit"),
                          ("sched.admit.sync", "sched.admit")]:
        assert 0 < t[child]["seconds"] <= t[parent]["seconds"]
        assert t[parent]["self_seconds"] < t[parent]["seconds"]


def test_busy_s_is_admit_begin_and_finish(chameleon):
    model, params, offset = chameleon
    sched = _scheduler(model, params)
    sched.run(_cfg_mix(offset, n_groups=2))
    t = sched.spans.totals()
    want = sum(t[n]["seconds"]
               for n in ("sched.admit", "sched.begin", "sched.finish"))
    assert sched.busy_s == pytest.approx(want) and want > 0


def test_spans_land_on_the_host_plane_of_a_trace(chameleon, tmp_path):
    model, params, offset = chameleon
    sched = _scheduler(model, params)
    reqs = _cfg_mix(offset, n_groups=2)
    for r in reqs:
        r.max_new = 8  # no group finishes in the traced steps
        sched.normalize(r)
        assert sched.try_admit(r, 0.0)
    sched.step()  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            sched.step()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    from jax.profiler import ProfileData

    names = [e.name for pl in ProfileData.from_file(path).planes
             if pl.name.startswith("/host:")
             for ln in pl.lines for e in ln.events]
    assert names.count("sched.sync") == 2
    assert names.count("sched.groups") == 2 * 2  # dispatch and commit
    assert names.count("sched.step") == names.count("sched.finish") == 2


def test_chunked_run_times_the_packer_and_counts_its_lanes():
    """A chunked run of greedy requests: one ``sched.plan`` (inside
    ``sched.blocks``) a mixed step, and a ``mixed_lane_trace`` entry of
    (real tokens, slots + budget) each; a decode step plans nothing."""
    from repro.configs import qwen2_5_3b

    model = get_model(qwen2_5_3b.SMOKE.replace(dtype="float32"))
    sched = Scheduler(model, model.init(KEY), slots=4, pad_to=24,
                      max_new_cap=6, paged=True, chunked=True, block_size=4,
                      prefill_budget=8)
    rng = np.random.default_rng(2)
    reqs = [ServeRequest(rid=i, prompt=rng.integers(0, 512, size=n),
                         max_new=6) for i, n in enumerate([20, 5, 11, 3])]
    sched.run(reqs)
    t = sched.spans.totals()
    n_mixed = sched.n_mixed_steps
    assert 0 < n_mixed < sched.n_decode_steps
    assert t["sched.plan"]["count"] == n_mixed
    assert 0 < t["sched.plan"]["seconds"] <= t["sched.blocks"]["seconds"]
    assert len(sched.mixed_lane_trace) == n_mixed
    chunk_tokens = sum(v for v, _ in sched.mixed_lane_trace)
    assert chunk_tokens >= sum(len(r.prompt) for r in reqs)
    assert all(c == 4 + 8 for _, c in sched.mixed_lane_trace)
