"""Whole runs at a size a CPU test holds: the harness's look for a chip
is skipped and everything else of a run is driven, on the CPU."""
import functools
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec
from bench.tests import tiny

CELLS = list(tiny.CELLS)


def _run(cell, seed=3, **kw):
    return harness.run(cell, seed, 2.0, False, t_start=time.perf_counter(),
                       require_tpu=False, peaks=tiny.PEAKS,
                       log=lambda m: None, **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = _run(tiny.tiny_cell(workload))
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    cell = tiny.tiny_cell(workload)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert out["attempted"] > 0 and out["failed"] == 0


def _on_steps(fault):
    """Plant ``fault(step, *args)`` under the scheduler's decode and mixed
    steps."""
    def plant(sched):
        for name in ("decode_step", "mixed_step"):
            step = getattr(sched._steps, name)
            setattr(sched._steps, name, functools.partial(fault, step))
    return plant


def _alter_tokens(sched):
    """A token altered where it is produced: every 3rd step's sampled
    tokens (greedy rows) are changed, and every 3rd step's logits leave
    the step with the vocabulary's last logit boosted in every row, so a
    group's pick follows it whatever code commits the group."""
    n_sample, n_step = [0], [0]
    sample = sched._sample

    def bad_sample(logits):
        n_sample[0] += 1
        toks = np.array(sample(logits))
        return (toks + 1) % logits.shape[-1] if n_sample[0] % 3 == 0 else toks

    def boosted(step, *args):
        logits, cache = step(*args)
        n_step[0] += 1
        if n_step[0] % 3 == 0:
            logits = logits.at[:, -1].add(1e4)
        return logits, cache

    sched._sample = bad_sample
    _on_steps(boosted)(sched)


def _batched_group_commit(sched):
    """Stand in a group commit that never calls ``_advance_group``, as one
    batched group step over all resident groups would not: every group's
    profile step is dispatched, then one sync serves them all. From then
    on ``_advance_group`` raises where the step path reaches it (a group's
    admission still takes its first token through it)."""
    from repro.core import sampling

    stepping = [False]
    step, advance = sched.step, sched._advance_group

    def guarded_step():
        stepping[0] = True
        try:
            return step()
        finally:
            stepping[0] = False

    def guarded_advance(g, rows, now):
        if stepping[0]:
            raise AssertionError("the step path called _advance_group")
        return advance(g, rows, now)

    def commit(logits, now):
        groups = list(sched.groups.values())
        outs = []
        for g in groups:
            key = jax.random.fold_in(
                sampling.request_key(sched.base_key, g.req.rid), g.n_generated)
            out = g.profile.step(g.pstate, logits[g.slot_rows], key)
            g.pstate = out.state
            outs.append((out.feed, out.done))
        done = []
        for g, (feed, fin) in zip(groups, jax.device_get(outs)):
            g.kv_len += 1
            g.n_generated += 1
            g.req.t_tokens.append(now)
            for i, s in enumerate(g.slots):
                sched._token[s] = int(feed[i])
                sched._ngen[s] = g.n_generated
            if (fin is not None and bool(fin.all())
                    or g.n_generated >= g.req.max_new):
                sched._finish_group(g, now)
                done.append(g.req)
        return done

    sched.step, sched._advance_group = guarded_step, guarded_advance
    sched._commit_groups = commit


def test_batched_group_commit_is_correct():
    """The stand-in group commit serves what the program serves: a sound
    run through it reads correct."""
    out = _run(tiny.tiny_cell("chameleon-34b-l6.t2i"),
               fault=_batched_group_commit)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_altered_token_is_not_correct(workload):
    """The fault reads false through the program's own group commit and,
    where the cell decodes in groups, through the stand-in that never
    calls ``_advance_group``."""
    plants = [_alter_tokens]
    traffic = tiny.TRAFFIC[tiny.CELLS[workload][1]]
    if traffic["decoding"]["profile"] == "contrastive":
        plants.append(lambda s: (_batched_group_commit(s), _alter_tokens(s)))
    for plant in plants:
        out = _run(tiny.tiny_cell(workload), fault=plant)
        assert not out["correct"]
        assert out["checks"]["max_logit_gap"]["value"] > \
            out["checks"]["max_logit_gap"]["limit"]


def _state_unchanged(step, model, params, cache, *rest):
    """A step that returns the cache it was given: the tokens it runs
    never reach the KV pool."""
    kept = jax.tree.map(jnp.copy, cache)  # the step donates its cache
    logits, _ = step(model, params, cache, *rest)
    return logits, kept


def _half_batch(step, *args):
    """Half of the batch left out: every odd row gets the row before's
    logits."""
    logits, cache = step(*args)
    return logits.at[1::2].set(logits[0::2]), cache


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("workload", ["chameleon-34b-l6.t2i",
                                      "chameleon-34b-l6.i2t"])
def test_broken_step_is_not_correct(workload, fault):
    """Faults of the step. (At this size a random dense model's greedy
    tokens barely depend on their context, so the dense mix reads a gap
    of 0 with its cache left unchanged and is not held to this.)"""
    out = _run(tiny.tiny_cell(workload), fault=_on_steps(fault))
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]


def test_int8_control_reads_above_the_program():
    """The control: the tokens the reference in int8 puts first take the
    served tokens' place in the same checks, against the cell's limits,
    and read a wider gap than the program on every seed. (At this size
    the gaps are too small to fail the cell's limit; the control is held
    to the limit only at the cell's size, on the chip: PERF.md section 6.)"""
    cell = tiny.tiny_cell("chameleon-34b-l6.t2i", hidden_size=256,
                          intermediate_size=512, head_dim=64,
                          vocab_size=1024, image_vocab=256)
    for seed in (1, 3, 4):
        out = _run(cell, seed=seed, control=True)
        ctl = out["control"]
        assert out["correct"]
        assert set(ctl["checks"]) == set(out["checks"])
        for k, v in ctl["checks"].items():
            assert v["limit"] == out["checks"][k]["limit"]
        gap = ctl["checks"]["max_logit_gap"]
        assert gap["value"] > out["checks"]["max_logit_gap"]["value"]
        assert ctl["correct"] == (gap["value"] <= gap["limit"])


def test_cli_without_a_chip_prints_no_result():
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload",
         "chameleon-34b-l6.t2i", "--seed", "0", "--seconds", "10",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert "metrics" not in p.stdout
    with pytest.raises(json.JSONDecodeError):
        json.loads(p.stdout.strip().splitlines()[-1] if p.stdout.strip() else "")
