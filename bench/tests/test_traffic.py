import numpy as np
import pytest

from bench import traffic
from bench.tests.tiny import TRAFFIC


def _load(name):
    return TRAFFIC[name]


@pytest.mark.parametrize("name", TRAFFIC)
def test_same_seed_same_requests(name):
    tr = _load(name)
    a = traffic.generate(tr, 2**40 + 9, 20.0, vocab=65536, image_offset=57344)
    b = traffic.generate(tr, 2**40 + 9, 20.0, vocab=65536, image_offset=57344)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due) == (y.max_new, y.due)


@pytest.mark.parametrize("name", TRAFFIC)
def test_lengths_within_profile(name):
    tr = _load(name)
    reqs = traffic.generate(tr, 3, 30.0, vocab=65536, image_offset=57344)
    p, o = tr["prompt"], tr["output"]
    for r in reqs:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.max_new <= o["max"]
        assert 0 <= r.prompt.min() and r.prompt.max() < 65536
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)


@pytest.mark.parametrize("name", TRAFFIC)
def test_every_seed_gets_the_same_work(name):
    """Each stratum holds the same sizes and gaps whatever the seed."""
    tr = _load(name)
    k = tr["stratum"]
    runs = [traffic.generate(tr, s, 30.0, vocab=65536, image_offset=57344)
            for s in (1, 2**33 + 1)]
    assert len(runs[0]) == len(runs[1])
    n = len(runs[0]) // k * k
    for i in range(0, n, k):
        for size in (lambda r: len(r.prompt), lambda r: r.max_new):
            sets = [sorted(size(r) for r in run[i:i + k]) for run in runs]
            assert sets[0] == sets[1]
        gaps = [np.diff([0.0] + [r.due for r in run])[i:i + k] for run in runs]
        assert np.allclose(sorted(gaps[0]), sorted(gaps[1]))


def test_prompt_ids_respect_the_image_range():
    t2i, i2t = _load("t2i"), _load("i2t")
    for r in traffic.generate(t2i, 5, 10.0, vocab=65536, image_offset=57344):
        assert r.prompt.max() < 57344 and r.prompt.min() >= 1
    for r in traffic.generate(i2t, 5, 10.0, vocab=65536, image_offset=57344):
        assert (r.prompt[:1024] >= 57344).all()
        assert (r.prompt[1024:] < 57344).all()


def test_open_loop_covers_the_window():
    tr = _load("code")
    reqs = traffic.generate(tr, 4, 30.0, vocab=100, image_offset=None)
    assert reqs[-1].due >= 30.0  # requests run past the window's end
