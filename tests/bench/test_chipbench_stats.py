"""Percentile, rate and sampling arithmetic over all samples of a window."""
import numpy as np
import pytest

from chipbench import harness


@pytest.mark.parametrize("n", [1, 2, 7, 100, 1001])
@pytest.mark.parametrize("q", [0, 50, 95, 99, 100])
def test_percentile_matches_numpy_linear(n, q):
    xs = np.random.default_rng(n).exponential(size=n).tolist()
    assert harness.percentile(xs, q) == pytest.approx(
        float(np.percentile(xs, q)), rel=1e-12, abs=1e-15)


def test_percentile_uses_every_sample():
    xs = [1.0] * 95 + [10.0] * 5
    assert harness.percentile(xs, 95) == pytest.approx(1.45)
    assert harness.percentile(xs[:-1], 95) < harness.percentile(xs, 95)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        harness.percentile([], 95)


def test_rate_is_work_over_the_whole_window():
    assert harness.rate(300, 30.0) == 10.0
    with pytest.raises(ValueError):
        harness.rate(1, 0.0)


def test_reservoir_is_seeded_and_uniform():
    def draw(seed):
        r = harness.Reservoir(3, np.random.default_rng(seed))
        for i in range(50):
            r.offer(i)
        return sorted(r.items)
    assert draw(1) == draw(1)
    counts = np.zeros(50)
    for s in range(2000):
        counts[draw(s)] += 1
    # each item kept with probability 3/50: 120 of 2000 draws
    assert counts.min() > 70 and counts.max() < 180


def test_reservoir_keeps_a_short_stream_whole():
    r = harness.Reservoir(4, np.random.default_rng(0))
    for i in range(3):
        r.offer(i)
    assert r.items == [0, 1, 2]


def test_seed_words_take_large_seeds():
    a = harness.seed_words(2**33 + 5, 1)
    assert 0 <= a < 2**32
    assert a == harness.seed_words(2**33 + 5, 1)
    assert a != harness.seed_words(2**33 + 5, 2)
