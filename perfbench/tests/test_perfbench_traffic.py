"""The open-loop generator's schedule and the percentile arithmetic."""
import numpy as np
import pytest

from perfbench.harness import traffic


def test_arrivals_offer_the_same_work_for_every_seed():
    a = traffic.open_loop_arrivals(7, 500.0, 30.0)
    b = traffic.open_loop_arrivals(2**40 + 3, 500.0, 30.0)
    assert len(a) == len(b) == 15_000
    assert np.all(np.diff(a) > 0) and a[0] == 0.0
    # the same multiset of gaps, another order
    ga = np.sort(np.diff(np.concatenate([[0.0], a])))
    gb = np.sort(np.diff(np.concatenate([[0.0], b])))
    assert not np.array_equal(np.diff(a), np.diff(b))
    assert a[-1] == pytest.approx(b[-1], rel=1e-3)
    assert np.mean(np.diff(a)) == pytest.approx(1 / 500.0, rel=1e-2)
    assert len(ga) == len(gb)


def test_arrivals_repeat_for_one_seed():
    np.testing.assert_array_equal(traffic.open_loop_arrivals(3, 100.0, 2.0),
                                  traffic.open_loop_arrivals(3, 100.0, 2.0))


def test_arrivals_are_poisson_like():
    a = traffic.open_loop_arrivals(11, 1000.0, 20.0)
    gaps = np.diff(a)
    # exponential: the standard deviation equals the mean
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.05)


def test_histories_lengths_and_padding():
    h = traffic.histories(5, 100, 50, 1000, 5, 11.0, 50)
    lengths = np.sum(h >= 0, axis=1)
    assert h.shape == (100, 50) and h.dtype == np.int32
    assert lengths.min() == 5 and lengths.max() <= 50
    for row, n in zip(h, lengths):
        assert np.all(row[:n] >= 0) and np.all(row[n:] == -1)
    same = traffic.histories(6, 100, 50, 1000, 5, 11.0, 50)
    np.testing.assert_array_equal(np.sort(lengths), np.sort(np.sum(same >= 0, axis=1)))


def test_history_lengths_keep_the_cited_mean_and_bounds():
    lengths = traffic.history_lengths(2**40 + 1, 20_000, 5, 11.0, 50)
    assert lengths.min() == 5 and lengths.max() == 50
    assert np.mean(lengths) == pytest.approx(11.0, abs=0.02)
    assert np.median(lengths) < np.mean(lengths)  # skewed to short histories
    assert np.all(traffic.history_lengths(1, 10, 7, 7.0, 50) == 7)
    with pytest.raises(ValueError):
        traffic.history_lengths(1, 10, 5, 60.0, 50)


@pytest.mark.parametrize("values,want", [
    (list(range(1, 101)), 95), (list(range(1, 21)), 19), ([3.0], 3.0),
    ([5, 1, 4, 2, 3], 5)])
def test_p95_nearest_rank(values, want):
    assert traffic.p95(values) == want


def test_seeds_are_wider_than_32_bits():
    assert traffic.program_seed(2**33 + 5) < 2**31
    import jax

    k1 = traffic.jax_key(2**33 + 5)
    k2 = traffic.jax_key(5)
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    assert not np.array_equal(np.asarray(jax.random.PRNGKey(2**33 + 5)), np.asarray(k1))
