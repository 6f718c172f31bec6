"""The Zipf generator is a pure function of the seed, the traffic file and the
step, and draws the law it says."""

import numpy as np

from benchmarks.traffic.zipf import Generator

DATA = {"exponent": 1.1}


def test_same_seed_same_batches_whatever_the_order():
    a = Generator(DATA, seed=7, vocab_size=1000, rows=4, seq=64)
    b = Generator(DATA, seed=7, vocab_size=1000, rows=4, seq=64)
    late_first = b.batch(5)
    for step in (1, 2, 5):
        xa, ya = a.batch(step)
        xb, yb = late_first if step == 5 else b.batch(step)
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
    assert xa.dtype == np.int32 and xa.shape == (4, 64)


def test_targets_are_inputs_shifted_by_one():
    x, y = Generator(DATA, seed=1, vocab_size=1000, rows=2, seq=32).batch(1)
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])


def test_other_seed_other_data_and_other_permutation():
    a = Generator(DATA, seed=1, vocab_size=1000, rows=4, seq=64)
    b = Generator(DATA, seed=2, vocab_size=1000, rows=4, seq=64)
    assert not np.array_equal(a.batch(1)[0], b.batch(1)[0])
    assert not np.array_equal(a.token_of_rank, b.token_of_rank)
    assert sorted(a.token_of_rank) == list(range(1000))


def test_frequencies_follow_the_law():
    g = Generator(DATA, seed=3, vocab_size=50, rows=64, seq=1024)
    x, _ = g.batch(1)
    counts = np.bincount(x.ravel(), minlength=50)[g.token_of_rank]  # by rank
    want = np.arange(1, 51.0) ** -1.1
    want /= want.sum()
    np.testing.assert_allclose(counts / counts.sum(), want, atol=0.01)
    assert x.min() >= 0 and x.max() < 50
