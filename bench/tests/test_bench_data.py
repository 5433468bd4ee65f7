"""The benchmark's copy of the stream generator."""

import numpy as np
import pytest

from bench.data import SPECS, Stream, gaussian_mixtures


@pytest.mark.parametrize("n,seed", [(500, 0), (2000, 7), (1500, 2**33 + 5)])
def test_copy_matches_program_generator(n, seed):
    from repro.data.synthetic import dataset

    X, y = dataset("chem", n, seed=seed)
    Xb, yb = gaussian_mixtures(n, seed=seed, **SPECS["chem"])
    np.testing.assert_array_equal(X, Xb)
    np.testing.assert_array_equal(y, yb)


def test_stream_first_chunk_is_the_generator():
    from repro.data.synthetic import dataset

    X, _ = dataset("chem", 1024, seed=11)
    s = Stream("chem", 11, chunk=1024)
    got, pos = s.take(1000)
    np.testing.assert_array_equal(got, X[:1000])
    np.testing.assert_array_equal(pos, np.arange(1000))


def test_stream_chunks_are_addressable_and_seeded():
    a, b = Stream("chem", 3, chunk=256), Stream("chem", 3, chunk=256)
    parts = [a.take(100)[0] for _ in range(9)]  # crosses three chunk edges
    np.testing.assert_array_equal(np.concatenate(parts), a.rows(np.arange(900)))
    np.testing.assert_array_equal(np.concatenate(parts), b.take(900)[0])
    other = Stream("chem", 4, chunk=256).take(900)[0]
    assert not np.array_equal(other, b.rows(np.arange(900)))


def test_later_chunks_keep_the_mixture():
    s = Stream("chem", 5, chunk=4096)
    first = s.take(4096)[0]
    later = s.take(4096 * 3)[0]
    assert np.all(np.isfinite(later))
    # same centres and scales: the spread of later chunks matches the first
    np.testing.assert_allclose(later.std(axis=0), first.std(axis=0), rtol=0.15)
    fork = s.fork(1).take(4096)[0]
    assert not np.array_equal(fork, first)
    np.testing.assert_allclose(fork.mean(axis=0), first.mean(axis=0), atol=0.5)
