"""The calibration set is a function of the seed, seeds up to and past 32
bits included."""

import bench_tiny  # noqa: F401
import numpy as np
import pytest

from lib import traffic


def test_calibration_tokens_deterministic():
    a = traffic.calib_tokens(4, 64, 32064, 2**40 + 1)
    assert a.shape == (4, 64) and a.dtype == np.int32
    assert (a == traffic.calib_tokens(4, 64, 32064, 2**40 + 1)).all()
    assert not (a == traffic.calib_tokens(4, 64, 32064, 2**40 + 2)).all()


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**33 + 12345])
def test_calibration_tokens_in_vocabulary_and_zipf(seed):
    a = traffic.calib_tokens(8, 256, 1000, seed)
    assert a.min() >= 0 and a.max() < 1000
    # Rank 1 of a Zipf law over 1000 ids holds 1/H(1000) ≈ 13 % of draws.
    top = np.bincount(a.ravel(), minlength=1000).max() / a.size
    assert 0.10 < top < 0.17


def test_seeded_choices_differ_by_salt_and_seed():
    assert traffic.rng_for(2**33, "check").integers(1 << 30) == traffic.rng_for(
        2**33, "check").integers(1 << 30)
    assert traffic.rng_for(2**33, "check").integers(1 << 30) != traffic.rng_for(
        2**33, "calib").integers(1 << 30)
    assert traffic.rng_for(2**33, "check").integers(1 << 30) != traffic.rng_for(
        2**33 + 1, "check").integers(1 << 30)
