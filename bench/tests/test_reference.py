"""The reference's moment-matching constants are its own fit's output."""
import pytest

from bench import reference


def test_constants_file_is_the_fit():
    a, b = reference.fit_constants(64)
    assert reference.mm_constants(64) == pytest.approx((a, b), rel=1e-9)


def test_nearest_fitted_head_size_serves_another():
    assert reference.mm_constants(16) == reference.mm_constants(64)
