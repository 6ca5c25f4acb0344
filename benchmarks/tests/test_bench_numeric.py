import numpy as np
import pytest

from benchmarks.harness import numeric


def test_percentile_is_by_rank():
    values = list(range(1, 1001))          # 1..1000
    assert numeric.percentile(values, 0.99) == 990   # ten beyond it
    assert numeric.percentile(values, 0.5) == 500
    assert numeric.percentile([7.0], 0.99) == 7.0
    with pytest.raises(ValueError):
        numeric.percentile([], 0.5)


@pytest.mark.parametrize("n,q,ok", [
    (1000, 0.99, True), (999, 0.99, False), (200, 0.95, True),
    (199, 0.95, False), (20, 0.5, True), (10, 0.99, False)])
def test_ten_samples_beyond(n, q, ok):
    assert numeric.supports(n, q) is ok


def test_median_and_spread():
    assert numeric.median([3.0, 1.0, 2.0, 10.0]) == 2.5
    # quartiles of 1..6 by statistics.quantiles(n=4): 1.75 and 5.25
    assert numeric.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)


def test_bf16_rounding_is_nearest_even():
    x = np.array([1.0, 1.0 + 2 ** -8, 1.0 + 3 * 2 ** -8, 0.1], np.float32)
    got = numeric.to_bf16(x)
    # 1 + 2^-8 is halfway between 1 and 1 + 2^-7: ties to even -> 1.0;
    # 1 + 3*2^-8 is halfway between 1 + 2^-7 and 1 + 2^-6 -> 1 + 2^-6
    assert got[0] == 1.0 and got[1] == 1.0
    assert got[2] == np.float32(1.0 + 2 ** -6)
    assert abs(got[3] - 0.1) / 0.1 < 2 ** -8
    assert (got.view(np.uint32) & 0xFFFF == 0).all()
