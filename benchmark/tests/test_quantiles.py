"""The percentile rule: a timing is reported at the highest percentile
that has ten samples beyond it (choosing-metrics, section 1)."""

import pytest

from benchmark import quantiles as q


def test_percentile_interpolates_between_order_statistics():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert q.percentile(xs, 0) == 10.0
    assert q.percentile(xs, 50) == 30.0
    assert q.percentile(xs, 100) == 50.0
    assert q.percentile(xs, 95) == pytest.approx(48.0)   # 4 * 0.95 = 3.8
    assert q.median([4.0, 1.0, 3.0, 2.0]) == 2.5


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        q.percentile([], 50)


@pytest.mark.parametrize("n, highest", [
    (9, None),      # not even the median has ten beyond it
    (20, 50.0),
    (99, 50.0),     # 9.9 beyond the 90th
    (100, 90.0),
    (199, 90.0),    # 9.95 beyond the 95th
    (200, 95.0),    # why a window wants two hundred steps
    (999, 95.0),
    (1000, 99.0),
    (10000, 99.9),
])
def test_highest_percentile_with_ten_samples_beyond(n, highest):
    assert q.highest_supported_percentile(n) == highest
