"""The interval arithmetic, on intervals small enough to do by hand."""

from benchmark import intervals as iv


def test_union_merges_touching_and_overlapping_and_drops_empty():
    assert iv.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7)]


def test_intersect():
    a, b = [(0, 4), (6, 10)], [(2, 7), (9, 12)]
    assert iv.intersect(a, b) == [(2, 4), (6, 7), (9, 10)]


def test_subtract():
    assert iv.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert iv.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert iv.subtract([(1, 2)], [(0, 5)]) == []


def test_total():
    assert iv.total([(1, 6), (8, 8.5)]) == 5.5
    assert iv.total([]) == 0


def test_the_parts_of_a_window_add_up():
    window = [(0.0, 100.0)]
    busy = iv.union([(3, 20), (10, 40), (70, 90)])
    gaps = iv.subtract(window, busy)
    assert iv.total(busy) + iv.total(gaps) == 100.0
