"""The arithmetic that decides ``correct``, on hand-written readings."""
import numpy as np

import compare


def test_judge():
    limits = {"a": 0.5, "b": 0, "part_of_a": None}
    ok, out = compare.judge({"a": 0.4, "b": 0.0, "part_of_a": 9.0}, limits)
    assert ok and out["a"] == {"value": 0.4, "limit": 0.5}
    assert out["part_of_a"] == {"value": 9.0, "limit": None}
    for bad in ({"a": 0.6, "b": 0.0}, {"a": 0.4, "b": 1.0}, {"a": 0.4},
                {"a": float("nan"), "b": 0.0}):
        assert not compare.judge(bad, limits)[0], bad


def test_worst_norm_gap_and_moving_leaves():
    want = np.array([1.0, 2.0, 1e-9, 4.0, 3.0])      # median leaf: 2.0
    got = np.array([1.1, 2.0, 0.2, 2.0, 3.0])
    # leaf 2 is all but zero: its gap is held against the median leaf's norm
    gaps = [0.1 / 2.0, 0.0, (0.2 - 1e-9) / 2.0, 2.0 / 4.0, 0.0]
    gap, leaf = compare.worst_norm_gap(got, want)
    assert (leaf, gap) == (3, gaps[3])
    moving = compare.moving_leaves(want)
    assert moving.tolist() == [True, True, False, True, True]
    keep = np.array([True, True, True, False, True])
    gap, leaf = compare.worst_norm_gap(got, want, keep=keep)
    assert leaf == 2 and abs(gap - gaps[2]) < 1e-12
    assert compare.rel_gap(1.01, 1.0) == abs(1.01 - 1.0) / 1.0
