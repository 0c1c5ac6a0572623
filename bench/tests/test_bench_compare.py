"""The numbers that decide ``correct``."""
import numpy as np
import pytest

from bench import compare


def test_norm_gap_takes_the_worst_leaf_against_the_median_floor():
    want = np.array([1.0, 2.0, 4.0, 1e-6])
    got = np.array([1.01, 2.0, 4.2, 2e-6])
    # Leaf 3's own norm is tiny; the median leaf's (1.5) is its floor.
    assert compare.norm_gap(got, want) == pytest.approx(0.05)
    assert compare.norm_gap(got, want, np.array([1, 1, 0, 1], bool)) == \
        pytest.approx(0.01 / 1.0)


def test_still_leaves_are_left_out_of_the_change():
    grads = np.array([1.0, 2.0, 3.0, 1e-4, 5e-3])
    assert compare.moving_leaves(grads).tolist() == [True, True, True,
                                                     False, True]
    prog = {"losses": [2.0, 1.9, 1.8], "grad": grads,
            "change": np.array([1.0, 1.0, 1.0, 9.0, 1.0])}
    ref = {"losses": [2.0, 1.9, 1.8], "grad": grads,
           "change": np.array([1.0, 1.0, 1.0, 1.0, 1.0])}
    assert compare.train_numbers(prog, ref) == {
        "loss_gap": 0.0, "grad_norm_gap": 0.0, "grad_norm_gap_median": 0.0,
        "change_norm_gap": 0.0}


def test_median_leaf_gap():
    ref = {"losses": [1.0], "grad": np.array([1.0, 1.0, 1.0, 1.0, 1.0]),
           "change": np.ones(5)}
    prog = dict(ref, grad=np.array([1.0, 1.01, 1.02, 1.03, 2.0]))
    numbers = compare.train_numbers(prog, ref)
    assert numbers["grad_norm_gap"] == pytest.approx(1.0)
    assert numbers["grad_norm_gap_median"] == pytest.approx(0.02)


def test_state_left_unchanged_reads_one():
    ref = {"losses": [2.0, 1.9, 1.8], "grad": np.array([1.0, 2.0]),
           "change": np.array([3.0, 4.0])}
    prog = dict(ref, change=np.zeros(2))
    assert compare.train_numbers(prog, ref)["change_norm_gap"] == 1.0


def test_percentile_nearest_rank():
    values = list(range(1, 11))
    assert compare.percentile(values, 90) == 9
    assert compare.percentile(values, 100) == 10
    assert compare.percentile([5.0], 90) == 5.0
    with pytest.raises(ValueError):
        compare.percentile([], 90)
