"""The comparison that decides ``correct``: worst-leaf gaps of norms."""
import math

from benchlib import check


def test_gaps_by_worst_leaf():
    ref = {"losses": [10.0, 10.0], "g0": [4.0, 1.0, 2.0],
           "dx": [4.0, 1.0, 2.0], "step": [4.0, 1.0, 2.0]}
    got = {"losses": [10.01, 9.98], "g0": [4.4, 1.1, 2.0],
           "dx": [4.0, 1.5, 2.0]}
    n = check.gaps(got, ref)
    assert math.isclose(n["loss_gap"], 0.002)
    # leaf 0: 0.4 / 4; leaf 1: 0.1 over the median leaf's 2
    assert math.isclose(n["g0_gap"], 0.1)
    # leaf 1's 0.5 is measured against the median leaf (2), not its own 1
    assert math.isclose(n["dx_gap"], 0.25)


def test_leaves_without_gradient_are_left_out_of_the_change():
    ref = {"losses": [1.0], "g0": [1.0, 1.0, 1e-9], "dx": [1.0, 1.0, 1e-9],
           "step": [1.0, 1.0, 1e-9]}
    got = {"losses": [1.0], "g0": [1.0, 1.0, 1e-9], "dx": [1.0, 1.0, 5.0]}
    assert check.gaps(got, ref)["dx_gap"] == 0.0


def test_leaves_whose_stored_change_is_rounding_are_left_out():
    # leaf 2's update is under the stored dtype's resolution: as stored it
    # moved 0.5 where the update was 2.0, so which coordinates move is
    # rounding and the leaf is not compared
    ref = {"losses": [1.0], "g0": [1.0, 1.0, 1.0], "dx": [1.0, 1.0, 0.5],
           "step": [1.0, 1.05, 2.0]}
    got = {"losses": [1.0], "g0": [1.0, 1.0, 1.0], "dx": [1.0, 1.0, 3.0]}
    assert check.gaps(got, ref)["dx_gap"] == 0.0


def test_unchanged_state_reads_one():
    ref = {"losses": [1.0], "g0": [3.0, 1.0], "dx": [3.0, 1.0],
           "step": [3.0, 1.0]}
    got = {"losses": [1.0], "g0": [3.0, 1.0], "dx": [0.0, 0.0]}
    assert check.gaps(got, ref)["dx_gap"] == 1.0


def test_verdict():
    limits = {k: {"limit": 0.5} for k in check.NUMBERS}
    ok, checks = check.verdict({"loss_gap": 0.1, "g0_gap": 0.2,
                                "dx_gap": 0.4}, limits)
    assert ok and checks["dx_gap"] == {"value": 0.4, "limit": 0.5}
    assert not check.verdict({"loss_gap": 0.1, "g0_gap": float("nan"),
                              "dx_gap": 0.4}, limits)[0]
    assert not check.verdict({"loss_gap": 0.6, "g0_gap": 0.2,
                              "dx_gap": 0.4}, limits)[0]
