"""The comparison that decides ``correct``, on made-up readings."""
import math

import pytest

from chipbench import check

LEAVES = {"a": 1.0, "b": 2.0, "c": 4.0, "still": 1e-6}


def _ref():
    return {"loss": [10.0, 9.0, 8.0], "grad_norm": dict(LEAVES),
            "change": {"a": 1.0, "b": 1.0, "c": 1.0, "still": 0.5}}


def test_equal_readings_are_zero_and_correct():
    read = check.readings(_ref(), _ref())
    assert all(read[n]["value"] == 0 for n in check.NUMBERS)
    assert check.NUMBERS == ("loss_gap", "grad_norm_median_gap",
                             "change_gap")
    assert check.judge(read, {n: 0.0 for n in check.NUMBERS})


def test_leaf_gaps_against_its_norm_or_the_median():
    prog = _ref()
    prog["grad_norm"]["a"] = 1.3          # 0.3 over max(1.0, median 1.5)
    prog["grad_norm"]["c"] = 4.4          # 0.4 / 4.0
    read = check.readings(prog, _ref())
    assert read["grad_norm_gap"]["value"] == pytest.approx(0.2)
    assert read["grad_norm_gap"]["leaf"] == "a"
    # gaps 0.2, 0, 0.1, 0: the median of four is 0.05
    assert read["grad_norm_median_gap"]["value"] == pytest.approx(0.05)
    assert "grad_norm_gap" not in check.NUMBERS


def test_loss_gap_is_the_worst_step():
    prog = _ref()
    prog["loss"] = [10.0, 9.09, 8.0]
    assert check.readings(prog, _ref())["loss_gap"]["value"] == \
        pytest.approx(0.01)


def test_still_leaves_are_left_out_of_the_change():
    prog = _ref()
    prog["change"]["still"] = 0.0         # would read 0.5 / 1.0
    read = check.readings(prog, _ref())
    assert read["change_gap"]["value"] == 0
    assert read["change_gap"]["left_out"] == ["still"]


def test_unchanged_state_reads_one():
    prog = _ref()
    prog["change"] = {k: 0.0 for k in prog["change"]}
    assert check.readings(prog, _ref())["change_gap"]["value"] == 1.0


def test_judge_limits_and_non_finite():
    read = check.readings(_ref(), _ref())
    assert not check.judge(read, {"loss_gap": 1.0})      # a missing limit
    prog = _ref()
    prog["loss"][1] = float("nan")
    bad = check.readings(prog, _ref())
    assert math.isinf(bad["loss_gap"]["value"])
    assert not check.judge(bad, {n: 1.0 for n in check.NUMBERS})


def test_lines_name_each_number_with_its_limit():
    lines = check.lines(check.readings(_ref(), _ref()),
                        {n: 0.5 for n in check.NUMBERS})
    assert [l.split()[0] for l in lines] == list(check.NUMBERS)
    assert all("limit 0.5" in l for l in lines)
