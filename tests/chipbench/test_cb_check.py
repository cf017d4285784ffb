"""The comparison that decides ``correct``, on made-up readings."""
import math

import numpy as np
import pytest

from chipbench import check

LEAVES = {"a": 1.0, "b": 2.0, "c": 4.0, "still": 1e-6}
IDS = np.arange(3, 503, dtype=np.int32)         # 500 touched rows
ROWS = np.random.default_rng(0).normal(size=(500, 64)) * np.random.default_rng(
    1).lognormal(size=(500, 1))                 # rows of unequal norms


def _ref():
    return {"loss": [10.0, 9.0, 8.0], "grad_norm": dict(LEAVES),
            "change": {"a": 1.0, "b": 1.0, "c": 1.0, "still": 0.5},
            "embed_ids": IDS.copy(), "embed_rows": ROWS.copy()}


def _dense(rel):
    """Every row moved by ``rel`` of its norm, in a random direction."""
    noise = np.random.default_rng(2).normal(size=ROWS.shape)
    noise *= (rel * np.linalg.norm(ROWS, axis=1)
              / np.linalg.norm(noise, axis=1))[:, None]
    return ROWS + noise


def test_equal_readings_are_zero_and_correct():
    read = check.readings(_ref(), _ref())
    assert all(read[n]["value"] == 0 for n in check.NUMBERS)
    assert check.NUMBERS == ("loss_gap", "grad_norm_median_gap",
                             "change_gap", "embed_row_gap")
    assert check.judge(read, {n: 0.0 for n in check.NUMBERS})


def test_row_gap_keeps_a_dense_change_and_drops_a_sparse_one():
    """A large change to 2% of the rows, as routing flips make, stays
    under a limit that a 1% change to every row, as a lower precision
    makes, exceeds."""
    limit = 0.005
    sparse = _ref()
    flipped = np.random.default_rng(3).choice(500, 10, replace=False)
    sparse["embed_rows"][flipped] *= -3.0
    got = check.readings(sparse, _ref())["embed_row_gap"]["value"]
    assert got <= limit
    dense = dict(_ref(), embed_rows=_dense(0.01))
    got = check.readings(dense, _ref())["embed_row_gap"]["value"]
    assert got == pytest.approx(0.01, rel=1e-3) and got > limit


@pytest.mark.parametrize("factor", [0.3, 1.7])
def test_row_gap_takes_out_one_global_scale(factor):
    """Clipping multiplies one side's rows by one factor: that factor is
    taken out, and only the dense change is left."""
    prog = dict(_ref(), embed_rows=factor * _dense(0.01))
    read = check.readings(prog, _ref())["embed_row_gap"]
    assert read["value"] == pytest.approx(0.01, rel=1e-3)
    assert read["scale"] == pytest.approx(1 / factor, rel=1e-3)
    assert read["rows"] == 500


def _nan_row(r):
    r["embed_rows"][7, 3] = np.nan


@pytest.mark.parametrize("spoil", [
    lambda r: r.pop("embed_rows"),
    lambda r: r.update(embed_rows=None),
    _nan_row,
    lambda r: r.update(embed_rows=r["embed_rows"][:-1]),
    lambda r: r.update(embed_ids=r["embed_ids"] + 1),
    lambda r: r.update(embed_rows=np.zeros_like(ROWS)),     # state unchanged
], ids=["missing", "none", "non-finite", "short", "other-ids", "zero"])
def test_row_gap_reads_infinite_where_rows_are_missing_or_bad(spoil):
    prog = _ref()
    spoil(prog)
    read = check.readings(prog, _ref())
    assert math.isinf(read["embed_row_gap"]["value"])
    assert not check.judge(read, {n: 1.0 for n in check.NUMBERS})


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
