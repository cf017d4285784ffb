"""A run whose timed path is broken underneath comes out not correct:
the harness's look for a chip is skipped, the rest of the run is driven
at a size a CPU test can hold, and one fault of ``proof.FAULTS`` is
planted in the program each time."""
import functools

import pytest

from cb_support import no_compile_cache, tiny_root  # noqa: F401
from chipbench import proof, run

CELLS = {"unchanged": "tiny-sort", "half_batch": "tiny-sort",
         "answer": "tiny-gshard", "exchange": "tiny-ep4"}


@functools.lru_cache(maxsize=None)
def _faulty_run(root, cell, fault):
    with proof.FAULTS[fault]():
        return run.run(cell, 1, 0.2, False, root=root, require_tpu=False)


@pytest.mark.parametrize("fault", sorted(proof.FAULTS))
def test_fault_is_not_correct(tiny_root, no_compile_cache, fault):
    res = _faulty_run(tiny_root, CELLS[fault], fault)
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_not_correct_on_the_dropless_path(tiny_root,
                                                   no_compile_cache, fault):
    """The faults a one-chip cell can have, on the GShard gate's dropless
    grouped Pallas path too."""
    res = _faulty_run(tiny_root, "tiny-gshard", fault)
    assert res["correct"] is False, res["compared"]


def test_answer_fault_is_not_correct_on_the_sort_path(tiny_root,
                                                      no_compile_cache):
    res = _faulty_run(tiny_root, "tiny-sort", "answer")
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("cell", ["tiny-sort", "tiny-gshard"])
@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_embed_row_gap_fails_the_state_and_batch_faults(
        tiny_root, no_compile_cache, fault, cell):
    """The embedding rows of the first step see a state left unchanged
    (no first moment: infinite) and, at this size, half of the batch left
    out (0.30 and more against the limit 0.09)."""
    got = _faulty_run(tiny_root, cell, fault)["compared"]["embed_row_gap"]
    assert got["value"] > got["limit"], got
