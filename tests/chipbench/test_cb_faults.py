"""A run whose timed path is broken underneath comes out not correct:
the harness's look for a chip is skipped, the rest of the run is driven
at a size a CPU test can hold, and one fault of ``proof.FAULTS`` is
planted in the program each time."""
import pytest

from cb_support import no_compile_cache, tiny_root  # noqa: F401
from chipbench import proof, run

CELLS = {"unchanged": "tiny-sort", "half_batch": "tiny-sort",
         "answer": "tiny-gshard", "exchange": "tiny-ep4"}


@pytest.mark.parametrize("fault", sorted(proof.FAULTS))
def test_fault_is_not_correct(tiny_root, no_compile_cache, fault):
    with proof.FAULTS[fault]():
        res = run.run(CELLS[fault], 1, 0.2, False, root=tiny_root,
                      require_tpu=False)
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_not_correct_on_the_dropless_path(tiny_root,
                                                   no_compile_cache, fault):
    """The faults a one-chip cell can have, on the GShard gate's dropless
    grouped Pallas path too."""
    with proof.FAULTS[fault]():
        res = run.run("tiny-gshard", 1, 0.2, False, root=tiny_root,
                      require_tpu=False)
    assert res["correct"] is False, res["compared"]
