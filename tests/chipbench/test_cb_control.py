"""The correctness check at a size a CPU test can hold: a sound run of
each path is correct, and the control (the reference in float8, in the
program's place) is not, on three seeds."""
import functools

import pytest

from cb_support import no_compile_cache, tiny_root  # noqa: F401
from chipbench import check, reference, run, spec, traffic

SEEDS = (1, 2, 3)
CELLS = ["tiny-sort", "tiny-gshard", "tiny-ep4"]


@functools.lru_cache(maxsize=None)
def _sound_run(root, cell):
    return run.run(cell, 2, 0.2, False, root=root, require_tpu=False)


@functools.lru_cache(maxsize=None)
def _control_readings(root, cell):
    """The control's readings against the reference, one per seed."""
    c = spec.load_cell(cell, root)
    kw = dict(shards=c.chips, dropless=c.dropless)
    ref = reference.Reference(c.model, c.workload["train"], **kw)
    fp8 = reference.Reference(c.model, c.workload["train"],
                              precision="fp8", **kw)
    out = []
    for seed in SEEDS:
        ring = traffic.make_ring(c.traffic, c.model["vocab_size"], seed)
        out.append(check.readings(fp8.train(seed, ring),
                                  ref.train(seed, ring)))
    return c.limits, out


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, no_compile_cache, cell):
    res = _sound_run(tiny_root, cell)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("number", check.NUMBERS)
@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_passes_each_number(tiny_root, no_compile_cache, cell,
                                      number):
    got = _sound_run(tiny_root, cell)["compared"][number]
    assert got["value"] <= got["limit"], got


@pytest.mark.parametrize("cell", ["tiny-sort", "tiny-ep4", "tiny-gshard"])
def test_control_is_not_correct(tiny_root, cell):
    limits, reads = _control_readings(tiny_root, cell)
    for seed, read in zip(SEEDS, reads):
        assert not check.judge(read, limits), (seed, read)


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_embed_row_gap(tiny_root, cell):
    """The embedding rows alone tell the control from the program, on the
    dropless path too, where routing flips blur the leaf norms."""
    limits, reads = _control_readings(tiny_root, cell)
    for seed, read in zip(SEEDS, reads):
        assert read["embed_row_gap"]["value"] > limits["embed_row_gap"], (
            seed, read["embed_row_gap"])
