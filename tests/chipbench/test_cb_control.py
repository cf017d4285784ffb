"""The correctness check at a size a CPU test can hold: a sound run of
each path is correct, and the control (the reference in float8, in the
program's place) is not, on three seeds."""
import pytest

from cb_support import no_compile_cache, tiny_root  # noqa: F401
from chipbench import check, reference, run, spec, traffic

SEEDS = (1, 2, 3)


@pytest.mark.parametrize("cell", ["tiny-sort", "tiny-gshard", "tiny-ep4"])
def test_sound_run_is_correct(tiny_root, no_compile_cache, cell):
    res = run.run(cell, 2, 0.2, False, root=tiny_root, require_tpu=False)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("cell", ["tiny-sort", "tiny-ep4"])
def test_control_is_not_correct(tiny_root, cell):
    c = spec.load_cell(cell, tiny_root)
    kw = dict(shards=c.chips, dropless=c.dropless)
    ref = reference.Reference(c.model, c.workload["train"], **kw)
    fp8 = reference.Reference(c.model, c.workload["train"],
                              precision="fp8", **kw)
    for seed in SEEDS:
        ring = traffic.make_ring(c.traffic, c.model["vocab_size"], seed)
        read = check.readings(fp8.train(seed, ring), ref.train(seed, ring))
        assert not check.judge(read, c.limits), (seed, read)
