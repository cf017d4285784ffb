"""Helpers and fixtures of the chip benchmark's CPU tests.

``tiny_root`` is a benchmark root of its own (a ``BENCHMARK.json`` and a
``chipbench/`` of data files) whose cells run the paper's model at a
width a CPU test can hold: the sort path on one device, the GShard gate
on the grouped Pallas path (interpreted) on one device, and the grouped
path over four devices.  Its limits were set from CPU readings at this
size (``proof.py --cpu``, seeds 1-4 and 2147483660): the program read
loss gaps up to 1.33e-3, median gradient gaps up to 1.2e-3 and change
gaps up to 5.0e-3; the float8 control read median gradient gaps of
4.0e-3 and more, loss gaps of 2.1e-3 to 4.2e-3 on one device and change
gaps of 6.9e-3 to 1.3e-2.
"""
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_LIMITS = {"loss_gap": 1.6e-3, "grad_norm_median_gap": 2.5e-3,
               "change_gap": 6e-3}


def make_tiny_root(dst: pathlib.Path, limits=TINY_LIMITS) -> pathlib.Path:
    bench = dst / "chipbench"
    bench.mkdir(parents=True)
    for d in ("metrics", "traffic", "workloads", "configs"):
        shutil.copytree(ROOT / "chipbench" / d, bench / d)
    shutil.copy(ROOT / "chipbench" / "peaks.json", bench / "peaks.json")

    def dump(rel, obj):
        (dst / rel).write_text(json.dumps(obj, indent=1))

    cfg = json.loads((ROOT / "chipbench/configs/hetumoe-paper-16e.json")
                     .read_text())
    small = {"d_model": 128, "vocab_size": 512, "num_heads": 4,
             "num_kv_heads": 4, "head_dim": 32, "num_experts": 4,
             "d_ff_expert": 256}
    cfg.update(name="tiny", overrides={
        "d_model": 128, "d_ff": 256, "vocab_size": 512,
        "attention": {"num_heads": 4, "num_kv_heads": 4, "head_dim": 32},
        "moe": {"num_experts": 4, "d_ff_expert": 256}})
    cfg["model"].update(small)
    dump("chipbench/configs/tiny.json", cfg)
    gs = json.loads(json.dumps(cfg))
    gs["name"] = "tiny-gshard"
    gs["overrides"]["moe"]["gate"] = "gshard"
    gs["model"].update(gate="gshard", experts_per_token=2)
    dump("chipbench/configs/tiny-gshard.json", gs)
    for b in (4, 8):
        dump(f"chipbench/traffic/tiny-b{b}.json", {
            "generator": "zipf_copy", "batch": b, "seq": 64,
            "zipf_exponent": 1.0, "copy_fraction": 0.25, "ring": 4})
    train = json.loads((ROOT / "chipbench/workloads/"
                        "paper16e-switch-sort-1chip.json").read_text())["train"]
    grouped = {"dispatch": "grouped", "use_pallas_gate": True}
    for name, mesh, moe in (("tiny-sort", [1, 1], {"dispatch": "sort"}),
                            ("tiny-gshard", [1, 1], grouped),
                            ("tiny-ep4", [1, 4], grouped)):
        dump(f"chipbench/workloads/{name}.json", {
            "mesh": mesh, "path": "test", "moe": moe, "train": train,
            "limits": dict(limits)})
    top = json.loads((ROOT / "BENCHMARK.json").read_text())
    top["configs"] = [
        {"name": n, "source": "test", "file": f"chipbench/configs/{n}.json",
         "reduced": [], "why": "test"} for n in ("tiny", "tiny-gshard")]
    top["workloads"] = [
        {"name": "tiny-sort", "config": "tiny", "traffic": "tiny-b4",
         "chips": 1, "why": "test"},
        {"name": "tiny-gshard", "config": "tiny-gshard", "traffic": "tiny-b4",
         "chips": 1, "why": "test"},
        {"name": "tiny-ep4", "config": "tiny", "traffic": "tiny-b8",
         "chips": 4, "why": "test"}]
    dump("BENCHMARK.json", top)
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture
def no_compile_cache(monkeypatch, tmp_path):
    """Keep the tests' compiles out of the checkout's cache: with the
    variable set, ``enable_compile_cache`` turns nothing on."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
