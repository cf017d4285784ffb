"""The benchmark's files: BENCHMARK.json keeps to its format, and the
loader finds configurations, cells, traffic and metrics by name alone."""
import json
import re
import shutil

import pytest

from cb_support import ROOT
from chipbench import check, program, spec

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)) and all(map(NAME.match, names))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_per_layer_metric_lists_its_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m.get("workloads") and set(m["workloads"]) <= cells, m["name"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_and_matches_the_program(cell):
    c = spec.load_cell(cell)
    assert c.chips in (1, 4) and c.chips == int(__import__("math").prod(
        c.mesh))
    assert {m["name"] for m in c.end_to_end} == {
        m["name"] for m in BENCH["end_to_end"]}
    assert c.per_layer, "every cell reports a per-layer metric"
    program.model_config(c)         # raises where the file and program differ
    assert set(c.limits) == set(check.NUMBERS)
    assert hasattr(spec.module(c.reference), "Reference")
    assert hasattr(spec.module(c.counts), "step_model_flops")


def _copy_root(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_a_new_cell_and_metric_are_found_by_their_files(tmp_path):
    root = _copy_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "paper16e-switch-sort-1chip-b16",
                               "config": "hetumoe-paper-16e",
                               "traffic": "zipf-copy-b16-s1024",
                               "chips": 1, "why": "a fixture"})
    bench["per_layer"].append({"name": "steps_traced", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "Trainer loop",
                               "moves": "tokens_per_s_per_chip"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = json.loads((root / "chipbench/traffic/"
                          "zipf-copy-b8-s1024.json").read_text())
    traffic["batch"] = 16
    (root / "chipbench/traffic/zipf-copy-b16-s1024.json").write_text(
        json.dumps(traffic))
    shutil.copy(root / "chipbench/workloads/paper16e-switch-sort-1chip.json",
                root / "chipbench/workloads/"
                       "paper16e-switch-sort-1chip-b16.json")
    (root / "chipbench/metrics/steps_traced.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")

    cell = spec.load_cell("paper16e-switch-sort-1chip-b16", root)
    assert cell.batch == 16 and cell.config["name"] == "hetumoe-paper-16e"
    assert "steps_traced" in [m["name"] for m in cell.per_layer]
    reader = spec.metric_reader("steps_traced", root)
    assert reader(type("Ctx", (), {"steps": 7})) == 7.0


def test_missing_files_and_names_are_errors(tmp_path):
    root = _copy_root(tmp_path)
    with pytest.raises(spec.SpecError, match="no cell"):
        spec.load_cell("no-such-cell", root)
    with pytest.raises(spec.SpecError, match="not a valid"):
        spec.load_cell("a cell/with a slash", root)
    with pytest.raises(spec.SpecError, match="no reader"):
        spec.metric_reader("no_such_metric", root)
    (root / "chipbench/traffic/zipf-copy-b8-s1024.json").unlink()
    with pytest.raises(spec.SpecError, match="missing"):
        spec.load_cell("paper16e-switch-sort-1chip", root)


def test_peaks_come_from_the_table_and_unknown_devices_are_errors():
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError, match="no peaks"):
        spec.peaks("TPU v9 imaginary")


def test_config_mismatch_is_an_error(tmp_path):
    root = _copy_root(tmp_path)
    path = root / "chipbench/configs/hetumoe-paper-16e.json"
    cfg = json.loads(path.read_text())
    cfg["model"]["d_model"] = 1024
    path.write_text(json.dumps(cfg))
    with pytest.raises(spec.SpecError, match="d_model"):
        program.model_config(spec.load_cell("paper16e-switch-sort-1chip",
                                            root))
