"""The benchmark's pieces, found by name.

``BENCHMARK.json`` at the root names the configurations, cells and
metrics.  Everything that belongs to one of them sits in a file of its
own under ``chipbench/``, found from its name alone:

    configs/<config>.json     a model configuration (``file`` in BENCHMARK.json)
    traffic/<traffic>.json    a traffic mix, read by ``traffic.make_ring``
    workloads/<cell>.json     a cell: mesh, dispatch path, optimizer, limits
    metrics/<metric>.py       a per-layer metric: ``read(ctx) -> float | None``
    <reference>.py            a configuration's plain reference
    <counts>.py               a configuration's operation and byte counts
    peaks.json                peaks per ``device_kind``, with their source

A configuration file names its reference and counts modules under the
keys ``"reference"`` and ``"counts"`` (default ``reference`` and
``counts``), each a module ``chipbench/<name>.py`` found from its name
alone (:func:`module`):

* a reference module gives ``Reference(model, train, *, shards,
  dropless, precision="f32")`` (``model``: the file's ``model`` numbers;
  ``train``: the cell file's ``train``; ``precision="fp8"``: the
  control), whose ``.train(seed, ring, steps, device)`` returns the
  first steps' ``loss`` list, per-leaf ``grad_norm`` and ``change``
  norms under the program's canonical leaf names, and the first step's
  clipped gradient of the embedding table at the ids of the first
  batch's inputs (``embed_ids``, sorted and unique, and ``embed_rows``,
  one row per id), as ``check.readings`` compares them;
* a counts module gives ``step_model_flops(model, batch, seq)``, the
  model operations of one training step, which ``step_mfu`` reads.  Any
  other function in it is read only by the metrics whose ``workloads``
  list the cell.

The readers get the cell's counts module as ``ctx.counts``.  So a later
change adds a configuration of another architecture, a traffic mix, a
cell or a metric by adding files and entries, without editing any file
here.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re
from dataclasses import dataclass, field
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_DIR = "chipbench"
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class SpecError(ValueError):
    """A name, file or entry of the benchmark is missing or malformed."""


def _name(s: str) -> str:
    if not isinstance(s, str) or not _NAME.match(s):
        raise SpecError(f"not a valid benchmark name: {s!r}")
    return s


def _read_json(path: pathlib.Path) -> Any:
    if not path.is_file():
        raise SpecError(f"missing benchmark file {path}")
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names."""
    name: str
    chips: int
    config: Dict[str, Any]          # configs/<config>.json
    traffic_name: str
    traffic: Dict[str, Any]         # traffic/<traffic>.json
    workload: Dict[str, Any]        # workloads/<cell>.json
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)

    @property
    def model(self) -> Dict[str, Any]:
        return self.config["model"]

    @property
    def batch(self) -> int:
        return int(self.traffic["batch"])

    @property
    def seq(self) -> int:
        return int(self.traffic["seq"])

    @property
    def mesh(self) -> tuple:
        return tuple(int(n) for n in self.workload["mesh"])

    @property
    def dropless(self) -> bool:
        """Whether the cell's dispatch keeps every assignment (``grouped``)
        or drops those past each expert's capacity (``sort``)."""
        return self.workload["moe"].get("dispatch", "sort") == "grouped"

    @property
    def limits(self) -> Dict[str, float]:
        return dict(self.workload.get("limits", {}))

    @property
    def reference(self) -> str:
        """The name of the configuration's reference module."""
        return self.config.get("reference", "reference")

    @property
    def counts(self) -> str:
        """The name of the configuration's counts module."""
        return self.config.get("counts", "counts")


def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return _read_json(pathlib.Path(root) / "BENCHMARK.json")


def _applies(metric: Dict[str, Any], cell: str,
             cell_e2e: List[str]) -> bool:
    """A metric with a ``workloads`` list applies to the cells it lists;
    one without applies to every cell that reports what it moves (or,
    for an end-to-end metric, to every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in cell_e2e


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    root = pathlib.Path(root)
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == _name(name)),
                 None)
    if entry is None:
        raise SpecError(f"no cell {name!r} in {root / 'BENCHMARK.json'}; "
                        f"cells: {[w['name'] for w in bench['workloads']]}")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"cell {name!r} names unknown config "
                        f"{entry['config']!r}")
    config = _read_json(root / cfg_entry["file"])
    if config.get("name") != cfg_entry["name"]:
        raise SpecError(f"{cfg_entry['file']} holds config "
                        f"{config.get('name')!r}, not {cfg_entry['name']!r}")
    traffic_name = _name(entry["traffic"])
    traffic = _read_json(root / BENCH_DIR / "traffic" / f"{traffic_name}.json")
    workload = _read_json(root / BENCH_DIR / "workloads" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic_name=traffic_name, traffic=traffic,
                workload=workload, end_to_end=e2e, per_layer=per_layer)


def _load(path: pathlib.Path, prefix: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        prefix + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: pathlib.Path = ROOT
                  ) -> Callable[[Any], Optional[float]]:
    """The ``read`` function of ``metrics/<name>.py``."""
    path = pathlib.Path(root) / BENCH_DIR / "metrics" / f"{_name(name)}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {name!r} at {path}")
    return _load(path, "chipbench_metric_").read


def module(name: str, root: pathlib.Path = ROOT) -> ModuleType:
    """The module ``chipbench/<name>.py``: a configuration's reference or
    counts."""
    path = pathlib.Path(root) / BENCH_DIR / f"{_name(name)}.py"
    if not path.is_file():
        raise SpecError(f"no module {name!r} at {path}")
    return _load(path, "chipbench_module_")


def peaks(device_kind: str, root: pathlib.Path = ROOT) -> Dict[str, float]:
    """Peak bf16 FLOP/s, HBM bytes/s and HBM bytes of ``device_kind``;
    a device that is not in ``peaks.json`` is an error."""
    table = _read_json(pathlib.Path(root) / BENCH_DIR / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device_kind {device_kind!r} in "
                        f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
