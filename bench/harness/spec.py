"""What one cell is: its entry in BENCHMARK.json, its configuration file,
its traffic file, the builder of its model, and the per-layer metric
readers it reports.

Everything is found by name: a configuration ``<name>`` is
``bench/configs/<name>.json``; the model it describes is built by
``bench/builders/<builder>.py``, the file's ``"builder"`` (``llama`` where
it names none), whose ``model_config(config)`` returns the program's
``ModelConfig``; a traffic mix ``<name>`` is
``bench/traffic/<name>.json`` and a per-layer metric ``<name>`` is read by
``bench/metrics/<name>.py``, or, where that file does not exist, by the
reader of the name's part before its first dot: ``mfu.decode`` and
``mfu.docs`` (one quantity, split by the end-to-end metric it moves) share
``bench/metrics/mfu.py``.  The configuration's ``deployment`` may set
``dp`` and ``tp`` (1 and 1 by default): the program serves the cell on a
(dp, tp) mesh, and dp x tp has to be the cell's ``chips``.  Adding a cell,
of a new model family too, adds files and a ``workloads`` entry; no code
here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str] = None
    moves: Optional[str] = None
    workloads: Optional[tuple] = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple
    per_layer: tuple

    def mesh(self) -> tuple:
        """(dp, tp) of the configuration's deployment; ValueError where
        their product is not the cell's chips."""
        dep = self.config["deployment"]
        dp, tp = int(dep.get("dp", 1)), int(dep.get("tp", 1))
        if dp < 1 or tp < 1 or dp * tp != self.chips:
            raise ValueError(f"{self.name}: deployment dp {dp} x tp {tp} "
                             f"does not make the cell's {self.chips} chips")
        return dp, tp


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    return json.loads(path.read_text())


def _metrics(entries, cell: str) -> tuple:
    out = []
    for e in entries:
        m = Metric(e["name"], e["unit"], e["better"], e["source"],
                   e.get("layer"), e.get("moves"),
                   tuple(e["workloads"]) if "workloads" in e else None)
        if m.applies_to(cell):
            out.append(m)
    return tuple(out)


def load_cell(name: str) -> Cell:
    bench = load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    cell = Cell(name, config, load_json("traffic", w["traffic"]),
                int(w["chips"]), _metrics(bench["end_to_end"], name),
                _metrics(bench["per_layer"], name))
    cell.mesh()     # refuses a mesh that is not the cell's chips
    return cell


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded as a module."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(config: dict):
    """The program's ModelConfig for a configuration file, from the
    builder the file names."""
    builder = load_module("builders", config.get("builder", "llama"))
    return builder.model_config(config)


def metric_reader(name: str) -> Callable:
    """``read(run)`` of the metric's reader; it returns a number, or None
    where the run holds nothing for it to read."""
    if not (BENCH / "metrics" / f"{name}.py").is_file():
        name = name.split(".", 1)[0]
    return load_module("metrics", name).read


def read_metrics(metrics, run) -> Dict[str, dict]:
    """Each metric's reader applied to ``run``; metrics whose reader finds
    nothing are left out."""
    out = {}
    for m in metrics:
        value = metric_reader(m.name)(run)
        if value is not None:
            out[m.name] = {"value": value, "unit": m.unit}
    return out
