"""What one cell is: its entry in BENCHMARK.json, its configuration file,
its traffic file, and the per-layer metric readers it reports.

Everything is found by name: a configuration ``<name>`` is
``bench/configs/<name>.json``, a traffic mix ``<name>`` is
``bench/traffic/<name>.json`` and a per-layer metric ``<name>`` is read by
``bench/metrics/<name>.py``, or, where that file does not exist, by the
reader of the name's part before its first dot: ``mfu.decode`` and
``mfu.docs`` (one quantity, split by the end-to-end metric it moves) share
``bench/metrics/mfu.py``.  Adding a cell adds files and a ``workloads``
entry; no code here changes.
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
    return Cell(name, config, load_json("traffic", w["traffic"]),
                int(w["chips"]), _metrics(bench["end_to_end"], name),
                _metrics(bench["per_layer"], name))


def model_config(config: dict):
    """The program's ModelConfig for a configuration file (a Llama-style
    decoder: RMSNorm, RoPE, GQA, SwiGLU, untied output head)."""
    from repro.configs.base import ModelConfig

    act = config["hidden_act"]
    if act != "silu":
        raise ValueError(f"{config['name']}: hidden_act {act!r}, not silu")
    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        head_dim=config["head_dim"],
        attention="gqa", pos_emb="rope", rope_theta=config["rope_theta"],
        norm="rmsnorm", activation="swiglu",
        tie_embeddings=config["tie_word_embeddings"],
        max_seq=config["max_position_embeddings"],
        dtype=config["torch_dtype"],
    )


def metric_reader(name: str) -> Callable:
    """``read(run)`` of the metric's reader; it returns a number, or None
    where the run holds nothing for it to read."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        path = BENCH / "metrics" / f"{name.split('.', 1)[0]}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics, run) -> Dict[str, dict]:
    """Each metric's reader applied to ``run``; metrics whose reader finds
    nothing are left out."""
    out = {}
    for m in metrics:
        value = metric_reader(m.name)(run)
        if value is not None:
            out[m.name] = {"value": value, "unit": m.unit}
    return out
