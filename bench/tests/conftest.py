"""Tests of the benchmark itself, run on the CPU at small sizes:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests
"""

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def tiny_config():
    return json.loads((DATA / "tiny.json").read_text())


def tiny_mix(name: str) -> dict:
    """A committed traffic mix with its lengths, lead-in and load cut to
    what the tiny configuration serves in a few CPU seconds."""
    mix = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    for key in ("prompt", "output"):
        d = dict(mix[key])
        d["min"] = max(2, d["min"] // 32)
        d["max"] = max(d["min"] + 1, d["max"] // 32)
        if "median" in d:
            d["median"] = max(d["min"], d["median"] // 32)
        mix[key] = d
    mix["lead_in_s"] = 1.0
    if mix["loop"] == "open":
        mix["rate_per_s"] = 8.0
    else:
        mix["clients"] = 3
    return mix


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A copy of the benchmark's data, builders and readers under a
    temporary root, which the harness then reads."""
    from harness import spec

    root = tmp_path / "repo"
    bench = root / "bench"
    for sub in ("configs", "traffic", "metrics", "builders"):
        shutil.copytree(spec.BENCH / sub, bench / sub)
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    monkeypatch.setattr(spec, "BENCH", bench)
    monkeypatch.setattr(spec, "ROOT", root)
    return root
