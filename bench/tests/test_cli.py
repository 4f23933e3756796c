"""``bench/run.py`` refuses to produce a result where it cannot measure."""

import os
import shutil
import subprocess
import sys

from harness import spec

ARGS = ["--workload", "mistral7b-nsvd.decode", "--seed", "2147483999",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env=None):
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          capture_output=True, text=True, timeout=300,
                          env={**os.environ, **(env or {})})


def test_no_accelerator_no_result():
    p = _run(spec.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
