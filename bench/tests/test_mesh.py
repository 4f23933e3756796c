"""A configuration's ``deployment`` sets the (dp, tp) mesh the program
serves the cell on: the harness refuses a mesh that is not the cell's
chips before it builds anything, and on a mesh draws each param straight
into the engine's own sharding of it."""

import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from conftest import DATA, tiny_mix
from harness import cell_run, spec, weights


@pytest.mark.parametrize("dp, tp, chips", [(2, 2, 1), (1, 1, 4), (2, 1, 4),
                                           (4, 1, 2), (0, 4, 4)])
def test_a_mesh_that_is_not_the_cells_chips_is_refused_before_any_build(
        tiny_config, monkeypatch, dp, tp, chips):
    tiny_config["deployment"].update(dp=dp, tp=tp)
    cell = spec.Cell("tiny.decode", tiny_config, tiny_mix("decode"), chips,
                     (), ())

    def built(*args, **kwargs):
        raise AssertionError("built before the mesh was checked")

    monkeypatch.setattr(spec, "model_config", built)
    monkeypatch.setattr(weights, "build_params", built)
    with pytest.raises(ValueError, match=f"dp {dp} x tp {tp}"):
        cell_run.run(cell, 1, 1.0, False, time.perf_counter(), jax.devices())


def test_loading_a_cell_refuses_a_mesh_that_is_not_its_chips(bench_copy):
    path = bench_copy / "bench" / "configs" / "mistral7b-nsvd.json"
    config = json.loads(path.read_text())
    assert spec.load_cell("mistral7b-nsvd.decode").mesh() == (1, 1)
    config["deployment"].update(dp=2, tp=2)
    path.write_text(json.dumps(config))
    with pytest.raises(ValueError, match="1 chips"):
        spec.load_cell("mistral7b-nsvd.decode")


def test_run_py_exits_without_a_result_on_a_mesh_that_is_not_the_chips(
        tmp_path):
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    path = tmp_path / "bench" / "configs" / "mistral7b-nsvd.json"
    config = json.loads(path.read_text())
    config["deployment"].update(dp=2, tp=2)
    path.write_text(json.dumps(config))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mistral7b-nsvd.decode",
         "--seed", "2147483999", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "dp 2 x tp 2" in p.stderr


_FOUR_DEVICE_RUN = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = sys.argv[1:4]
    import jax
    from conftest import tiny_mix
    from harness import cell_run, spec, weights

    config = json.load(open(sys.argv[4]))
    config["deployment"].update(dp=2, tp=2)
    cell = spec.Cell("tiny.decode", config, tiny_mix("decode"), 4, (), ())
    drawn, seen = {}, {}
    build = weights.build_params

    def capture(*args, **kwargs):
        drawn["params"] = build(*args, **kwargs)
        return drawn["params"]

    def look(eng):
        seen.update(shardings=eng._sh.params, mesh=dict(eng.par.mesh.shape),
                    devices=eng.par.mesh.devices.size)

    weights.build_params = capture
    res = cell_run.run(cell, 2 ** 31 + 11, 5.0, False, time.perf_counter(),
                       jax.devices(), fault=look)
    leaves = jax.tree.leaves(drawn["params"])
    shardings = jax.tree.leaves(seen["shardings"])
    print(json.dumps({
        "correct": res["correct"], "checks": res["checks"],
        "count": res["device"]["count"], "mesh": seen["mesh"],
        "mesh_devices": seen["devices"],
        "leaves": [len(leaves), len(shardings)],
        "as_engine": [x.sharding.is_equivalent_to(s, x.ndim)
                      for x, s in zip(leaves, shardings)],
        "split": sum(not x.sharding.is_fully_replicated for x in leaves),
        "by_device": res["notes"]["memory_peak_bytes_by_device"]}))
""")


def test_a_dp2_tp2_run_on_four_host_devices_is_correct_and_sharded():
    """A whole run of the tiny configuration on a (2, 2) mesh of four
    host devices, in a process of its own (the device count is fixed when
    JAX starts): every param is drawn into the sharding the engine gives
    it, and the served tokens pass the reference."""
    tests = os.path.dirname(os.path.abspath(__file__))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                         + " --xla_force_host_platform_device_count=4")}
    p = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICE_RUN, tests, str(spec.BENCH),
         str(spec.ROOT / "src"), str(DATA / "tiny.json")],
        capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["count"] == 4
    assert out["mesh"] == {"data": 2, "model": 2} and out["mesh_devices"] == 4
    assert out["leaves"][0] == out["leaves"][1] and all(out["as_engine"])
    assert out["split"] > 0
    assert len(out["by_device"]) == 4


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2, with the persistent compilation cache off
    meanwhile: a compile for a described chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 -- any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_a_sharded_draw_holds_no_leaf_whole_on_a_device(topo):
    """The draw of one Mistral-7B layer's factored params into a (2, 2)
    mesh of a described v5e, compiled by the chip's compiler: the
    temporaries on a device stay far below a tenth of the largest leaf,
    so no device draws a sharded leaf whole and keeps its slice (the
    chip's "rbg" generator, unpartitioned, would need the whole leaf as
    uint32, twice its bfloat16 size)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.launch.steps import ServingShardings
    from repro.models import build_model
    from repro.parallel.sharding import make_parallelism

    config = json.loads((spec.BENCH / "configs" / "mistral7b-nsvd.json")
                        .read_text())
    config["num_hidden_layers"] = 1
    model = build_model(spec.model_config(config))
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    shapes = weights.param_shapes(model, config["compression"])
    shardings = ServingShardings(make_parallelism(mesh), shapes, None,
                                 config["deployment"]["max_batch"]).params
    largest = max(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    words = jax.ShapeDtypeStruct((2,), np.uint32,
                                 sharding=NamedSharding(mesh, PartitionSpec()))
    compiled = weights.sharded_draw(model, config["compression"],
                                    shardings).lower(words).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < largest / 10
