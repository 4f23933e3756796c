"""The per-layer readers on the recorded trace ``data/small.xplane.pb``
(one TPU v5e, a 2-layer engine at Mistral-7B widths): pinned to the values
they gave before the yardsticks learned to count per chip, and read the
same on four devices that each do the same calls, for four times the
work, as on the one."""

import json
from types import SimpleNamespace as NS

import numpy as np
import pytest

from conftest import DATA
from harness import loadgen, roofline, spec, trace, traffic, weights

# Every per-layer reader of BENCHMARK.json on the run ``_run(1)`` builds,
# as the harness computed them before it counted per chip: bit for bit.
PINNED = {
    "submit_late_p95_ms": 4.750000000000004,
    "queue_wait_p90_ms": 9.000000000000002,
    "decode_rows_mean": 8.0,
    "decode_step_ms": 35.5,
    "engine_host_ms": 0.19999999999999998,
    "prefill_chunk_ms": 5.5859475000000005,
    "decode_root_ms": 3.552334666666667,
    "paged_attention_roofline": 57.359424334453394,
    "nested_lowrank_roofline": 54.96919192283008,
    "idle_share.decode": 51.34944263287649,
    "idle_share.docs": 51.34944263287649,
    "mfu.decode": 2.7114907303707363,
    "mfu.docs": 2.7114907303707363,
}


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(DATA / "small.xplane.pb"))


def _planes(pd, devices: int):
    """The trace with its one device plane copied onto ``devices``."""
    dev = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    assert len(dev) == 1
    rest = [p for p in pd.planes if not p.name.startswith("/device:TPU:")]
    return NS(planes=rest + [NS(name=f"/device:TPU:{i}",
                                lines=list(dev[0].lines))
                             for i in range(devices)])


def _run(pd, devices: int):
    """A run around the trace: six requests and twelve decode dispatches
    of 8 rows on each device, so ``devices`` times the rows, cached tokens
    and requests of one."""
    from repro.models import build_model

    config = json.loads((spec.BENCH / "configs" / "mistral7b-nsvd.json")
                        .read_text())
    config["num_hidden_layers"] = 2
    cfg = spec.model_config(config)
    rng = np.random.default_rng(5)
    sent, tokens, admits = [], {}, []
    for uid in range(6):
        n = int(rng.integers(20, 200))
        due = 0.05 * uid
        t = due + 0.02 + 0.003 * uid
        toks = [(t, 1)]
        for k in range(11):
            t += 0.03 + 0.001 * k
            toks.append((t, 1))
        for copy in range(devices):
            u = uid + 6 * copy
            sent.append(loadgen.Sent(traffic.Request(
                u, np.zeros(n, np.int32), 12, 0.0), u, due, due + 0.001 * uid))
            admits.append((due + 0.01, u, 0.002 * uid))
            tokens[u] = toks
    obs = NS(admits=admits, tokens=tokens, expect={}, completed=[],
             dispatches=[(0.1 + 0.05 * i, 8 * devices,
                          8 * (150 + 3 * i) * devices) for i in range(12)])
    win = loadgen.Window(
        start=0.0, end=1.0, sent=sent, obs=obs,
        step_times=[0.03 + 0.001 * i for i in range(12)],
        step_host_s=[1e-4 * (1 + i % 3) for i in range(12)])
    red = trace.reduce_xspace(pd if devices == 1 else _planes(pd, devices))
    assert red.devices == devices
    return NS(cell=spec.Cell("mistral7b-nsvd.decode", config,
                             {"loop": "open"}, devices, (), ()),
              model_cfg=cfg,
              factored_rows=weights.factored_rows(build_model(cfg),
                                                  config["compression"]),
              window=win, setup_s=1.0,
              peaks=roofline.device_peaks("TPU v5 lite"), trace=red)


def test_every_per_layer_reader_reads_as_it_did(profile):
    run = _run(profile, 1)
    names = [m["name"] for m in spec.load_benchmark()["per_layer"]]
    assert sorted(names) == sorted(PINNED)
    assert {n: spec.metric_reader(n)(run) for n in names} == PINNED


@pytest.mark.parametrize("name", ["mfu.decode", "paged_attention_roofline",
                                  "nested_lowrank_roofline",
                                  "idle_share.decode", "decode_root_ms"])
def test_four_devices_doing_the_same_calls_read_as_one(profile, name):
    one = spec.metric_reader(name)(_run(profile, 1))
    four = spec.metric_reader(name)(_run(profile, 4))
    assert four == pytest.approx(one, rel=1e-12)


def test_paged_attention_charges_each_step_once_on_four_devices(profile):
    """The least time is one chip's work for the whole batch once per step
    and layer, where the time spent is every device's."""
    from harness import measure

    one, four = _run(profile, 1), _run(profile, 4)
    calls = len(one.trace.kernels["paged_attention"])
    assert len(four.trace.kernels["paged_attention"]) == 4 * calls
    assert measure.paged_attention_least_s(four) == pytest.approx(
        4 * measure.paged_attention_least_s(one), rel=1e-12)
    assert measure.kernel_time_s(four, "paged_attention") == pytest.approx(
        4 * measure.kernel_time_s(one, "paged_attention"), rel=1e-12)


def test_nested_lowrank_sums_each_devices_calls(profile):
    """Each call is costed from its own, per-device HLO shapes, and the
    least and spent times sum over every device's calls."""
    from harness import measure

    one = measure.nested_lowrank_times(_run(profile, 1))
    four = measure.nested_lowrank_times(_run(profile, 4))
    assert four == pytest.approx((4 * one[0], 4 * one[1]), rel=1e-12)
