"""Cells, traffic mixes and metrics are found by name: a new traffic file
and a new metric file take effect with no code edited."""

import json

import numpy as np
import pytest

from harness import spec, traffic


def test_a_new_traffic_file_and_metric_file_are_picked_up(bench_copy):
    bench = bench_copy / "bench"
    mix = json.loads((bench / "traffic" / "decode.json").read_text())
    mix.update(name="bursty", rate_per_s=3.0, arrival_cv=3.0)
    (bench / "traffic" / "bursty.json").write_text(json.dumps(mix))
    (bench / "metrics" / "prompt_tokens_sent.py").write_text(
        "def read(run):\n"
        "    return sum(len(s.req.prompt) for s in run.window.sent)\n")
    b = json.loads((bench_copy / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "mistral7b-nsvd.bursty",
                           "config": "mistral7b-nsvd", "traffic": "bursty",
                           "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "prompt_tokens_sent", "unit": "tokens",
                           "better": "higher", "source": "host_clock",
                           "layer": "load generator", "moves": "out_tok_s",
                           "workloads": ["mistral7b-nsvd.bursty"]})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.load_cell("mistral7b-nsvd.bursty")
    assert cell.traffic["arrival_cv"] == 3.0
    assert cell.per_layer[-1].name == "prompt_tokens_sent"
    assert "prompt_tokens_sent" not in [
        m.name for m in spec.load_cell("mistral7b-nsvd.decode").per_layer]

    class FakeRun:
        class window:
            sent = [type("S", (), {"req": r})()
                    for r in traffic.generate(mix, 1, 5.0, 100)[:3]]

    out = spec.read_metrics(cell.per_layer[-1:], FakeRun)
    assert out["prompt_tokens_sent"]["unit"] == "tokens"
    assert out["prompt_tokens_sent"]["value"] > 0


def test_a_reader_that_finds_nothing_leaves_its_metric_out(bench_copy):
    (bench_copy / "bench" / "metrics" / "nothing.py").write_text(
        "def read(run):\n    return None\n")
    m = spec.Metric("nothing", "%", "higher", "device_trace")
    assert spec.read_metrics([m], object()) == {}


def test_every_metric_in_the_benchmark_has_a_reader():
    b = spec.load_benchmark()
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_a_split_metric_shares_one_reader(bench_copy):
    """``idle_share.<any suffix>`` is read by ``idle_share.py``: a new cell
    that splits it again names it in BENCHMARK.json and adds no reader."""
    class FakeRun:
        class trace:
            busy_s, window_s = 3.0, 4.0

    for name in ("idle_share.decode", "idle_share.docs", "idle_share.new"):
        assert spec.metric_reader(name)(FakeRun) == pytest.approx(25.0)
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric.decode")


def test_same_work_for_every_seed():
    mix = json.loads((spec.BENCH / "traffic" / "decode.json").read_text())
    a = traffic.generate(mix, 1, 45.0, 32000)
    b = traffic.generate(mix, 2 ** 31 + 99, 45.0, 32000)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert [r.gap_s for r in a] == [r.gap_s for r in b]
    assert not any((x.prompt == y.prompt).all() for x, y in zip(a, b))
    again = traffic.generate(mix, 1, 45.0, 32000)
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, again))


@pytest.mark.parametrize("cv", [1.0, 3.0])
def test_arrivals_are_as_bursty_as_the_mix_says(cv):
    """The gaps are a random sample of the stated process, not smoothed:
    their coefficient of variation is the mix's ``arrival_cv``, and the
    arrivals in equal windows spread accordingly."""
    mix = json.loads((spec.BENCH / "traffic" / "decode.json").read_text())
    mix.update(rate_per_s=4.0, arrival_cv=cv, lead_in_s=0)
    gaps = np.array([r.gap_s for r in traffic.generate(mix, 7, 2000.0, 50)])
    assert gaps.mean() == pytest.approx(0.25, rel=0.1)
    assert gaps.std() / gaps.mean() == pytest.approx(cv, rel=0.15)
    counts = np.histogram(np.cumsum(gaps), bins=np.arange(0, 1500, 10))[0]
    # Poisson: variance of the counts equals their mean (40); cv 3 far more.
    ratio = counts.var() / counts.mean()
    assert (0.6 < ratio < 1.6) if cv == 1.0 else ratio > 4
