"""The trace reduction, on a small trace recorded on a TPU v5e: a 2-layer
engine at Mistral-7B widths running 40 ``run(max_steps=1)`` iterations
(12 decode steps, 2 chunked-prefill ticks) under ``bench.run1`` spans."""

from pathlib import Path

import pytest

from harness import trace

TRACE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData

    return trace.reduce_xspace(ProfileData.from_file(str(TRACE)))


def test_union_merges_nested_and_overlapping_intervals():
    assert trace._union([(5, 7), (0, 10), (12, 13), (13, 15)]) == [
        [0, 10], [12, 15]]
    assert trace._clip([[0, 10], [12, 15]], 5, 14) == [(5, 10), (12, 14)]


def test_busy_and_idle_partition_the_window(red):
    idle = sum(s for _, s in red.gaps)
    # Gaps shorter than the reduction's floor are neither busy nor listed.
    assert 0 < red.busy_s < red.window_s
    assert red.busy_s + idle <= red.window_s + 1e-9
    assert red.busy_s + idle > 0.95 * red.window_s


def test_modules_count_each_root_execution(red):
    assert len(red.module_calls("jit_paged_decode_step")) == 12
    assert len(red.module_calls("jit_paged_prefill_chunk_step")) == 2
    per_call = red.module_calls("jit_paged_decode_step")
    assert all(3e-3 < d < 4e-3 for d in per_call)


def test_kernels_are_found_by_name_with_their_shapes(red):
    # 2 layers x 12 decode steps of 8 rows, and 2 chunk ticks of 8 x 64
    # rows (under the kernel's row limit at this batch); k and v take the
    # nested kernel.
    assert len(red.kernels["paged_attention"]) == 24
    rows = [trace.custom_call_types(h)[1][1][0]
            for _, h, _ in red.kernels["nested_lowrank_matmul"]]
    assert sorted(set(rows)) == [8, 512]
    assert rows.count(8) == 48 and rows.count(512) == 8
    hlo = next(h for _, h, _ in red.kernels["nested_lowrank_matmul"]
               if "bf16[8,1024]" in h)
    res, *ops = trace.custom_call_types(hlo)
    assert res[:2] == ("bf16", (8, 1024))
    assert [s for _, s, _ in ops] == [(8, 4096), (4096, 622), (622, 1024),
                                      (4096, 33), (33, 1024)]


def test_idle_gaps_are_labelled_by_host_spans(red):
    labels = {label for label, _ in red.gaps}
    assert labels <= {"bench.run1", "bench.drain", "outside host spans"}
    assert "bench.run1" in labels
    bd = red.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert not any(k.startswith("while ") for k, _ in bd["device_ops"])


def test_op_key_and_custom_call_types_parse_hlo_text():
    hlo = ("%paged_attention.9 = bf16[32,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} "
           "custom-call(s32[32,256]{1,0:T(8,128)S(1)} %a, "
           "bf16[4096,16,8,128]{3,2,1,0:T(8,128)(2,1)} %b), "
           'custom_call_target="tpu_custom_call"')
    assert trace.op_key(hlo) == ("paged_attention", "bf16[32,8,4,128]")
    assert trace.op_key("%fusion.7 = (f32[2,4]{1,0:T(2,128)}, s32[2]{0}) "
                        "fusion(f32[2,4]{1,0} %p)") == (
        "fusion", "(f32[2,4], s32[2])")
    assert trace.custom_call_types(hlo) == [
        ("bf16", (32, 8, 4, 128), True), ("s32", (32, 256), True),
        ("bf16", (4096, 16, 8, 128), False)]
