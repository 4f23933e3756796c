"""The ops/bytes functions against counts made by hand."""

import pytest

from harness import roofline


def test_paged_attention_cost_by_hand():
    # 2 live rows holding 100 cached tokens between them; 4 query heads
    # over 2 KV heads of 8.  QK^T + PV: 4 * 4 heads * 8 * 100 tokens.
    c = roofline.paged_attention_cost(2, 100, 4, 2, 8)
    assert c["flops"] == 12800
    # K and V: 2 * 2 heads * 8 * 2 bytes * 100 tokens = 6400; query in and
    # output out: 2 * 2 rows * 4 heads * 8 * 2 bytes = 256.
    assert c["bytes"] == 6656
    assert roofline.paged_attention_cost(2, 100, 4, 2, 8,
                                         act_bytes=0)["bytes"] == 6400


def test_nested_lowrank_cost_by_hand():
    # y = (x u) v + (x u2) v2 on 2 rows, 8 -> 4 with k1 = 3, k2 = 1.
    c = roofline.nested_lowrank_cost(2, 8, 4, 3, 1)
    assert c["flops"] == 2 * 2 * (8 * 3 + 3 * 4 + 8 * 1 + 1 * 4)   # 192
    assert c["bytes"] == 2 * (8 * 4 + 4 * 4 + 2 * (8 + 4))          # 144


def test_least_time_names_its_bound():
    peaks = roofline.device_peaks("TPU v5 lite")
    t, bound = roofline.least_time(197e12, 1.0, peaks)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = roofline.least_time(1.0, 819e9, peaks)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.device_peaks("cpu")


def test_token_flops_by_hand():
    from harness.weights import FactoredRow

    rows = [FactoredRow(("a",), 8, 4, 3, 1, (2,)),
            FactoredRow(("b",), 4, 8, 2, 0, ())]
    # 2 layers x 2 * (8 + 4) * 4, plus 2 * (4 + 8) * 2.
    assert roofline.linear_flops_per_token(rows) == 2 * 2 * 12 * 4 + 2 * 12 * 2
    assert roofline.attention_flops(2, 4, 8, 10) == 4 * 2 * 4 * 8 * 10
    assert roofline.head_flops(8, 100) == 1600
    assert roofline.prompt_attention_keys(4) == 1 + 2 + 3 + 4


def _xspace(ops, async_ops):
    """A trace with one device plane holding ``ops`` and ``async_ops``
    ((start ns, duration ns, HLO text)) under one ``bench.window``."""
    from types import SimpleNamespace as NS

    def line(name, evs):
        return NS(name=name, events=[NS(start_ns=s, duration_ns=d, name=n)
                                     for s, d, n in evs])

    host = NS(name="/host:CPU", lines=[line("main", [(0, 10 ** 6,
                                                      "bench.window")])])
    dev = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", [(0, 10 ** 5, "jit_paged_decode_step(1)")]),
        line("XLA Ops", ops), line("Async XLA Ops", async_ops)])
    return NS(planes=[host, dev])


def test_nested_lowrank_share_counts_staged_factors_by_hand():
    """Factors XLA moved from HBM into VMEM (``S(1)``) for the call count
    as bytes, with the time of the op that moved them; a factor the call
    reads from HBM itself counts as bytes within the call's time; one
    whose producer the trace does not show counts neither."""
    import types

    from harness import measure, trace

    T = "{1,0:T(8,128)(2,1)}"
    V = "{1,0:T(8,128)(2,1)S(1)}"
    ops = [
        (1000, 4000, f"%dynamic-slice_fusion.1 = bf16[256,32]{V} fusion("
                     f"bf16[4,256,32]{{2,1,0:T(8,128)(2,1)}} %p.1, "
                     f"s32[]{{:T(128)}} %i), kind=kLoop"),
        (5000, 10, f"%copy-done.2 = bf16[32,128]{V} copy-done(("
                   f"bf16[32,128]{V}, bf16[32,128]{T}, u32[]{{:S(2)}}) "
                   f"%copy-start.2)"),
        (6000, 1000, f"%nested_lowrank_matmul.4 = bf16[8,128]{T} custom-call("
                     f"bf16[8,256]{V} %fusion.0, bf16[256,32]{V} "
                     f"%dynamic-slice_fusion.1, bf16[32,128]{V} %copy-done.2, "
                     f"bf16[256,2]{V} %bitcast.3, bf16[2,128]{T} %p.5), "
                     f'custom_call_target="tpu_custom_call"'),
    ]
    async_ops = [(2000, 3000, f"%copy-start.2 = (bf16[32,128]{V}, "
                              f"bf16[32,128]{T}, u32[]{{:S(2)}}) copy-start("
                              f"bf16[32,128]{T} %p.2)")]
    red = trace.reduce_xspace(_xspace(ops, async_ops))
    peaks = roofline.device_peaks("TPU v5 lite")
    run = types.SimpleNamespace(trace=red, peaks=peaks)
    least, spent = measure.nested_lowrank_times(run)
    # 8 rows, 256 -> 128, k1 32, k2 2: 2 * 8 * (256 * 34 + 34 * 128) FLOPs.
    # Bytes: y out 8*128*2 = 2048; u staged from HBM 256*32*2 = 16384; v
    # copied from HBM 32*128*2 = 8192; v2 read by the call 2*128*2 = 512;
    # x and u2 (no producer in the trace) none.
    assert least == pytest.approx(max(208896 / 197e12, 27136 / 819e9))
    # The call, the slice that staged u, the copy of v (done + transfer).
    assert spent == pytest.approx((1000 + 4000 + 10 + 3000) * 1e-9)
