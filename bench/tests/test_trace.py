"""Trace reduction on a synthetic event list with known answers."""
import pytest

import trace as tracing

MS = 1_000_000                   # ns


def _trace():
    host = [("bench.window", 0, 100 * MS),
            ("bench.tick", 0, 40 * MS),
            ("bench.tick", 40 * MS, 100 * MS),
            ("bench.drain", 90 * MS, 100 * MS),
            ("other", 0, 100 * MS)]
    ops = [("fusion.1", -5 * MS, 10 * MS),          # clipped at the start
           ("paged_attention_kernel", 10 * MS, 30 * MS),
           ("fusion.2", 25 * MS, 35 * MS),          # overlaps the kernel
           ("paged_attention_kernel", 50 * MS, 70 * MS),
           ("copy.3", 95 * MS, 120 * MS)]           # clipped at the end
    return {"/device:TPU:0": ops}, host


def test_busy_union_idle_gaps_and_kernel_sums():
    device, host = _trace()
    r = tracing.reduce(device, host, {"paged_attn": "paged_attention"})
    assert r["window_s"] == pytest.approx(0.1)
    # union: [0,35] + [50,70] + [95,100] = 60 ms
    assert r["busy_s"] == pytest.approx(0.060)
    gaps = {round(g, 6): name for name, g in r["idle_gaps"]}
    # idle: [35,50] in the first tick, [70,95] in the second tick (its
    # middle, 82.5 ms, is before the drain span starts)
    assert gaps == {0.015: "bench.tick", 0.025: "bench.tick"}
    assert r["kernel_s"]["paged_attn"] == pytest.approx(0.040)
    ops = dict(r["device_ops"])
    assert ops["paged_attention_kernel"] == pytest.approx(0.040)
    assert ops["fusion.1"] == pytest.approx(0.010)
    assert ops["copy.3"] == pytest.approx(0.005)
    assert [n for n, _ in r["device_ops"]][0] == "paged_attention_kernel"


def test_gap_named_by_innermost_span():
    device = {"/device:TPU:0": [("a", 0, 10 * MS)]}
    host = [("bench.window", 0, 100 * MS), ("bench.tick", 0, 100 * MS),
            ("bench.drain", 20 * MS, 100 * MS)]
    r = tracing.reduce(device, host, {})
    assert r["idle_gaps"] == [["bench.drain", pytest.approx(0.09)]]


def test_busy_averaged_over_devices_and_window_required():
    device = {"/device:TPU:0": [("a", 0, 10 * MS)],
              "/device:TPU:1": [("a", 0, 30 * MS)]}
    host = [("bench.window", 0, 100 * MS)]
    assert tracing.reduce(device, host, {})["busy_s"] == pytest.approx(0.02)
    with pytest.raises(RuntimeError):
        tracing.reduce(device, [], {})
    with pytest.raises(RuntimeError):
        tracing.reduce({}, host, {})


def test_union_merges_touching_and_nested():
    assert tracing.union([(5, 7), (0, 2), (2, 3), (1, 1.5), (6, 9)]) == \
        [(0, 3), (5, 9)]


HLO_WHILE = ("%while.31 = (s32[]{:T(128)}, bf16[24,3072,16,8,128]{4,3,2,1,0:"
             "T(8,128)(2,1)}) while((s32[]{:T(128)}, bf16[24,3072]{1,0}) "
             "%tuple.126), condition=%c, body=%b")
HLO_KERNEL = ("%closed_call.33 = (f32[1,16,16,128]{3,2,1,0:T(8,128)S(1)}, "
              "f32[1,16,16,1]{3,2,1,0}) custom-call(s32[16,2048]{1,0} "
              "%fusion.166), custom_call_target=\"tpu_custom_call\"")
HLO_COPY = ("%copy.83 = bf16[24,3072,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[24,3072,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} %gte)")


def test_hlo_names_containers_left_out():
    assert tracing.opcode(HLO_WHILE) == "while"
    assert tracing.opcode(HLO_KERNEL) == "custom-call"
    assert tracing.opcode(HLO_COPY) == "copy"
    assert tracing.short(HLO_COPY) == "copy.83 copy bf16[24,3072,16,8,128]"
    assert tracing.short(HLO_KERNEL) == \
        "closed_call.33 custom-call tpu_custom_call"
    device = {"/device:TPU:0": [(HLO_WHILE, 0, 90 * MS),
                                (HLO_KERNEL, 10 * MS, 30 * MS),
                                (HLO_COPY, 40 * MS, 50 * MS)]}
    host = [("bench.window", 0, 100 * MS)]
    r = tracing.reduce(device, host, {"paged_attn": "tpu_custom_call"})
    assert r["busy_s"] == pytest.approx(0.030)      # the loop is no work
    assert r["kernel_s"]["paged_attn"] == pytest.approx(0.020)
    assert [n for n, _ in r["device_ops"]] == [
        "closed_call.33 custom-call tpu_custom_call",
        "copy.83 copy bf16[24,3072,16,8,128]"]


def test_dense_kernels_credit_the_named_call_alone():
    """Two Mosaic custom calls in one window: the dense block's KERNELS
    credits the paged kernel's, by its name, and neither the other kernel
    nor an op that reads the paged kernel's result."""
    import cell
    kernels = cell.arch("dense_swiglu").KERNELS
    paged = ("%paged_attention.11 = (f32[1,16,16,128]{3,2,1,0}) "
             "custom-call(s32[16,2048]{1,0} %fusion.166), "
             "custom_call_target=\"tpu_custom_call\"")
    other = ("%other_kernel.3 = (bf16[16,2048]{1,0}) custom-call("
             "bf16[16,2048]{1,0} %fusion.170), "
             "custom_call_target=\"tpu_custom_call\"")
    reader = ("%fusion.171 = bf16[16,2048]{1,0} fusion(f32[1,16,16,128]"
              "{3,2,1,0} %paged_attention.11), kind=kLoop")
    device = {"/device:TPU:0": [(paged, 10 * MS, 30 * MS),
                                (other, 40 * MS, 55 * MS),
                                (reader, 60 * MS, 62 * MS)]}
    host = [("bench.window", 0, 100 * MS)]
    r = tracing.reduce(device, host, kernels)
    assert r["kernel_s"] == {"paged_attn": pytest.approx(0.020)}
    assert r["busy_s"] == pytest.approx(0.037)
