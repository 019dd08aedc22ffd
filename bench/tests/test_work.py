"""FLOP, byte, MFU and roofline arithmetic against hand counts."""
import math

import pytest

import cell

work = cell.arch("dense_swiglu")
reader = cell.metric_reader


@pytest.fixture(params=["internlm2-1.8b", "qwen1.5-7b-8L"])
def dims(request):
    return work.dims(cell.config_file(request.param))


# hand counts: (per-layer matmul params, KV bytes per token, decode FLOPs
# at 1000 keys, attention (FLOPs, bytes) at 1000 keys, step weight bytes)
HAND = {
    # 2048*(2048+2*1024) + 2048*2048 + 3*2048*8192
    2048: (62914560, 24 * 2 * 8 * 128 * 2,
           24 * (2 * 62914560 + 4 * 2048 * 1000) + 2 * 2048 * 92544,
           (24 * 4 * 2048 * 1000, 24 * (2 * 1000 * 8 * 128 * 2 + 4 * 2048)),
           2 * (24 * 62914560 + 2048 * 92544)),
    # 4096*(3*4096) + 4096*4096 + 3*4096*11008
    4096: (202375168, 8 * 2 * 32 * 128 * 2,
           8 * (2 * 202375168 + 4 * 4096 * 1000) + 2 * 4096 * 151936,
           (8 * 4 * 4096 * 1000, 8 * (2 * 1000 * 32 * 128 * 2 + 4 * 4096)),
           2 * (8 * 202375168 + 4096 * 151936)),
}


def test_hand_counts(dims):
    lp, kv, dec, attn, wb = HAND[dims["d"]]
    assert work.layer_matmul_params(dims) == lp
    assert work.kv_bytes_per_token(dims) == kv
    assert work.decode_flops(dims, 1000) == dec
    assert work.decode_attention(dims, 1000) == attn
    assert work.step_weight_bytes(dims) == wb
    assert work.kernel_work(dims, 1000) == {"paged_attn": attn}
    assert work.step_bytes(dims, [1000, 24]) == wb + 1024 * kv
    assert work.session_pages(dims, {"page_size": 16}, 1000) == 63


def test_prefill_counts_causal_half(dims):
    # n tokens: n(n+1)/2 query-key pairs, 2 FLOPs each for QK and PV
    n, L, qd = 4, dims["L"], dims["H"] * dims["dh"]
    want = (L * (2 * work.layer_matmul_params(dims) * n
                 + 4 * qd * n * (n + 1) // 2) + work.head_flops(dims))
    assert work.prefill_flops(dims, n) == want


def _rec(dims, **kw):
    rec = {"arch": "dense_swiglu", "dims": dims, "window_s": 2.0,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "requests": [{"prompt_len": 100, "out_start": 5, "out_end": 8}],
           "trace": {"kernel_s": {"paged_attn": 0.01}, "busy_s": 1.5,
                     "window_s": 2.0}}
    rec.update(kw)
    return rec


def test_roofline_and_mfu(dims):
    rec = _rec(dims)
    # decode steps j = 5, 6, 7 attend to 105, 106, 107 keys
    f = sum(work.decode_attention(dims, c)[0] for c in (105, 106, 107))
    b = sum(work.decode_attention(dims, c)[1] for c in (105, 106, 107))
    assert b / 819e9 > f / 197e12                # bandwidth bound
    assert reader("paged_attn_roofline")(rec) == \
        pytest.approx(100 * (b / 819e9) / 0.01)
    mf = sum(work.decode_flops(dims, c) for c in (105, 106, 107))
    assert reader("step_mfu")(rec) == pytest.approx(100 * mf / (2 * 197e12))
    assert reader("device_idle_frac")(rec) == pytest.approx(0.25)


def test_readers_silent_without_their_source(dims):
    rec = _rec(dims, trace=None)
    assert reader("paged_attn_roofline")(rec) is None
    assert reader("device_idle_frac")(rec) is None
    rec = _rec(dims, trace={"kernel_s": {"paged_attn": 0.0}, "busy_s": 0,
                            "window_s": 2.0})
    assert reader("paged_attn_roofline")(rec) is None
    rec = _rec(dims, requests=[])
    assert reader("step_mfu")(rec) is None


def test_prefill_in_window_counts_in_mfu(dims):
    rec = _rec(dims, requests=[{"prompt_len": 50, "out_start": 0,
                                "out_end": 3}])
    want = work.prefill_flops(dims, 50) + sum(
        work.decode_flops(dims, 50 + j) for j in (1, 2))
    assert reader("step_mfu")(rec) == pytest.approx(100 * want / (2 * 197e12))
    assert math.isfinite(reader("step_mfu")(rec))
