"""The window/full MoE block (``bench/arch/window_moe.py``): its check, its
work counts, its closed batch, the served path against its float32
reference, and the harness end to end on a tiny configuration of it."""
import copy
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cell
import faults
import reference
import tiny
import tiny_moe
import weights

ARCH = cell.arch("window_moe")


@pytest.fixture(scope="module")
def full():
    cf = cell.config_file("mellum2-12b-a2.5b-ep8")
    return cf, ARCH.dims(cf)


# ---- check -----------------------------------------------------------------

def test_published_file_is_what_the_program_serves(full):
    cf, m = full
    cfg = cell.model_config(cf)
    assert (cfg.n_layers, cfg.n_experts, cfg.moe_held, cfg.moe_top_k,
            cfg.sliding_window, cfg.yarn_factor) == (28, 64, 8, 8, 1024, 16)
    assert cf["reduced"] == ["num_experts"] and cf["num_experts"] == 8
    assert sum(m["windowed"]) == 21 and m["windowed"][3::4] == (False,) * 7


def _edit(cf, path, value):
    cf = copy.deepcopy(cf)
    d = cf
    for k in path[:-1]:
        d = d[k]
    d[path[-1]] = value
    return cf


@pytest.mark.parametrize("path,value", [
    (("sliding_window",), 512),
    (("rope_parameters", "full_attention", "factor"), 8),
    (("rope_parameters", "full_attention", "original_max_position_embeddings"),
     4096),
    (("rope_parameters", "full_attention", "attention_factor"), 1.0),
    (("rope_parameters", "full_attention", "beta_fast"), 64),
    (("rope_parameters", "full_attention", "rope_type"), "default"),
    (("num_experts",), 16),
    (("num_experts_published",), 128),
    (("experts_held_first",), 8),
    (("num_experts_per_tok",), 4),
    (("moe_intermediate_size",), 1024),
    (("head_dim",), 64),
    (("layer_types", 0), "full_attention"),
    (("norm_topk_prob",), False),
])
def test_check_refuses_a_file_the_program_does_not_serve(full, path, value):
    with pytest.raises(SystemExit, match="mellum2-12b-a2.5b"):
        cell.model_config(_edit(full[0], path, value))


# ---- work ------------------------------------------------------------------

def test_work_hand_counts(full):
    _, m = full
    qd, d, ff = 32 * 128, 2304, 896
    attn = d * (qd + 2 * 512) + qd * d
    assert ARCH.attn_params(m) == attn == 21233664
    # a token holds one of its 8 experts here in expectation: 8 * 8 / 64
    assert ARCH.held_per_token(m) == 1.0
    kv = 2 * 4 * 128 * 2                        # K and V bytes, one layer
    for ctx in (500, 1024, 14000):
        win = min(ctx, 1024)
        assert ARCH.kernel_work(m, ctx) == {
            "paged_attn": (7 * 4 * qd * ctx, 7 * (ctx * kv + 2 * qd * 2)),
            "paged_attn_window": (21 * 4 * qd * win,
                                  21 * (win * kv + 2 * qd * 2))}
        assert ARCH.decode_flops(m, ctx) == (
            28 * 2 * (attn + d * 64 + 3 * d * ff)
            + 4 * qd * (7 * ctx + 21 * win) + 2 * d * 98304)
    # one row touches 8 * (1 - 7/8) = 1 held expert; four rows 3.31
    one = 2 * (28 * (attn + d * 64 + 3 * d * ff) + d * 98304
               + (7 * 14000 + 21 * 1024) * 2 * 4 * 128)
    assert ARCH.step_bytes(m, [14000]) == one
    rows = [6855, 11996, 15936, 21077]
    touched = 8 * (1 - (7 / 8) ** 4)
    assert ARCH.step_bytes(m, rows) == int(2 * (
        28 * (attn + d * 64 + touched * 3 * d * ff) + d * 98304
        + sum(7 * c + 21 * 1024 for c in rows) * 2 * 4 * 128))
    assert ARCH.session_pages(m, {"page_size": 16}, 1000) == 63


def test_prefill_counts_each_layer_kind(full):
    _, m = full
    qd, w = 32 * 128, 1024
    per_token = ARCH.decode_flops(m, 1) - 4 * qd * 28 - 2 * 2304 * 98304
    for n in (100, 5000):
        full_pairs = n * (n + 1) // 2
        win_pairs = sum(min(i + 1, w) for i in range(n))
        assert ARCH.prefill_flops(m, n) == (
            per_token * n + 4 * qd * (7 * full_pairs + 21 * win_pairs)
            + 2 * 2304 * 98304)


def test_closed_plan(full):
    cf, m = full
    e = cf["engine"]
    ctxs, budget = cell.traffic_kind("closed").plan(
        cell.traffic_file("decode_long"), ARCH, m, e,
        cell.benchmark()["run_seconds"])
    pages = sum(ARCH.session_pages(m, e, c + budget + e["decode_horizon"]
                                   + 1) for c in ctxs)
    assert (ctxs, budget, pages) == ([6855, 11996, 15936, 21077], 2604, 4147)
    assert pages <= e["n_pages"] == 4352


def test_kernel_patterns_tell_the_calls_apart():
    import re
    full_call = "%paged_attention.12 = (f32[1]{0}) custom-call(%a)"
    window_call = "%paged_attention_window.3 = (f32[1]{0}) custom-call(%b)"
    reads = "%fusion.7 = f32[1]{0} fusion(%paged_attention.12)"
    pats = {k: re.compile(p) for k, p in ARCH.KERNELS.items()}
    assert [bool(pats["paged_attn"].search(x))
            for x in (full_call, window_call, reads)] == [True, False, False]
    assert [bool(pats["paged_attn_window"].search(x))
            for x in (full_call, window_call, reads)] == [False, True, False]


def test_roofline_readers(full):
    _, m = full
    rec = {"arch": "window_moe", "dims": m,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "requests": [{"prompt_len": 2000, "out_start": 5, "out_end": 8}],
           "trace": {"kernel_s": {"paged_attn": 0.02,
                                  "paged_attn_window": 0.004}}}
    for metric, kernel, t in (("paged_attn_full_roofline", "paged_attn", 0.02),
                              ("paged_attn_window_roofline",
                               "paged_attn_window", 0.004)):
        b = sum(ARCH.kernel_work(m, c)[kernel][1] for c in (2005, 2006, 2007))
        assert cell.metric_reader(metric)(rec) == \
            pytest.approx(100 * b / 819e9 / t)
        # silent with no trace, and on a block that has no such call
        assert cell.metric_reader(metric)(dict(rec, trace=None)) is None
        assert cell.metric_reader(metric)(dict(rec, trace={
            "kernel_s": {k: v for k, v in rec["trace"]["kernel_s"].items()
                         if k != kernel}})) is None


def test_full_layers_draw_q_and_k_smaller_by_the_attention_factor():
    """YaRN scales a full layer's scores by its attention factor squared;
    its q and k are drawn smaller by the factor, so its scores spread as
    a window layer's do."""
    cf = tiny_moe.config()
    m = ARCH.dims(cf, 16)
    p = ARCH.make(m, 5, "float32")["layers"]["attn"]
    af = cf["rope_parameters"]["full_attention"]["attention_factor"]
    dense = weights.QK_GAIN / math.sqrt(m["d"])
    for w in ("wq", "wk"):
        ratio = float(p[w][3].std() / dense)
        assert ratio == pytest.approx(1 / af, rel=0.05), (w, ratio)
    assert float(p["wv"][3].std() / p["wv"][:3].std()) == \
        pytest.approx(1, rel=0.05)


def test_window_layers_draw_q_and_k_at_their_own_gain():
    """A window layer's q and k are drawn at ``WINDOW_QK_GAIN``, so a head
    puts about the share of its weight on its own key over the window that
    a full layer's head puts there over the mix's median context."""
    cf = tiny_moe.config()
    m = ARCH.dims(cf, 16)
    p = ARCH.make(m, 5, "float32")["layers"]["attn"]
    for w in ("wq", "wk"):
        std = float(p[w][:3].std() * math.sqrt(m["d"]))
        assert std == pytest.approx(ARCH.WINDOW_QK_GAIN, rel=0.05), (w, std)

    # at the published sizes: scores spread gain^2, the own key scoring
    # SELF sqrt(head_dim) times that, against the other keys' lognormal sum
    pub = cell.config_file("mellum2-12b-a2.5b-ep8")
    median = cell.traffic_file("decode_long")["context_tokens"]["mean"]

    def self_share(gain, keys):
        s = gain ** 2
        own = math.exp(weights.SELF * math.sqrt(pub["head_dim"]) * s)
        return own / (own + keys * math.exp(s * s / 2))
    full = self_share(weights.QK_GAIN, median - 1)
    window = pub["sliding_window"] - 1
    assert self_share(ARCH.WINDOW_QK_GAIN, window) == \
        pytest.approx(full, rel=0.05)
    assert self_share(weights.QK_GAIN, window) > 5 * full


# ---- the served path against the reference ---------------------------------

def _logit_error(dtype):
    """Prefill a 70-token prompt, then decode 40 tokens through the paged
    cache (table width past the window, positions past YaRN's 64 original
    ones), the engine's runtime (cond_window) and the program's float32
    or bf16 weights; returns the widest gap between the program's logits
    and the float32 reference's at every decoded position, over the
    reference logits' standard deviation."""
    cell.use_program()
    from repro.core.paged_kv import PoolSpec, init_pool
    from repro.models import model as MDL
    cf = tiny_moe.config(dtype)
    cfg = cell.model_config(cf)
    m = ARCH.dims(cf, cfg.vocab_multiple)
    params = ARCH.make(m, 11, "float32")
    served = jax.tree.map(lambda a: a.astype(dtype), params)
    P, D, page = 70, 40, 8
    seq = np.random.default_rng(5).integers(0, m["V"], P + D).astype(
        np.int32)
    maxp = (P + D) // page + 2
    spec = PoolSpec(cfg.n_layers, maxp, page, cfg.n_kv_heads, cfg.d_head,
                    maxp, dtype=dtype)
    rt = MDL.Runtime(cond_window=MDL.mixed_window(cfg))
    assert rt.cond_window == 16
    bt = jnp.arange(maxp, dtype=jnp.int32)[None]
    logits, state = jax.jit(partial(MDL.prefill, cfg, rt=rt))(
        served, {"pool": init_pool(spec)}, jnp.asarray(seq[None, :P]), bt)
    step = jax.jit(partial(MDL.decode_step, cfg, rt=rt))
    got = [logits[0]]
    for t in range(P, P + D - 1):
        logits, state = step(served, state, jnp.asarray(seq[t:t + 1]), bt,
                             jnp.asarray([t + 1], jnp.int32),
                             jnp.asarray([t // page]),
                             jnp.asarray([t % page]))
        got.append(logits[0])
    got = np.asarray(jnp.stack(got))[:, :m["V"]]
    h = ARCH.hidden(params, m, seq)
    ref = np.asarray(jnp.dot(h[P - 1:P + D - 1], params["head"][:, :m["V"]],
                             precision=reference.HI))
    return float(np.abs(got - ref).max() / ref.std())


def test_prefill_then_decode_matches_reference():
    """Float32 program and reference differ only in the order of float32
    sums: 1.3e-5 of the logits' spread here, so 1e-3 leaves 80x room. The
    same program in bf16 misses by 0.9 of the spread (its rounding moves
    attention and flips near-tied expert choices), so the tolerance sees a
    precision lower than the configuration's."""
    tol = 1e-3
    assert _logit_error("float32") < tol
    assert _logit_error("bfloat16") > 10 * tol


# ---- the harness end to end ------------------------------------------------

def test_tiny_run_is_correct():
    res, rec = tiny.run(tiny_moe.make())
    assert res["correct"], res
    assert res["check"]["max_logit_gap"]["value"] <= 1e-3
    assert res["failed"] == 0 and rec["arch"] == "window_moe"
    for q in rec["requests"]:
        assert q["out_end"] > q["out_start"] >= 1
    # every session's context passes the window and YaRN's original length
    assert min(q["prompt_len"] for q in rec["requests"]) > 64


@pytest.mark.parametrize("fault", ["decode_kv_dropped", "half_pages"])
def test_broken_timed_path_fails(fault):
    undo = faults.install(fault)
    try:
        res, _ = tiny.run(tiny_moe.make())
    finally:
        undo()
    assert not res["correct"]


def test_control_fails_where_the_program_passes():
    """The float8 control, read at the positions of the served tokens,
    lies well past the bf16 program's widest gap at the same size."""
    seed = 2 ** 31 + 7
    res, rec = tiny.run(tiny_moe.make(limit=1e9, dtype="bfloat16"),
                        seed=seed)
    program = res["check"]["max_logit_gap"]["value"]
    m = ARCH.dims(tiny_moe.config("bfloat16"), 16)
    params = ARCH.make(m, seed, "bfloat16")
    worst = max(float(reference.served_gaps(
        ARCH, params, m, rec["prompts"][rid], np.asarray(served),
        control=True).max()) for rid, served in rec["served"].items())
    assert math.isfinite(worst) and worst >= 3 * program, (worst, program)
