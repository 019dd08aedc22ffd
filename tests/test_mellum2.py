"""Window layers beside YaRN full layers, and one chip's held share of an
expert-parallel MoE layer (mellum2-12b-a2.5b and its reduced twin): the
YaRN tables, the per-kind table choice, the held share's sum and batch
independence, and the engine's windowed decode branch."""
import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import layers as L
from repro.models import model as MDL
from repro.models import moe as MOE
from repro.serving import DecodeEngine, EngineConfig, Request

MELLUM = "mellum2-12b-a2.5b"


def _tiny(name=MELLUM):
    return replace(reduced(get_config(name)), dtype="float32")


def _hf_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """HF ``_compute_yarn_parameters`` transcribed line by line in float64
    (``truncate`` true)."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    linear = (np.arange(dim // 2, dtype=np.float64) - low) / (high - low)
    extrapolation_factor = 1 - np.clip(linear, 0, 1)
    return (inv_freq_interpolation * (1 - extrapolation_factor)
            + inv_freq_extrapolation * extrapolation_factor), (low, high)


@pytest.mark.parametrize("name", [MELLUM, "reduced"])
def test_yarn_tables_match_hf(name):
    cfg = get_config(MELLUM) if name == MELLUM else _tiny()
    args = (cfg.d_head, cfg.rope_theta, cfg.yarn_factor,
            cfg.yarn_original_max)
    want, (low, high) = _hf_yarn(*args, L.YARN_BETA_FAST, L.YARN_BETA_SLOW)
    got = L.yarn_freqs(*args)
    # float32 frequencies against float64: a few ulp
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = L.rope_freqs(cfg.d_head, cfg.rope_theta)
    if name == MELLUM:
        # 128 dims, theta 5e5, 8192 original positions: dims below 18 turn
        # more than 32 times and keep their frequency; from 35 on they
        # turn less than once and are interpolated by the factor 16
        assert (low, high) == (18, 35)
    np.testing.assert_allclose(got[:low], plain[:low], rtol=1e-6)
    np.testing.assert_allclose(got[math.ceil(high):],
                               plain[math.ceil(high):] / cfg.yarn_factor,
                               rtol=1e-6)
    # cos and sin at 30000 positions, scaled by the attention factor
    pos = jnp.arange(30000, dtype=jnp.int32)[None]
    cs_plain, cs_yarn = MDL._cos_sin(cfg, pos)
    ang = np.arange(30000)[:, None] * want[None]
    scale = cfg.yarn_attention_factor
    # float32 angles at positions up to 3e4 lose about 2e-3 rad
    np.testing.assert_allclose(cs_yarn[0][0], np.cos(ang) * scale, atol=5e-3)
    np.testing.assert_allclose(cs_yarn[1][0], np.sin(ang) * scale, atol=5e-3)
    ref_plain = np.arange(30000)[:, None] * plain[None]
    np.testing.assert_allclose(cs_plain[0][0], np.cos(ref_plain), atol=5e-3)


def test_each_layer_kind_takes_its_table():
    cfg = _tiny()
    pos = jnp.arange(100, dtype=jnp.int32)[None]
    plain, yarn = MDL._cos_sin(cfg, pos)
    for got, want in zip(MDL._layer_cs(cfg, (plain, yarn),
                                       jnp.int32(cfg.sliding_window)), plain):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(MDL._layer_cs(cfg, (plain, yarn), jnp.int32(0)),
                         yarn):
        np.testing.assert_array_equal(got, want)
    # without YaRN the tables pass through untouched: the program is the one
    # it was (one table, no choice)
    dense = _tiny("internlm2-1.8b")
    cs = MDL._cos_sin(dense, pos)
    assert MDL._layer_cs(dense, cs, jnp.int32(0)) is cs


def _moe_weights(cfg, key):
    """Every expert of a layer, [E, ...], and its router."""
    p = MOE.init_moe(key, replace(cfg, moe_held=cfg.n_experts,
                                  moe_held_first=0), jnp.float32)
    assert p["w1"].shape[0] == cfg.n_experts
    return p


def _share(p, first, n):
    return {"router": p["router"],
            **{k: p[k][first:first + n] for k in ("w1", "w2", "w3")}}


def _whole_layer(p, cfg, x):
    """Every expert over every token, plainly: top k of the softmax over
    all experts, renormalised, no capacity."""
    xt = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    logits = xt @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        top = np.argsort(-probs[t])[:cfg.moe_top_k]
        w = probs[t, top] / probs[t, top].sum()
        for e, we in zip(top, w):
            g = xt[t] @ np.asarray(p["w1"][e], np.float64)
            u = xt[t] @ np.asarray(p["w3"][e], np.float64)
            h = g / (1 + np.exp(-g)) * u
            out[t] += we * (h @ np.asarray(p["w2"][e], np.float64))
    return out.reshape(x.shape)


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_held_shares_sum_to_the_whole_layer(held):
    """The outputs of the shares [0, h), [h, 2h), ... add up to the uncut
    layer over all 8 experts, computed plainly in float64; each share
    alone is a part of it. Float32 matmuls of width 64 and 128 against
    float64: 1e-5 of the output's scale."""
    cfg = _tiny()
    E = cfg.n_experts
    p = _moe_weights(cfg, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, cfg.d_model))
    want = _whole_layer(p, cfg, x)
    total = 0
    for first in range(0, E, held):
        c = replace(cfg, moe_held=held, moe_held_first=first)
        y, _ = MOE.moe_held(_share(p, first, held), c, x)
        total = total + np.asarray(y, np.float64)
    scale = np.abs(want).max()
    assert np.abs(total - want).max() < 1e-5 * scale
    if held < E:                 # a share is not the whole
        assert np.abs(np.asarray(y) - want).max() > 1e-2 * scale


def test_held_share_does_not_depend_on_the_batch():
    """A token's output alone equals its output among 31 copies of itself
    and 32 other tokens: no capacity, so nothing is dropped. GShard
    capacity (``moe_local``) zeroes the copies past its experts' slots."""
    cfg = _tiny()
    p = MOE.init_moe(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 1, cfg.d_model))
    others = jax.random.normal(jax.random.PRNGKey(5), (1, 32, cfg.d_model))
    batch = jnp.concatenate([jnp.repeat(x, 32, axis=1), others], axis=1)
    alone, _ = MOE.moe_held(p, cfg, x)
    many, _ = MOE.moe_held(p, cfg, batch)
    np.testing.assert_allclose(many[0, :32], jnp.repeat(alone[0], 32, 0),
                               rtol=1e-5, atol=1e-6)
    # the capacity path over the whole layer, for contrast: 48 identical
    # tokens overflow their 4 experts' ceil(48 * 4 * 1.25 / 8) = 30 slots
    whole = replace(cfg, moe_held=0, moe_held_first=0)
    pw = MOE.init_moe(jax.random.PRNGKey(3), whole, jnp.float32)
    a, _ = MOE.moe_local(pw, whole, x)
    b, _ = MOE.moe_local(pw, whole, jnp.repeat(x, 48, axis=1))
    np.testing.assert_allclose(b[0, :30], jnp.repeat(a[0], 30, 0),
                               rtol=1e-5, atol=1e-6)
    assert np.abs(b[0, 30:]).max() == 0


@pytest.mark.parametrize("name,window", [
    (MELLUM, 16), ("gemma3-27b", 16), ("internlm2-1.8b", 0),
    ("qwen1.5-7b", 0), ("mixtral-8x22b", 0)])
def test_engine_runtime_bounds_window_layers(name, window):
    """The engine's own runtime gives a stack that mixes window and full
    layers the windowed decode branch; every other stack keeps one."""
    cfg = _tiny(name)
    eng = DecodeEngine(cfg, EngineConfig(n_slots=2, page_size=8, n_pages=16,
                                         max_context=64))
    assert eng.rt.cond_window == window == MDL.mixed_window(cfg)


def _serve(cfg, params, rt, kernels):
    ecfg = EngineConfig(n_slots=3, page_size=8, n_pages=96, max_context=256,
                        decode_horizon=4, eos_token=-1, use_pallas=kernels,
                        kernel_interpret=True if kernels else None)
    if rt is not None:
        from repro.kernels.backend import KernelConfig
        rt = MDL.Runtime(kernels=KernelConfig(use_pallas=kernels,
                                              interpret=True if kernels
                                              else None))
    eng = DecodeEngine(cfg, ecfg, params=params, rt=rt)
    rng = np.random.default_rng(0)
    for i, n in enumerate((70, 41, 23)):
        eng.submit(Request(i, rng.integers(0, cfg.vocab_size, n).tolist(),
                           12))
    eng.run()
    return eng


@pytest.mark.parametrize("kernels", [False, True], ids=["gather", "pallas"])
def test_windowed_branch_decodes_as_the_whole_table(kernels):
    """The engine's decode with the windowed branch (window layers read only
    the pages their 16-token window spans, in the stacked pool at their
    layer's offset) serves the same tokens as with the whole table on
    every layer, on the gather path and through the Pallas kernel."""
    cfg = _tiny()
    params = MDL.init_params(cfg, jax.random.PRNGKey(7))
    windowed = _serve(cfg, params, None, kernels)
    whole = _serve(cfg, params, "whole", kernels)
    assert windowed.rt.cond_window == 16 and whole.rt.cond_window == 0
    assert windowed.outputs == whole.outputs
    assert all(len(o) == 13 for o in windowed.outputs.values())
