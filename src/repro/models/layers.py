"""Core NN layers: norms, rotary embeddings, attention, MLPs, embeddings.

Everything is a pure function over explicit param pytrees. Matmuls run in the
model dtype (bf16) with fp32 accumulation (``preferred_element_type``);
norm/softmax/router math is fp32.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def dense(x: jax.Array, w) -> jax.Array:
    """x @ w in the compute dtype.

    bf16 in -> bf16 out: the MXU accumulates fp32 internally either way;
    requesting fp32 *outputs* (preferred_element_type=f32) materializes fp32
    activations/cotangents and pushes fp32 weight all-gathers into the FSDP
    path — measured 2x collective + activation traffic on the train cells
    (EXPERIMENTS.md §Perf T1). fp32 stays where it matters numerically:
    norms, softmax/flash accumulators, router/logits.

    ``w`` may be an int8 QTensor ({"q", "s"}, core/quant.py): dequantization
    fuses into the matmul per use — the int8 tensor is what streams from HBM.
    """
    if isinstance(w, dict):                      # int8 weight-only quant
        y = jax.lax.dot_general(
            x, w["q"].astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())))
        return (y * w["s"][..., 0, :].astype(x.dtype)).astype(x.dtype)
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ()))).astype(x.dtype)


def init_dense(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return out.astype(x.dtype)


def activate(x: jax.Array, kind: str) -> jax.Array:
    if kind in ("swiglu",):            # silu on the gate half (applied by caller)
        return jax.nn.silu(x)
    if kind in ("geglu", "gelu"):
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {kind!r}")


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE + M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, d_head // 2, dtype=np.float32) * 2 / d_head))


# YaRN's correction range in turns over the original context: HF's
# defaults, and the only values a served config uses
YARN_BETA_FAST, YARN_BETA_SLOW = 32.0, 1.0


def yarn_freqs(d_head: int, theta: float, factor: float,
               original_max: int) -> np.ndarray:
    """YaRN's inverse frequencies, as HF ``_compute_yarn_parameters`` makes
    them: each frequency blends the interpolated (``/ factor``) and the
    extrapolated (plain) one by a linear ramp over the dimensions between
    those that turn ``YARN_BETA_FAST`` and ``YARN_BETA_SLOW`` times over
    ``original_max`` positions (floored and ceiled, as HF truncates)."""
    def dim_of(turns):
        return (d_head * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(dim_of(YARN_BETA_FAST)), 0)
    hi = min(math.ceil(dim_of(YARN_BETA_SLOW)), d_head - 1)
    ramp = np.clip((np.arange(d_head // 2, dtype=np.float32) - lo)
                   / (hi - lo if hi > lo else 0.001), 0, 1)
    plain = rope_freqs(d_head, theta)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def rope_cos_sin(positions: jax.Array, d_head: int, theta: float,
                 inv: np.ndarray | None = None, scale: float = 1.0):
    """positions [...]->(cos,sin) of shape [..., d_head/2]; ``inv`` replaces
    the plain frequencies and ``scale`` multiplies both (YaRN)."""
    inv = jnp.asarray(rope_freqs(d_head, theta) if inv is None else inv)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def mrope_cos_sin(positions: jax.Array, d_head: int, theta: float,
                  sections: tuple[int, ...]):
    """M-RoPE: positions [3, B, S] (t/h/w); sections sum to d_head/2.

    Frequency slot j takes its position from the axis whose section owns j
    (Qwen2-VL §3.1, interleaved t/h/w layout simplified to contiguous blocks).
    """
    assert sum(sections) == d_head // 2, (sections, d_head)
    inv = jnp.asarray(rope_freqs(d_head, theta))
    sel = np.concatenate([np.full(s, i) for i, s in enumerate(sections)])
    pos = jnp.take(positions.astype(jnp.float32), jnp.asarray(sel), axis=0)  # [d/2,B,S]
    ang = jnp.moveaxis(pos, 0, -1) * inv                                     # [B,S,d/2]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B, S, H, D]; cos/sin [B, S, D/2] (or broadcastable)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention — chunked online-softmax ("lax-flash"), GQA + sliding window
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: jax.Array | int = 0,
                    q_offset: jax.Array | int = 0,
                    kv_chunk: int = 512) -> jax.Array:
    """Memory-bounded attention via online softmax over KV chunks.

    q [B, Sq, H, D]; k,v [B, Skv, KVH, D]. ``q_offset`` is the global position
    of q[0] relative to k[0] (sequence-parallel shards / prefill
    continuation); a [B] vector gives each request its own offset
    (prefix-cache suffix prefill batches different resume depths).
    ``window``>0 restricts attention to the last ``window`` keys (inclusive of
    self); it may be a traced scalar (per-layer scan value), 0 = unwindowed.
    Returns [B, Sq, H, D].

    GQA-group-aware: K/V are never repeated to H heads (grouped einsums), KV
    chunks are dynamic-sliced in place (no stacked/transposed copy), the
    probability matrix drops to the KV dtype for the PV matmul; fp32 lives
    only in the accumulators (§Perf P2).
    """
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    kv_chunk = min(kv_chunk, skv)
    while skv % kv_chunk:          # largest divisor <= requested chunk
        kv_chunk -= 1
    n_chunks = skv // kv_chunk

    qt = q.reshape(b, sq, kvh, g, d).transpose(0, 2, 3, 1, 4)  # [B,KVH,G,Sq,d]
    qt = qt.astype(jnp.float32)
    q_pos = (jnp.reshape(jnp.asarray(q_offset, jnp.int32), (-1, 1))
             + jnp.arange(sq)[None, :])                        # [1|B,Sq]
    scale = 1.0 / math.sqrt(d)
    w = jnp.asarray(window, jnp.int32)

    def step(carry, idx):
        m, l, o = carry
        kb = jax.lax.dynamic_slice_in_dim(k, idx * kv_chunk, kv_chunk, 1)
        vb = jax.lax.dynamic_slice_in_dim(v, idx * kv_chunk, kv_chunk, 1)
        kv_pos = idx * kv_chunk + jnp.arange(kv_chunk)[None, :]
        s = jnp.einsum("bkgqd,bckd->bkgqc", qt, kb,
                       preferred_element_type=jnp.float32) * scale
        ok = jnp.broadcast_to(kv_pos < skv, (1, kv_chunk))[:, None, :]
        ok = jnp.broadcast_to(ok, (1, sq, kv_chunk))
        if causal:
            ok = ok & (kv_pos[None, :, :] <= q_pos[:, :, None])
        ok = ok & ((w <= 0) | (kv_pos[None, :, :] > q_pos[:, :, None] - w))
        s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bkgqc,bckd->bkgqd", p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    o0 = jnp.zeros((b, kvh, g, sq, d), jnp.float32)
    (m, l, o), _ = jax.lax.scan(step, (m0, l0, o0),
                                jnp.arange(n_chunks, dtype=jnp.int32))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         ctx_len: jax.Array, *, window: int = 0) -> jax.Array:
    """Single-token decode attention against a (contiguous) cache.

    q [B, H, D]; k,v [B, T, KVH, D]; ctx_len [B] = number of valid cache
    entries (the new token's K/V already appended). Reference path / oracle.
    """
    b, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    k = _repeat_kv(k, h // kvh)
    v = _repeat_kv(v, h // kvh)
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32), k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    pos = jnp.arange(t)[None, :]
    ok = pos < ctx_len[:, None]
    if window:
        ok = ok & (pos >= ctx_len[:, None] - window)
    s = jnp.where(ok[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, act: str, dtype):
    ks = jax.random.split(key, 3)
    p = {"w1": init_dense(ks[0], d_model, d_ff, dtype),
         "w2": init_dense(ks[1], d_ff, d_model, dtype)}
    if act in ("swiglu", "geglu"):
        p["w3"] = init_dense(ks[2], d_model, d_ff, dtype)
    return p


def mlp(p, x: jax.Array, act: str) -> jax.Array:
    h = activate(dense(x, p["w1"]), act)
    if "w3" in p:
        h = h * dense(x, p["w3"])
    return dense(h, p["w2"])


# ---------------------------------------------------------------------------
# attention block params
# ---------------------------------------------------------------------------

def init_attention(key, cfg, dtype, *, cross: bool = False):
    ks = jax.random.split(key, 6)
    p = {"wq": init_dense(ks[0], cfg.d_model, cfg.q_dim, dtype),
         "wk": init_dense(ks[1], cfg.d_model, cfg.kv_dim, dtype),
         "wv": init_dense(ks[2], cfg.d_model, cfg.kv_dim, dtype),
         "wo": init_dense(ks[3], cfg.q_dim, cfg.d_model, dtype,
                          scale=1.0 / math.sqrt(cfg.q_dim * 2 * cfg.n_layers))}
    if cfg.qk_norm and not cross:
        p["qn"] = jnp.zeros((cfg.d_head,), dtype)
        p["kn"] = jnp.zeros((cfg.d_head,), dtype)
    return p


def qkv_project(p, cfg, x: jax.Array):
    """x [B,S,D] -> q [B,S,H,dh], k,v [B,S,KVH,dh], with qk-norm if configured."""
    b, s, _ = x.shape
    q = dense(x, p["wq"]).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = dense(x, p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = dense(x, p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    if "qn" in p:
        q = rms_norm(q, p["qn"], cfg.norm_eps)
        k = rms_norm(k, p["kn"], cfg.norm_eps)
    return q, k, v


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype):
    return (jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02).astype(dtype)


def embed(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def lm_head(x: jax.Array, w, *, transpose: bool) -> jax.Array:
    """Logits in fp32. ``transpose`` for tied embeddings ([V,D] table)."""
    if isinstance(w, dict):                      # int8 head (untied only)
        y = jax.lax.dot_general(
            x, w["q"].astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return y * w["s"][..., 0, :]
    wt = w.T if transpose else w
    return jax.lax.dot_general(
        x, wt, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def sinusoidal_positions(n: int, d: int) -> np.ndarray:
    pos = np.arange(n, dtype=np.float32)[:, None]
    div = np.exp(np.arange(0, d, 2, dtype=np.float32) * (-math.log(10000.0) / d))
    pe = np.zeros((n, d), np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div)
    return pe


def sinusoidal_at(positions: jax.Array, d: int) -> jax.Array:
    """Sinusoidal embedding at arbitrary (traced) positions [...]->[..., d]."""
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32)
                  * (-math.log(10000.0) / d))
    ang = positions.astype(jnp.float32)[..., None] * div
    out = jnp.zeros((*positions.shape, d), jnp.float32)
    out = out.at[..., 0::2].set(jnp.sin(ang))
    out = out.at[..., 1::2].set(jnp.cos(ang))
    return out
