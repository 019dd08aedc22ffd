"""Unified model zoo: one builder covering dense / MoE / SSM / hybrid /
enc-dec / VLM architectures from a ``ModelConfig``.

Layer stacks are scan-compiled (stacked params) for compile-time and memory
sanity at 60-80 layers. Heterogeneous patterns:

* gemma3 local:global — one uniform attention stack, per-layer ``window``
  flags ride through the scan as data;
* xlstm — scan over (mLSTM, sLSTM) cycles;
* zamba2 — scan over Mamba2 sub-stacks with a single shared attention block
  invoked between cycles (weights shared, zamba2-style);
* whisper — separate encoder stack (bidirectional) + decoder stack with
  cross-attention.

Execution is runtime-injected (``Runtime``): sharding constraints, the ITPP
sharded decode attention, and the expert-parallel MoE are provided by the
distribution layer; defaults are single-device reference paths so every model
runs standalone on CPU.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.core.itpp import ItppSpec, itpp_decode_attention_shard
from repro.kernels.backend import KernelConfig


# ---------------------------------------------------------------------------
# runtime injection
# ---------------------------------------------------------------------------

@dataclass
class Runtime:
    """Distribution hooks; defaults = single-device reference."""
    constrain: Callable = lambda x, name: x          # sharding constraints
    itpp: Callable | None = None                     # sharded decode attention
    moe: Callable | None = None                      # expert-parallel MoE
    write_pool: Callable | None = None               # sharded prefill writer
    remat: bool = False
    gla_chunk: int = 128
    ring_width: int = 0                              # sliding-window ring pool
    # decode-attention kernel selection (kernels/backend.py); None keeps the
    # gather-then-dense reference path unconditionally (seed semantics),
    # KernelConfig() autodetects (pallas on TPU, reference elsewhere)
    kernels: KernelConfig | None = None
    cond_window: int = 0                             # windowed-bound lax.cond

    def moe_apply(self, p, cfg, x):
        if self.moe is not None:
            return self.moe(p, cfg, x)
        return MOE.moe_local(p, cfg, x)

    def itpp_apply(self, q, k, v, pk, pv, bt, ctx, npage, noff, window,
                   layer=None):
        """Decode attention over the pool pair ``pk``/``pv``: one layer's
        ``[P, page, KVH, D]``, or with ``layer`` the whole stacked
        ``[L, P, ...]`` pool addressed in place."""
        if self.itpp is not None:
            # hooks serving only per-layer pools (pipeline stages) take
            # no ``layer``
            extra = {} if layer is None else {"layer": layer}
            return self.itpp(q, k, v, pk, pv, bt, ctx, npage, noff, window,
                             **extra)
        spec = ItppSpec((), (), None, 1, 1, pk.shape[-3])
        return itpp_decode_attention_shard(
            q, k, v, pk, pv, bt, ctx, npage, noff, window, spec=spec,
            mesh_axis_sizes={}, max_pages_per_req=bt.shape[1],
            ring_width=self.ring_width, cond_window=self.cond_window,
            kernels=self.kernels, layer=layer)


DEFAULT_RT = Runtime()


# ---------------------------------------------------------------------------
# per-kind layer init
# ---------------------------------------------------------------------------

def _init_attn_layer(key, cfg, dtype, *, cross: bool = False,
                     with_mlp: bool = True, moe_virtual: int = 0):
    ks = jax.random.split(key, 4)
    p = {"ln1": jnp.zeros((cfg.d_model,), dtype),
         "attn": L.init_attention(ks[0], cfg, dtype)}
    if cross:
        p["lnx"] = jnp.zeros((cfg.d_model,), dtype)
        p["xattn"] = L.init_attention(ks[1], cfg, dtype, cross=True)
    if with_mlp and (cfg.d_ff or cfg.is_moe):
        p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
        if cfg.is_moe:
            p["moe"] = MOE.init_moe(ks[2], cfg, dtype,
                                    n_virtual=moe_virtual or cfg.n_experts)
        else:
            p["mlp"] = L.init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


_KIND_INIT = {
    "mamba": SSM.init_mamba,
    "mlstm": SSM.init_mlstm,
    "slstm": SSM.init_slstm,
}


def _stack_init(key, n, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_params(cfg, key=None, dtype=None, *, moe_virtual: int = 0):
    key = key if key is not None else jax.random.PRNGKey(0)
    dtype = jnp.dtype(dtype or cfg.dtype)
    ks = jax.random.split(key, 8)
    params: dict[str, Any] = {
        "embed": L.init_embedding(ks[0], cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.init_dense(ks[1], cfg.d_model, cfg.padded_vocab,
                                      dtype, scale=0.02)
    kinds = cfg.block_kinds()
    if cfg.family == "encdec":
        params["enc"] = _stack_init(
            ks[2], cfg.enc_layers,
            lambda k: _init_attn_layer(k, cfg, dtype))
        params["enc_norm"] = jnp.zeros((cfg.d_model,), dtype)
        params["dec"] = _stack_init(
            ks[3], cfg.n_layers,
            lambda k: _init_attn_layer(k, cfg, dtype, cross=True))
        return params
    if all(k in ("attn", "local") for k in kinds):
        params["layers"] = _stack_init(
            ks[2], cfg.n_layers,
            lambda k: _init_attn_layer(k, cfg, dtype, moe_virtual=moe_virtual))
        return params
    if set(cfg.pattern) == {"mlstm", "slstm"}:          # xlstm
        n_cyc = cfg.n_layers // len(cfg.pattern)
        params["mlstm"] = _stack_init(
            ks[2], n_cyc, lambda k: SSM.init_mlstm(k, cfg, dtype))
        params["slstm"] = _stack_init(
            ks[3], n_cyc, lambda k: SSM.init_slstm(k, cfg, dtype))
        return params
    if set(cfg.pattern) == {"mamba", "attn"}:           # zamba2 hybrid
        n_cyc = cfg.n_layers // len(cfg.pattern)
        per_cyc = sum(1 for k in cfg.pattern if k == "mamba")
        params["mamba"] = _stack_init(
            ks[2], n_cyc * per_cyc, lambda k: SSM.init_mamba(k, cfg, dtype))
        params["attn_shared"] = _init_attn_layer(ks[3], cfg, dtype)
        return params
    raise NotImplementedError(cfg.pattern)


def param_count_actual(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# position embeddings
# ---------------------------------------------------------------------------

def _cos_sin(cfg, positions):
    """positions [B,S] (rope) or [3,B,S] (mrope) -> cos/sin [B,S,dh/2]."""
    if cfg.rope_kind == "none":
        return None
    if cfg.rope_kind == "mrope":
        return L.mrope_cos_sin(positions, cfg.d_head, cfg.rope_theta,
                               cfg.mrope_sections)
    return L.rope_cos_sin(positions, cfg.d_head, cfg.rope_theta)


def default_positions(cfg, B, S, offset=0):
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None] + offset, (B, S))
    if cfg.rope_kind == "mrope":
        return jnp.broadcast_to(pos[None], (3, B, S))
    return pos


# ---------------------------------------------------------------------------
# training / prefill blocks (full-sequence)
# ---------------------------------------------------------------------------

def _attn_mlp_block(p, cfg, x, cs, window, rt: Runtime, *,
                    causal=True, enc_out=None, enc_cs=None):
    B, S, D = x.shape
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = L.qkv_project(p["attn"], cfg, h)
    if cs is not None:
        q = L.apply_rope(q, *cs)
        k = L.apply_rope(k, *cs)
    k = rt.constrain(k, "kv_full")
    v = rt.constrain(v, "kv_full")
    a = L.flash_attention(q, k, v, causal=causal, window=window)
    x = x + L.dense(a.reshape(B, S, cfg.q_dim), p["attn"]["wo"])
    aux = jnp.float32(0)
    if "xattn" in p:
        h = L.rms_norm(x, p["lnx"], cfg.norm_eps)
        qx = L.dense(h, p["xattn"]["wq"]).reshape(B, S, cfg.n_heads, cfg.d_head)
        kx = L.dense(enc_out, p["xattn"]["wk"]).reshape(
            B, -1, cfg.n_kv_heads, cfg.d_head)
        vx = L.dense(enc_out, p["xattn"]["wv"]).reshape(
            B, -1, cfg.n_kv_heads, cfg.d_head)
        ax = L.flash_attention(qx, kx, vx, causal=False)
        x = x + L.dense(ax.reshape(B, S, cfg.q_dim), p["xattn"]["wo"])
    if "ln2" in p:
        h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if "moe" in p:
            y, aux = rt.moe_apply(p["moe"], cfg, h2)
        else:
            y = L.mlp(p["mlp"], h2, cfg.act)
        x = x + y
    x = rt.constrain(x, "act")
    return x, aux


def _window_array(cfg) -> np.ndarray:
    return np.asarray([cfg.sliding_window if k == "local" else 0
                       for k in cfg.block_kinds()], np.int32)


def _stack_forward_train(cfg, params, x, cs, rt: Runtime):
    """Uniform attention stack via scan (dense/moe/local patterns)."""
    windows = jnp.asarray(_window_array(cfg))

    def body(carry, xs):
        h, aux = carry
        lp, w = xs
        h, a = _attn_mlp_block(lp, cfg, h, cs, w, rt)
        return (h, aux + a), None

    body = jax.checkpoint(body) if rt.remat else body
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)),
                               (params["layers"], windows))
    return x, aux


def _xlstm_forward_train(cfg, params, x, rt: Runtime):
    def body(carry, lp):
        h = carry
        y, _ = SSM.mlstm_forward(lp["m"], cfg, h, chunk=rt.gla_chunk)
        h = rt.constrain(h + y, "act")
        y, _ = SSM.slstm_forward(lp["s"], cfg, h)
        return rt.constrain(h + y, "act"), None

    body = jax.checkpoint(body) if rt.remat else body
    x, _ = jax.lax.scan(body, x, {"m": params["mlstm"], "s": params["slstm"]})
    return x, jnp.float32(0)


def _zamba_forward_train(cfg, params, x, cs, rt: Runtime):
    n_cyc = cfg.n_layers // len(cfg.pattern)
    per_cyc = sum(1 for k in cfg.pattern if k == "mamba")

    def mamba_body(h, lp):
        y, _ = SSM.mamba_forward(lp, cfg, h, chunk=rt.gla_chunk)
        return rt.constrain(h + y, "act"), None

    mamba_body = jax.checkpoint(mamba_body) if rt.remat else mamba_body
    aux = jnp.float32(0)
    for c in range(n_cyc):                       # unrolled: n_cyc == 2
        sub = jax.tree.map(lambda a: a[c * per_cyc:(c + 1) * per_cyc],
                           params["mamba"])
        x, _ = jax.lax.scan(mamba_body, x, sub)
        x, a = _attn_mlp_block(params["attn_shared"], cfg, x, cs, 0, rt)
        aux = aux + a
    return x, aux


def encode(cfg, params, frames, rt: Runtime = DEFAULT_RT):
    """Whisper encoder over stub frame embeddings [B, enc_seq, D]."""
    pe = jnp.asarray(L.sinusoidal_positions(frames.shape[1], cfg.d_model))
    x = (frames + pe[None].astype(frames.dtype))

    def body(h, lp):
        h, _ = _attn_mlp_block(lp, cfg, h, None, 0, rt, causal=False)
        return h, None

    body = jax.checkpoint(body) if rt.remat else body
    x, _ = jax.lax.scan(body, x, params["enc"])
    return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward_hidden(cfg, params, tokens, *, positions=None, extra_embeds=None,
                   frames=None, rt: Runtime = DEFAULT_RT):
    """Full-sequence forward -> (final hidden [B,S,D], moe aux)."""
    B, S = tokens.shape
    x = L.embed(params["embed"], tokens)
    if extra_embeds is not None:                 # VLM stub modality fusion
        x = x + extra_embeds.astype(x.dtype)
    if cfg.rope_kind == "none" and cfg.family == "encdec":
        pe = jnp.asarray(L.sinusoidal_positions(S, cfg.d_model))
        x = x + pe[None].astype(x.dtype)
    x = rt.constrain(x, "act")
    if positions is None:
        positions = default_positions(cfg, B, S)
    cs = _cos_sin(cfg, positions)

    if cfg.family == "encdec":
        enc_out = encode(cfg, params, frames, rt)

        def body(carry, lp):
            h, aux = carry
            h, a = _attn_mlp_block(lp, cfg, h, cs, 0, rt, enc_out=enc_out)
            return (h, aux + a), None

        body = jax.checkpoint(body) if rt.remat else body
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)), params["dec"])
    elif "layers" in params:
        x, aux = _stack_forward_train(cfg, params, x, cs, rt)
    elif "mlstm" in params:
        x, aux = _xlstm_forward_train(cfg, params, x, rt)
    else:
        x, aux = _zamba_forward_train(cfg, params, x, cs, rt)
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def forward(cfg, params, tokens, *, positions=None, extra_embeds=None,
            frames=None, rt: Runtime = DEFAULT_RT):
    """Full-sequence forward -> (fp32 logits [B, S, padded_vocab], aux)."""
    x, aux = forward_hidden(cfg, params, tokens, positions=positions,
                            extra_embeds=extra_embeds, frames=frames, rt=rt)
    w = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = L.lm_head(x, w, transpose=cfg.tie_embeddings)
    return rt.constrain(logits, "logits"), aux


def train_loss(cfg, params, batch, rt: Runtime = DEFAULT_RT,
               *, loss_chunk: int = 1024):
    """batch: tokens/targets [B,S], mask [B,S]; returns (loss, metrics).

    Cross-entropy is computed in sequence chunks (remat'd) so [B,S,V] logits
    never materialize — at 4k x 256k-vocab the full fp32 logits would be
    ~4 GB/device, dominating the training memory term.
    """
    hidden, aux = forward_hidden(cfg, params, batch["tokens"],
                                 positions=batch.get("positions"),
                                 extra_embeds=batch.get("extra_embeds"),
                                 frames=batch.get("frames"), rt=rt)
    w = params["embed"] if cfg.tie_embeddings else params["head"]
    tgt = batch["targets"]
    mask = batch["mask"].astype(jnp.float32)
    B, S = tgt.shape
    c = min(loss_chunk, S)
    n_chunks = S // c if S % c == 0 else 1
    if S % c != 0:
        c = S

    @jax.checkpoint
    def chunk_nll(h_c, t_c, m_c):
        logits = L.lm_head(h_c, w, transpose=cfg.tie_embeddings)
        logits = rt.constrain(logits, "logits")
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
        return ((lse - picked) * m_c).sum()

    def body(carry, xs):
        h_c, t_c, m_c = xs
        return carry + chunk_nll(h_c, t_c, m_c), None

    hs = hidden.reshape(B, n_chunks, c, -1).transpose(1, 0, 2, 3)
    ts = tgt.reshape(B, n_chunks, c).transpose(1, 0, 2)
    ms = mask.reshape(B, n_chunks, c).transpose(1, 0, 2)
    nll, _ = jax.lax.scan(body, jnp.float32(0), (hs, ts, ms))
    loss = nll / jnp.maximum(mask.sum(), 1.0)
    if cfg.is_moe:
        loss = loss + 0.01 * aux / max(1, cfg.n_layers)
    return loss, {"nll": loss, "tokens": mask.sum()}


# ---------------------------------------------------------------------------
# decode (single token, paged KV via ITPP)
# ---------------------------------------------------------------------------

# The decode program's compute scopes (``jax.named_scope``), as they show in
# each compiled op's ``op_name`` metadata: a device op under none of them is
# data movement (the layer scan's slices of the KV pool and of the stacked
# weights, the pool's write-back, XLA's copies). ``layers`` marks the layer
# scan itself.
DECODE_SCOPES = ("embed", "attn", "kv_write", "paged_attn", "mlp", "head",
                 "sample")


def _attn_block_decode(p, cfg, x, cs, window, pool_k, pool_v, bt, ctx,
                       npage, noff, rt: Runtime, cross_kv=None, layer=None):
    """x [B, D] one token. Returns (x, pool_k, pool_v); with ``layer`` the
    pools are the whole stack (``Runtime.itpp_apply``). The named scopes
    (``attn``, ``paged_attn`` with ``kv_write`` inside it, ``mlp``) put the
    block's compute under those names in the compiled program's metadata;
    see ``DECODE_SCOPES``."""
    B, D = x.shape
    with jax.named_scope("attn"):
        h = L.rms_norm(x[:, None, :], p["ln1"], cfg.norm_eps)
        q, k, v = L.qkv_project(p["attn"], cfg, h)          # [B,1,H,dh]
        if cs is not None:
            q = L.apply_rope(q, *cs)
            k = L.apply_rope(k, *cs)
    with jax.named_scope("paged_attn"):
        a, pool_k, pool_v = rt.itpp_apply(
            q[:, 0], k[:, 0], v[:, 0], pool_k, pool_v, bt, ctx, npage, noff,
            window, layer=layer)
    with jax.named_scope("attn"):
        x = x + L.dense(a.reshape(B, cfg.q_dim), p["attn"]["wo"])
        if cross_kv is not None:
            h = L.rms_norm(x[:, None, :], p["lnx"], cfg.norm_eps)
            qx = L.dense(h, p["xattn"]["wq"]).reshape(B, cfg.n_heads,
                                                     cfg.d_head)
            kx, vx = cross_kv
            ax = L.decode_attention_ref(
                qx, kx, vx, jnp.full((B,), kx.shape[1], jnp.int32))
            x = x + L.dense(ax.reshape(B, cfg.q_dim), p["xattn"]["wo"])
    if "ln2" in p:
        with jax.named_scope("mlp"):
            h2 = L.rms_norm(x[:, None, :], p["ln2"], cfg.norm_eps)
            if "moe" in p:
                y, _ = rt.moe_apply(p["moe"], cfg, h2)
            else:
                y = L.mlp(p["mlp"], h2, cfg.act)
            x = x + y[:, 0]
    return rt.constrain(x, "act_decode"), pool_k, pool_v


# state entries with a per-slot batch row at axis 1 ([L, B, ...] leaves):
# the recurrent carry (SSM/xLSTM hidden + conv states) and the enc-dec
# cross-attention KV. Everything the serving engine must snapshot/restore
# per slot for state-carrying chunked/batched prefill and
# preemption-resume; the paged ``pool`` is deliberately NOT here (pages are
# per-request via the block table, owned by the allocator).
RSTATE_KEYS = ("mamba", "mlstm", "slstm", "cross_k", "cross_v")


def rstate_entries(state) -> dict[str, Any]:
    """The per-slot recurrent/cross entries present in a decode state."""
    return {k: state[k] for k in RSTATE_KEYS if k in state}


def init_rstate(cfg, batch: int, *, dtype=None) -> dict[str, Any]:
    """Fresh (zero) recurrent/cross state for ``batch`` slots — every leaf
    [L, batch, ...]."""
    state: dict[str, Any] = {}
    kinds = cfg.block_kinds()
    if "mamba" in kinds:
        n_m = sum(1 for k in kinds if k == "mamba")
        state["mamba"] = jax.vmap(
            lambda _: SSM.mamba_init_state(cfg, batch))(jnp.arange(n_m))
    if "mlstm" in kinds:
        n = cfg.n_layers // 2
        state["mlstm"] = jax.vmap(
            lambda _: SSM.mlstm_init_state(cfg, batch))(jnp.arange(n))
        state["slstm"] = jax.vmap(
            lambda _: SSM.slstm_init_state(cfg, batch))(jnp.arange(n))
    if cfg.family == "encdec":
        state["cross_k"] = jnp.zeros(
            (cfg.n_layers, batch, cfg.enc_seq, cfg.n_kv_heads, cfg.d_head),
            jnp.dtype(dtype or cfg.dtype))
        state["cross_v"] = jnp.zeros_like(state["cross_k"])
    return state


def gather_rstate(state, idx) -> dict[str, Any]:
    """Rows ``idx`` of every recurrent/cross entry ([L, B, ...] ->
    [L, len(idx), ...]) — the engine's per-slot group gather for batched
    prefill and preemption snapshots."""
    idx = jnp.asarray(idx, jnp.int32)
    return jax.tree.map(lambda a: a[:, idx], rstate_entries(state))


def scatter_rstate(state, idx, rows) -> dict[str, Any]:
    """Return ``state`` with recurrent/cross rows ``idx`` replaced by
    ``rows`` (a ``gather_rstate``-shaped tree). Non-rstate entries pass
    through untouched."""
    idx = jnp.asarray(idx, jnp.int32)
    out = dict(state)
    out.update(jax.tree.map(lambda a, r: a.at[:, idx].set(r),
                            rstate_entries(state), rows))
    return out


def init_decode_state(cfg, pool_spec, batch: int, *, dtype=None):
    """Decode-side caches: paged pools for attention layers + recurrent
    states for ssm layers (+ cross-attn KV for enc-dec)."""
    from repro.core.paged_kv import init_pool
    state: dict[str, Any] = {}
    kinds = cfg.block_kinds()
    if any(k in ("attn", "local") for k in kinds) or cfg.family == "encdec":
        state["pool"] = init_pool(pool_spec)
    state.update(init_rstate(cfg, batch, dtype=dtype))
    return state


def make_cross_kv(cfg, params, enc_out):
    """Precompute whisper cross-attention KV [L, B, enc, KVH, dh]."""
    def one(lp):
        kx = L.dense(enc_out, lp["xattn"]["wk"]).reshape(
            enc_out.shape[0], -1, cfg.n_kv_heads, cfg.d_head)
        vx = L.dense(enc_out, lp["xattn"]["wv"]).reshape(
            enc_out.shape[0], -1, cfg.n_kv_heads, cfg.d_head)
        return kx, vx
    return jax.vmap(one)(params["dec"])


def _keep_rows(new, old, run):
    """Advance recurrent state only for running slots: rows with
    ``run=False`` keep their previous carry. Leaves are [L, B, ...]."""
    if run is None:
        return new
    return jax.tree.map(
        lambda n, o: jnp.where(run.reshape((1, -1) + (1,) * (n.ndim - 2)),
                               n, o), new, old)


def decode_step(cfg, params, state, tokens, bt, ctx, npage, noff, *,
                positions=None, run=None, rt: Runtime = DEFAULT_RT):
    """One decode step for the whole batch.

    tokens [B]; bt [B, maxp]; ctx [B] (INCLUDING the new token);
    npage/noff [B] write target for the new token's KV.
    ``run`` [B] bool: slots decoding this step. Attention KV writes already
    drop for non-running slots (out-of-bounds ``npage``), but recurrent /
    SSM state is a dense per-slot carry — without the mask an idle, paused
    or mid-chunk-prefill slot would absorb its stale pending token every
    step and corrupt the carry. ``None`` keeps the legacy advance-all
    behavior (callers whose batch is wholly active).
    Returns (fp32 logits [B, V], new_state).
    """
    B = tokens.shape[0]
    with jax.named_scope("embed"):
        x = L.embed(params["embed"], tokens)            # [B, D]
        if cfg.rope_kind == "none" and cfg.family == "encdec":
            x = x + L.sinusoidal_at(ctx - 1, cfg.d_model).astype(x.dtype)
        if positions is None:
            pos = (ctx - 1).astype(jnp.int32)[:, None]  # [B,1]
            if cfg.rope_kind == "mrope":
                pos = jnp.broadcast_to(pos[None], (3, B, 1))
            positions = pos
        cs = _cos_sin(cfg, positions)
    x = rt.constrain(x, "act_decode")
    kinds = cfg.block_kinds()
    state = dict(state)

    if cfg.family == "encdec" or all(k in ("attn", "local") for k in kinds):
        windows = jnp.asarray(_window_array(cfg))
        pool = state["pool"]
        stack = params["dec"] if cfg.family == "encdec" else params["layers"]
        has_cross = cfg.family == "encdec"

        # the whole stacked pool rides the scan's carry and each layer
        # addresses its own pages in it (``layer=``): the token scatters in
        # place and the kernel reads the stack. Passed as xs/ys instead, each
        # layer's pool was sliced out, written back into a new ys stack, and
        # copied whole into the horizon's carry: 30-58% of the decode step's
        # device time on a TPU v5e, where the token write needs a few KiB.
        def body(carry, xs):
            h, pk, pv = carry
            if has_cross:
                lp, w, i, ck, cv = xs
                cross = (ck, cv)
            else:
                lp, w, i = xs
                cross = None
            h, pk, pv = _attn_block_decode(lp, cfg, h, cs, w, pk, pv, bt,
                                           ctx, npage, noff, rt,
                                           cross_kv=cross, layer=i)
            return (h, pk, pv), None

        index = jnp.arange(windows.shape[0], dtype=jnp.int32)
        xs = ((stack, windows, index, state["cross_k"], state["cross_v"])
              if has_cross else (stack, windows, index))
        with jax.named_scope("layers"):
            (x, pk, pv), _ = jax.lax.scan(body, (x, pool["k"], pool["v"]),
                                          xs)
        state["pool"] = {"k": pk, "v": pv}
    elif "mlstm" in params:
        def body(carry, xs):
            h = carry
            lp_m, lp_s, st_m, st_s = xs
            y, st_m = SSM.mlstm_step(lp_m, cfg, h, st_m)
            h = h + y
            y, st_s = SSM.slstm_step(lp_s, cfg, h, st_s)
            return h + y, (st_m, st_s)

        (x), (new_m, new_s) = jax.lax.scan(
            body, x, (params["mlstm"], params["slstm"],
                      state["mlstm"], state["slstm"]))
        state["mlstm"] = _keep_rows(new_m, state["mlstm"], run)
        state["slstm"] = _keep_rows(new_s, state["slstm"], run)
    else:                                               # zamba hybrid
        n_cyc = cfg.n_layers // len(cfg.pattern)
        per_cyc = sum(1 for k in cfg.pattern if k == "mamba")
        pool = state["pool"]
        pk, pv = pool["k"], pool["v"]
        new_mamba = []

        def mbody(h, xs):
            lp, st = xs
            y, st = SSM.mamba_step(lp, cfg, h, st)
            return h + y, st

        for c in range(n_cyc):
            sl = lambda a: a[c * per_cyc:(c + 1) * per_cyc]
            x, st_out = jax.lax.scan(
                mbody, x, (jax.tree.map(sl, params["mamba"]),
                           jax.tree.map(sl, state["mamba"])))
            new_mamba.append(st_out)
            pkl, pvl = pk[c], pv[c]
            x, pkl, pvl = _attn_block_decode(
                params["attn_shared"], cfg, x, cs, 0, pkl, pvl,
                bt, ctx, npage, noff, rt)
            pk = pk.at[c].set(pkl)
            pv = pv.at[c].set(pvl)
        state["pool"] = {"k": pk, "v": pv}
        state["mamba"] = _keep_rows(
            jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *new_mamba),
            state["mamba"], run)

    with jax.named_scope("head"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        w = params["embed"] if cfg.tie_embeddings else params["head"]
        logits = L.lm_head(x, w, transpose=cfg.tie_embeddings)
    return rt.constrain(logits, "logits_decode"), state


def decode_multi(cfg, params, state, tokens, bt, ctx, rem, allow, key, *,
                 horizon: int, table_width: int, page_size: int, n_pages: int,
                 eos_token: int, sample, rt: Runtime = DEFAULT_RT):
    """Fused multi-step decode: ``horizon`` decode steps, on-device sampling
    and per-slot EOS/budget masking under ONE ``lax.scan`` — the host syncs
    once per horizon instead of once per token.

    Device-resident slot state (all [B] unless noted):
      tokens — incoming token per slot (the previous sample);
      bt     — [B, W] Va2Pa block table; attention reads the leading
               ``table_width`` slots (the engine's pow2 live-page bucket),
               write targets resolve against the full width;
      ctx    — context INCLUDING the incoming token;
      rem    — tokens the slot may still emit (budget - generated + 1);
      allow  — steps the slot may run THIS horizon (page reservation /
               chunked-prefill clamp; 0 = idle or frozen);
      key    — PRNG key chain for the sampler (split once per step).

    Per step, for every running slot: write the incoming token's KV
    (``ops.write_targets`` routes frozen slots out of bounds so their
    scatter drops), decode, sample ``sample(key, logits)``, then freeze the
    slot if the sampled token is EOS or the budget is spent. A slot that
    merely exhausts ``allow`` pauses with its pending token intact and
    resumes next horizon — per-token trajectories are identical for every
    horizon, so greedy outputs are horizon-invariant.

    Returns ``(toks [K, B], emit [K, B] bool, finished [B] bool, state,
    tokens, ctx, rem, key)`` — the last five re-enter the next horizon.
    """
    from repro.kernels.ops import write_targets
    W = bt.shape[1]
    bt_attn = bt[:, :table_width] if table_width < W else bt
    # samplers that opt in via the ``takes_run`` attribute (host-callback
    # adapters invoking a legacy per-row callable for active rows only)
    # get the run mask as a third argument
    sample_takes_run = getattr(sample, "takes_run", False)

    def body(carry, _):
        tokens, ctx, rem, allow, alive, state, key = carry
        with jax.named_scope("kv_write"):
            run = alive & (allow > 0)
            npage, noff = write_targets(bt, ctx, run, page_size=page_size,
                                        n_pages=n_pages,
                                        ring_width=rt.ring_width)
        logits, state = decode_step(cfg, params, state, tokens, bt_attn,
                                    ctx, npage, noff, run=run, rt=rt)
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
            nxt = sample(sub, logits, run) if sample_takes_run \
                else sample(sub, logits)
            tokens = jnp.where(run, nxt, tokens)
            rem = jnp.where(run, rem - 1, rem)
            fin = run & ((nxt == eos_token) | (rem <= 0))
            alive = alive & ~fin
            # finished slots freeze at their final context; paused (allow
            # spent) and running slots advance so the pending token's write
            # position is ready for the next step/horizon
            ctx = jnp.where(run & ~fin, ctx + 1, ctx)
            allow = jnp.where(run, allow - 1, allow)
        return (tokens, ctx, rem, allow, alive, state, key), (nxt, run)

    alive0 = allow > 0
    carry = (tokens, ctx, rem, allow, alive0, state, key)
    (tokens, ctx, rem, allow, alive, state, key), (toks, emit) = jax.lax.scan(
        body, carry, None, length=horizon)
    return toks, emit, alive0 & ~alive, state, tokens, ctx, rem, key


# ---------------------------------------------------------------------------
# speculative decode: draft-propose + one-pass multi-query verify
# ---------------------------------------------------------------------------

def draft_propose(cfg, params, state, tokens, bt, ctx, allow, key, *,
                  horizon: int, table_width: int, page_size: int,
                  n_pages: int, sample, need_q: bool,
                  rt: Runtime = DEFAULT_RT):
    """Draft side of a speculative round: up to ``horizon`` masked decode
    steps proposing the next tokens for every slot at once.

    The draft shares the TARGET's block tables and page ids — its (smaller)
    pool is indexed by the same Va2Pa, so draft KV at any page/offset is a
    pure function of (token prefix, position) and radix-shared pages stay
    coherent across requests. ``tokens``/``ctx`` are the target's
    device-resident slot state (ctx INCLUDING the pending token, whose
    draft KV is written by step 0 at position ctx-1); ``allow`` is the
    horizon reservation — step ``i`` runs where ``i < clip(allow-1, 0,
    horizon)``, so a slot reserved for a single token proposes nothing and
    the verify pass degrades to plain decode for it.

    ``sample``: scan-sampler ``(key, logits) -> tokens`` (the engine's
    kind, so the proposal distribution q matches what the verifier
    assumes). ``need_q``: stack the raw per-step logits for residual
    rejection sampling (stochastic kinds only — greedy needs tokens alone).
    Returns ``(proposals [B, horizon], qlogits [horizon, B, V] | None,
    state, key)``; proposals/ctx/tokens of masked slots are untouched
    garbage the verifier masks out via its own ``allow``.
    """
    from repro.kernels.ops import write_targets
    W = bt.shape[1]
    bt_attn = bt[:, :table_width] if table_width < W else bt
    nprop = jnp.clip(allow - 1, 0, horizon)

    def body(carry, i):
        tokens, ctx, state, key = carry
        run = i < nprop
        npage, noff = write_targets(bt, ctx, run, page_size=page_size,
                                    n_pages=n_pages,
                                    ring_width=rt.ring_width)
        logits, state = decode_step(cfg, params, state, tokens, bt_attn,
                                    ctx, npage, noff, run=run, rt=rt)
        key, sub = jax.random.split(key)
        nxt = sample(sub, logits)
        tokens = jnp.where(run, nxt, tokens)
        ctx = jnp.where(run, ctx + 1, ctx)
        return (tokens, ctx, state, key), \
            ((nxt, logits) if need_q else nxt)

    carry = (tokens, ctx, state, key)
    (_, _, state, key), ys = jax.lax.scan(body, carry, jnp.arange(horizon))
    toks, qlogits = ys if need_q else (ys, None)
    return toks.T, qlogits, state, key


def decode_verify(cfg, params, state, tokens, proposals, qlogits, bt, ctx,
                  rem, allow, key, *, horizon: int, table_width: int,
                  page_size: int, n_pages: int, eos_token: int, verifier,
                  rt: Runtime = DEFAULT_RT):
    """One-pass speculative verify: score the pending token plus the
    draft's ``horizon`` proposals in a single multi-query target forward,
    accept a prefix, and advance the device slot state exactly as
    ``decode_multi`` would have.

    The round forwards ``[pending, d_1..d_G]`` at positions ctx-1..ctx+G-1
    (uniform attention stacks only — recurrent carries cannot roll back
    past rejected tokens). Every row's K/V lands via the multi-token
    ``write_prefill(ctx_start=ctx-1, valid_len=nprop+1)`` route — frozen /
    idle slots get valid_len 0 so their scatter drops, exactly like frozen
    slots in the fused scan — then ``kernels.ops.verify_attention`` scores
    all G+1 query rows against the paged pool in one split-K pass (query
    row t masked to tok < ctx+t, so the causal frontier advances inside the
    round). Rollback is free: rejected positions' KV is dead beyond the new
    ctx (attention masks it) and the next round's writes start at the new
    ctx-1, overwriting the first stale row before it can ever be read.

    ``verifier`` (serving.sampling.make_verifier) turns (logits, qlogits,
    proposals) into ``(candidates [B, G+1], accept_len [B])`` — greedy
    longest-matching-prefix (token-identical to target-only decoding) or
    stochastic residual rejection sampling. The emitted run is
    ``candidates[:e]`` with ``e = accept_len+1`` truncated at the first
    EOS/budget stop, replicating ``decode_multi``'s freeze semantics
    (finished slots do not advance past their final token).

    Returns ``(toks [G+1, B], emit [G+1, B] bool, finished [B], state,
    tokens, ctx, rem, key, accept_len [B])`` — decode_multi's contract plus
    the accept counter, so the engine folds spec rounds and plain horizons
    identically.
    """
    from repro.core.paged_kv import write_prefill
    from repro.kernels.ops import verify_attention
    B = tokens.shape[0]
    C = horizon + 1
    run = allow > 0
    nprop = jnp.clip(allow - 1, 0, horizon)
    seq = jnp.concatenate([tokens[:, None], proposals], axis=1)   # [B, C]
    start = jnp.maximum(ctx - 1, 0).astype(jnp.int32)
    valid_len = jnp.where(run, nprop + 1, 0)
    W = bt.shape[1]
    bt_attn = bt[:, :table_width] if table_width < W else bt
    kc = rt.kernels

    x = L.embed(params["embed"], seq)
    x = rt.constrain(x, "act")
    positions = default_positions(cfg, B, C, offset=start[:, None])
    cs = _cos_sin(cfg, positions)
    state = dict(state)
    windows = jnp.asarray(_window_array(cfg))
    pool = state["pool"]

    def block(h, xs):
        lp, w, pkl, pvl = xs
        hn = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = L.qkv_project(lp["attn"], cfg, hn)      # [B, C, H, dh]
        if cs is not None:
            q = L.apply_rope(q, *cs)
            k = L.apply_rope(k, *cs)
        pkl, pvl = write_prefill(pkl, pvl, k, v, bt, ctx_start=start,
                                 valid_len=valid_len)
        grp = cfg.n_heads // cfg.n_kv_heads
        qr = q.transpose(0, 2, 1, 3).reshape(
            B, cfg.n_kv_heads, grp, C, cfg.d_head)
        a = verify_attention(
            qr, pkl, pvl, bt_attn, ctx, window=w,
            use_pallas=False if kc is None else kc.use_pallas,
            interpret=None if kc is None else kc.interpret,
            n_splits=1 if kc is None else kc.n_splits)
        a = a.transpose(0, 3, 1, 2, 4).reshape(B, C, cfg.q_dim)
        h = h + L.dense(a, lp["attn"]["wo"])
        return _prefill_block_tail(lp, cfg, h, None, rt), (pkl, pvl)

    x, (pk, pv) = jax.lax.scan(
        block, x, (params["layers"], windows, pool["k"], pool["v"]))
    state["pool"] = {"k": pk, "v": pv}
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    w_out = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = L.lm_head(x, w_out, transpose=cfg.tie_embeddings)    # [B, C, V]

    key, cand, acc = verifier(key, logits, qlogits, proposals, nprop, run)
    # EOS / budget truncation over the accepted run, replicating the fused
    # scan's per-step freeze: candidate j is EOS or spends the budget ->
    # emit exactly j+1 tokens and finish without advancing past them
    idx = jnp.arange(C)
    stop = (cand == eos_token) | (rem[:, None] - (idx + 1)[None] <= 0)
    stopped = (idx[None] <= acc[:, None]) & stop & run[:, None]
    any_stop = stopped.any(axis=1)
    e = jnp.where(any_stop, jnp.argmax(stopped, axis=1) + 1, acc + 1)
    e = jnp.where(run, e, 0).astype(jnp.int32)
    emit = idx[None] < e[:, None]                                 # [B, C]
    fin = run & any_stop
    newtok = cand[jnp.arange(B), jnp.maximum(e - 1, 0)]
    tokens = jnp.where(run, newtok, tokens)
    rem = jnp.where(run, rem - e, rem)
    ctx = jnp.where(run, ctx + e - fin.astype(jnp.int32), ctx)
    return (cand.T, emit.T, fin, state, tokens, ctx, rem, key,
            jnp.where(run, acc, 0).astype(jnp.int32))


# ---------------------------------------------------------------------------
# prefill: full-sequence forward that also fills the decode caches
# ---------------------------------------------------------------------------

def _prefill_block_tail(lp, cfg, h, cross, rt: Runtime):
    """Cross-attention + FFN epilogue of a prefill attention block, shared
    by the whole-sequence (``prefill``) and chunked (``prefill_chunk``)
    paths so the two can never diverge. ``cross``: (k, v) rows [B, enc,
    KVH, D] or None."""
    B, S = h.shape[:2]
    if cross is not None:
        hx = L.rms_norm(h, lp["lnx"], cfg.norm_eps)
        qx = L.dense(hx, lp["xattn"]["wq"]).reshape(
            B, S, cfg.n_heads, cfg.d_head)
        ax = L.flash_attention(qx, cross[0], cross[1], causal=False)
        h = h + L.dense(ax.reshape(B, S, cfg.q_dim), lp["xattn"]["wo"])
    if "ln2" in lp:
        h2 = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
        y = (rt.moe_apply(lp["moe"], cfg, h2)[0] if "moe" in lp
             else L.mlp(lp["mlp"], h2, cfg.act))
        h = h + y
    return rt.constrain(h, "act")


def _xlstm_prefill_body(cfg, rt: Runtime, mask):
    """Scan body over (mLSTM, sLSTM) cycles with explicit state carry,
    shared by ``prefill`` and ``prefill_chunk``."""
    def body(carry, xs):
        h = carry
        lp_m, lp_s, st_m, st_s = xs
        y, st_m = SSM.mlstm_forward(lp_m, cfg, h, state=st_m,
                                    chunk=rt.gla_chunk, mask=mask)
        h = h + y
        y, st_s = SSM.slstm_forward(lp_s, cfg, h, state=st_s, mask=mask)
        return h + y, (st_m, st_s)
    return body


def _mamba_prefill_body(cfg, rt: Runtime, mask):
    """Scan body over a Mamba2 sub-stack with explicit state carry, shared
    by ``prefill`` and ``prefill_chunk``."""
    def mbody(h, xs):
        lp, st = xs
        y, st = SSM.mamba_forward(lp, cfg, h, state=st,
                                  chunk=rt.gla_chunk, mask=mask)
        return h + y, st
    return mbody


def prefill(cfg, params, state, tokens, bt, *, positions=None,
            extra_embeds=None, frames=None, last_idx=None, valid_len=None,
            rt: Runtime = DEFAULT_RT):
    """Run the prompt through the model, writing KV pages / recurrent states.

    Returns (fp32 logits of the LAST position [B, V], new_state). Requests in
    the batch share the (padded) length S; for length-bucketed batched
    prefill, ``last_idx`` [B] picks each request's true last position for the
    logits and ``valid_len`` [B] masks pad-position pool writes (causal
    attention already keeps end-padding out of the real positions' math).
    """
    from repro.core.paged_kv import write_prefill
    B, S = tokens.shape
    x = L.embed(params["embed"], tokens)
    if extra_embeds is not None:
        x = x + extra_embeds.astype(x.dtype)
    if cfg.rope_kind == "none" and cfg.family == "encdec":
        pe = jnp.asarray(L.sinusoidal_positions(S, cfg.d_model))
        x = x + pe[None].astype(x.dtype)
    x = rt.constrain(x, "act")
    if positions is None:
        positions = default_positions(cfg, B, S)
    cs = _cos_sin(cfg, positions)
    kinds = cfg.block_kinds()
    state = dict(state)
    aux_unused = jnp.float32(0)
    # recurrent carries are dense per-row state: length-bucketed batches
    # must stop each row's state at its true last token (attention needs no
    # mask — pad writes drop and causality isolates real positions)
    mask = None
    if valid_len is not None:
        mask = (jnp.arange(S)[None, :]
                < jnp.asarray(valid_len, jnp.int32)[:, None])

    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(cfg, params, frames, rt)
        ck, cv = make_cross_kv(cfg, params, enc_out)
        state["cross_k"], state["cross_v"] = ck, cv

    def attn_prefill_block(lp, h, w, pkl, pvl, cross=None):
        hn = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = L.qkv_project(lp["attn"], cfg, hn)
        if cs is not None:
            q = L.apply_rope(q, *cs)
            k = L.apply_rope(k, *cs)
        if rt.write_pool is not None:
            pkl, pvl = rt.write_pool(pkl, pvl, k, v, bt)
        elif rt.ring_width:
            # ring pools recycle slots: tokens older than the ring are
            # overwritten before they could ever be read — write only the
            # final window (7x less scatter volume for mixtral prefill_32k;
            # EXPERIMENTS.md §Perf P4)
            page = pkl.shape[1]
            span = min(rt.ring_width * page, S)
            pkl, pvl = write_prefill(pkl, pvl, k[:, S - span:],
                                     v[:, S - span:], bt,
                                     ctx_start=S - span,
                                     ring_width=rt.ring_width)
        else:
            pkl, pvl = write_prefill(pkl, pvl, k, v, bt, valid_len=valid_len)
        kf = rt.constrain(k, "kv_full")
        vf = rt.constrain(v, "kv_full")
        a = L.flash_attention(q, kf, vf, causal=True, window=w)
        h = h + L.dense(a.reshape(B, S, cfg.q_dim), lp["attn"]["wo"])
        return _prefill_block_tail(lp, cfg, h, cross, rt), pkl, pvl

    if cfg.family == "encdec" or all(k in ("attn", "local") for k in kinds):
        windows = jnp.asarray(_window_array(cfg))
        pool = state["pool"]
        stack = params["dec"] if cfg.family == "encdec" else params["layers"]
        has_cross = cfg.family == "encdec"

        def body(carry, xs):
            h, pk, pv = carry
            if has_cross:
                i, lp, w, ckl, cvl = xs
                cross = (ckl, cvl)
            else:
                i, lp, w = xs
                cross = None
            pkl = jax.lax.dynamic_index_in_dim(pk, i, 0, keepdims=False)
            pvl = jax.lax.dynamic_index_in_dim(pv, i, 0, keepdims=False)
            h, pkl, pvl = attn_prefill_block(lp, h, w, pkl, pvl, cross)
            pk = jax.lax.dynamic_update_index_in_dim(pk, pkl, i, 0)
            pv = jax.lax.dynamic_update_index_in_dim(pv, pvl, i, 0)
            return (h, pk, pv), None

        body = jax.checkpoint(body) if rt.remat else body
        idx = jnp.arange(len(kinds))
        xs = ((idx, stack, windows, state["cross_k"], state["cross_v"])
              if has_cross else (idx, stack, windows))
        (x, pk, pv), _ = jax.lax.scan(body, (x, pool["k"], pool["v"]), xs)
        state["pool"] = {"k": pk, "v": pv}
    elif "mlstm" in params:
        body = _xlstm_prefill_body(cfg, rt, mask)
        body = jax.checkpoint(body) if rt.remat else body
        x, (new_m, new_s) = jax.lax.scan(
            body, x, (params["mlstm"], params["slstm"],
                      state["mlstm"], state["slstm"]))
        state["mlstm"], state["slstm"] = new_m, new_s
    else:                                               # zamba
        n_cyc = cfg.n_layers // len(cfg.pattern)
        per_cyc = sum(1 for k in cfg.pattern if k == "mamba")
        pool = state["pool"]
        pk, pv = pool["k"], pool["v"]
        new_mamba = []
        mbody = _mamba_prefill_body(cfg, rt, mask)
        mbody = jax.checkpoint(mbody) if rt.remat else mbody
        for c in range(n_cyc):
            sl = lambda a: a[c * per_cyc:(c + 1) * per_cyc]
            x, st_out = jax.lax.scan(
                mbody, x, (jax.tree.map(sl, params["mamba"]),
                           jax.tree.map(sl, state["mamba"])))
            new_mamba.append(st_out)
            x, pkl, pvl = attn_prefill_block(
                params["attn_shared"], x, 0, pk[c], pv[c])
            pk = pk.at[c].set(pkl)
            pv = pv.at[c].set(pvl)
        state["pool"] = {"k": pk, "v": pv}
        state["mamba"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, 0), *new_mamba)

    if last_idx is None:
        x = x[:, -1]
    else:
        x = x[jnp.arange(x.shape[0]), jnp.asarray(last_idx, jnp.int32)]
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = L.lm_head(x, w, transpose=cfg.tie_embeddings)
    return logits, state


def prefill_chunk(cfg, params, state, tokens, bt, ctx_start, *,
                  last_idx=None, valid_len=None, rt: Runtime = DEFAULT_RT):
    """Chunked prefill continuation — the DCS-style interleave primitive.

    Processes tokens [B, C] at global positions ctx_start..ctx_start+C-1
    against context already held by earlier chunks. Attention layers write
    the chunk's K/V via ``write_prefill(ctx_start=...)``, gather their
    pages, and attend with ``q_offset=ctx_start`` so the causal mask spans
    prior chunks; recurrent layers (Mamba2 / mLSTM / sLSTM) resume from the
    explicit per-row carry in ``state`` (the previous chunk's returned
    state — chunk-boundary handoff, exactly the ``chunked_gla`` state
    mechanism) and enc-dec decoder chunks attend over the carried
    ``cross_k``/``cross_v`` rows (computed once from the encoder at
    admission). ``ctx_start``/``last_idx``/``valid_len`` may be traced, so
    one jit serves every chunk position; ``ctx_start`` may also be a [B]
    vector — each request resumes at its own depth (prefix-cache suffix
    prefill / snapshot restore over a batch of different resume depths).
    ``valid_len`` masks end-padding out of pool writes AND recurrent
    carries, so pow2 length-bucketed groups stay exact.

    ``state`` carries whatever the family needs (``pool`` and/or the
    ``RSTATE_KEYS`` rows, batch axis = B). Returns (fp32 logits at last_idx
    (default C-1) [B, V], new_state).
    """
    from repro.core.paged_kv import gather_kv, write_prefill
    B, C = tokens.shape
    x = L.embed(params["embed"], tokens)
    start = jnp.asarray(ctx_start, jnp.int32)
    offset = start if start.ndim == 0 else start[:, None]
    if cfg.rope_kind == "none" and cfg.family == "encdec":
        pos = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32)[None] + offset,
                               (B, C))
        x = x + L.sinusoidal_at(pos, cfg.d_model).astype(x.dtype)
    x = rt.constrain(x, "act")
    positions = default_positions(cfg, B, C, offset=offset)
    cs = _cos_sin(cfg, positions)
    state = dict(state)
    mask = None
    if valid_len is not None:
        mask = (jnp.arange(C)[None, :]
                < jnp.asarray(valid_len, jnp.int32)[:, None])

    def chunk_attn_block(lp, h, w, pkl, pvl, cross=None):
        hn = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = L.qkv_project(lp["attn"], cfg, hn)
        if cs is not None:
            q = L.apply_rope(q, *cs)
            k = L.apply_rope(k, *cs)
        pkl, pvl = write_prefill(pkl, pvl, k, v, bt, ctx_start=start,
                                 valid_len=valid_len)
        kf, vf = gather_kv(pkl, pvl, bt)        # [B, maxp*page, KVH, D]
        a = L.flash_attention(q, kf, vf, causal=True, window=w,
                              q_offset=start)
        h = h + L.dense(a.reshape(B, C, cfg.q_dim), lp["attn"]["wo"])
        return _prefill_block_tail(lp, cfg, h, cross, rt), pkl, pvl

    if "layers" in params:
        windows = jnp.asarray(_window_array(cfg))
        pool = state["pool"]

        # pool layers stream through the scan as xs/ys (same HBM-traffic
        # argument as decode_step)
        def body(h, xs):
            lp, w, pkl, pvl = xs
            h, pkl, pvl = chunk_attn_block(lp, h, w, pkl, pvl)
            return h, (pkl, pvl)

        x, (pk, pv) = jax.lax.scan(
            body, x, (params["layers"], windows, pool["k"], pool["v"]))
        state["pool"] = {"k": pk, "v": pv}
    elif cfg.family == "encdec":
        pool = state["pool"]

        def body(h, xs):
            lp, pkl, pvl, ck, cv = xs
            h, pkl, pvl = chunk_attn_block(lp, h, 0, pkl, pvl,
                                           cross=(ck, cv))
            return h, (pkl, pvl)

        x, (pk, pv) = jax.lax.scan(
            body, x, (params["dec"], pool["k"], pool["v"],
                      state["cross_k"], state["cross_v"]))
        state["pool"] = {"k": pk, "v": pv}
    elif "mlstm" in params:                             # xlstm
        x, (new_m, new_s) = jax.lax.scan(
            _xlstm_prefill_body(cfg, rt, mask), x,
            (params["mlstm"], params["slstm"],
             state["mlstm"], state["slstm"]))
        state["mlstm"], state["slstm"] = new_m, new_s
    else:                                               # zamba hybrid
        n_cyc = cfg.n_layers // len(cfg.pattern)
        per_cyc = sum(1 for k in cfg.pattern if k == "mamba")
        pool = state["pool"]
        pk, pv = pool["k"], pool["v"]
        new_mamba = []
        mbody = _mamba_prefill_body(cfg, rt, mask)
        for c in range(n_cyc):
            sl = lambda a: a[c * per_cyc:(c + 1) * per_cyc]
            x, st_out = jax.lax.scan(
                mbody, x, (jax.tree.map(sl, params["mamba"]),
                           jax.tree.map(sl, state["mamba"])))
            new_mamba.append(st_out)
            x, pkl, pvl = chunk_attn_block(
                params["attn_shared"], x, 0, pk[c], pv[c])
            pk = pk.at[c].set(pkl)
            pv = pv.at[c].set(pvl)
        state["pool"] = {"k": pk, "v": pv}
        state["mamba"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, 0), *new_mamba)

    if last_idx is None:
        x = x[:, -1]
    else:
        x = x[jnp.arange(B), jnp.asarray(last_idx, jnp.int32)]
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = L.lm_head(x, w, transpose=cfg.tie_embeddings)
    return logits, state
