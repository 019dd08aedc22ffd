"""Mixture-of-Experts: top-k routing with capacity, virtual-expert layout.

Weights are stored as **virtual experts**: ``V = n_virtual`` slices where
each real expert's d_ff is split across ``v = V / E`` consecutive virtual
experts. With ``V = tp`` this maps any expert count onto the mesh:
mixtral's 8 experts on a 16-way model axis -> EP8 x TP2 (v=2), phi3.5's 16
experts -> pure EP16 (v=1); single-device tests use V = E (v=1).

Two execution paths share the routing math:

* ``moe_local`` — everything on one shard (reference / tests / smoke).
* ``moe_ep``    — for use inside ``shard_map``: each shard owns exactly one
  virtual expert; tokens travel by ``lax.all_to_all`` over the model axis and
  the v partial outputs per chosen expert sum at combine (d_ff row-split).

Router math is fp32. Capacity per real expert follows GShard:
``C = ceil(T * top_k * capacity_factor / E)``; overflow tokens keep only the
residual path.

A third, ``moe_held``, is one chip's share of an expert-parallel layer
(``cfg.moe_held``): it holds a run of whole experts, routes over all of
them and computes its own experts' part of each token's output, with no
capacity, so no token is dropped and a token's output does not depend on
the others in its batch.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import activate, dense, init_dense

F32 = jnp.float32


def init_moe(key, cfg, dtype, n_virtual: int | None = None):
    E, D, ff = cfg.held_experts, cfg.d_model, cfg.d_ff
    V = n_virtual or E
    v = V // E
    assert v * E == V and ff % max(v, 1) == 0, (E, V, ff)
    ffv = ff // v
    ks = jax.random.split(key, 4)
    p = {"router": init_dense(ks[0], D, cfg.n_experts, dtype, scale=0.02),
         "w1": jax.vmap(lambda k: init_dense(k, D, ffv, dtype))(
             jax.random.split(ks[1], V)),
         "w2": jax.vmap(lambda k: init_dense(k, ffv, D, dtype,
                        scale=1.0 / math.sqrt(ff * 2 * cfg.n_layers)))(
             jax.random.split(ks[2], V))}
    if cfg.act in ("swiglu", "geglu"):
        p["w3"] = jax.vmap(lambda k: init_dense(k, D, ffv, dtype))(
            jax.random.split(ks[3], V))
    return p


def route(router_w, cfg, x):
    """x [T,D] -> (probs [T,K], experts [T,K], aux_loss scalar)."""
    logits = dense(x, router_w).astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.moe_top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    E = cfg.n_experts
    me = probs.mean(0)
    ce = jax.nn.one_hot(top_e[:, 0], E, dtype=F32).mean(0)
    aux = E * jnp.sum(me * ce)
    return top_p, top_e, aux


def capacity(cfg, T: int) -> int:
    return max(1, math.ceil(T * cfg.moe_top_k * cfg.capacity_factor
                            / cfg.n_experts))


def _dispatch_indices(top_e, E: int, C: int):
    """Flat (T*K) choices -> slot in the [E, C] buffers + keep mask."""
    flat_e = top_e.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
    slot = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    return slot, slot < C


def dispatch(x, top_e, slot, keep, E: int, C: int):
    """x [T,D] -> per-real-expert buffers [E, C, D]."""
    K = top_e.shape[1]
    flat_e = top_e.reshape(-1)
    xs = jnp.repeat(x, K, axis=0)
    buf = jnp.zeros((E, C, x.shape[-1]), x.dtype)
    return buf.at[flat_e, jnp.where(keep, slot, 0)].add(
        jnp.where(keep[:, None], xs, 0), mode="drop")


def combine(buf, top_p, top_e, slot, keep):
    """buf [E, C, D] (v-slices pre-summed) -> y [T, D]."""
    T, K = top_e.shape
    flat_e = top_e.reshape(-1)
    picked = buf[flat_e, jnp.where(keep, slot, 0)]
    w = (top_p.reshape(-1) * keep).astype(buf.dtype)
    return (picked * w[:, None]).reshape(T, K, -1).sum(1)


def _emm(spec, x, w):
    """Expert matmul; ``w`` may be an int8 QTensor (core/quant.py)."""
    if isinstance(w, dict):
        y = jnp.einsum(spec, x, w["q"].astype(x.dtype),
                       preferred_element_type=F32)
        return y * w["s"].astype(F32)          # s [E, 1, out] broadcasts
    return jnp.einsum(spec, x, w, preferred_element_type=F32)


def expert_mlp(p, cfg, buf):
    """buf [V, C, D] through each virtual expert's MLP slice (partial out)."""
    h = _emm("ecd,edf->ecf", buf, p["w1"])
    h = activate(h, cfg.act).astype(buf.dtype)
    if "w3" in p:
        h = h * _emm("ecd,edf->ecf", buf, p["w3"]).astype(buf.dtype)
    return _emm("ecf,efd->ecd", h, p["w2"]).astype(buf.dtype)


def moe_local(p, cfg, x):
    """x [B,S,D] -> (y, aux). Virtual-expert count inferred from weights."""
    B, S, D = x.shape
    E = cfg.n_experts
    w1 = p["w1"]["q"] if isinstance(p["w1"], dict) else p["w1"]
    V = w1.shape[0]
    v = V // E
    xt = x.reshape(-1, D)
    top_p, top_e, aux = route(p["router"], cfg, xt)
    C = capacity(cfg, xt.shape[0])
    slot, keep = _dispatch_indices(top_e, E, C)
    buf = dispatch(xt, top_e, slot, keep, E, C)           # [E, C, D]
    out = expert_mlp(p, cfg, jnp.repeat(buf, v, axis=0))  # [V, C, D] partials
    summed = out.reshape(E, v, C, D).sum(1)
    y = combine(summed, top_p, top_e, slot, keep)
    return y.reshape(B, S, D), aux


def moe_ep(p_local, cfg, x_loc, axis: str, n_shards: int):
    """Expert-parallel path for shard_map bodies.

    ``x_loc`` [T_loc, D] — this shard's tokens. ``p_local['w*']`` [1, D, ffv]
    — this shard's virtual expert (arrives pre-sliced via in_specs); router
    replicated. Requires n_virtual == n_shards. Returns (y_loc, aux_local).
    """
    E, D = cfg.n_experts, cfg.d_model
    v = n_shards // E
    T = x_loc.shape[0]
    top_p, top_e, aux = route(p_local["router"], cfg, x_loc)
    C = capacity(cfg, T)
    slot, keep = _dispatch_indices(top_e, E, C)
    buf = dispatch(x_loc, top_e, slot, keep, E, C)          # [E, C, D]
    send = jnp.repeat(buf, v, axis=0)                       # [V=n_shards, C, D]
    recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
    flat = recv.reshape(1, n_shards * C, D)                 # my virtual expert
    out = expert_mlp(p_local, cfg, flat)[0]
    back = jax.lax.all_to_all(out.reshape(n_shards, C, D), axis, 0, 0,
                              tiled=False)
    summed = back.reshape(E, v, C, D).sum(1)
    y = combine(summed, top_p, top_e, slot, keep)
    return y, aux


def moe_held(p, cfg, x):
    """x [B,S,D] -> (y, aux 0): the part of each token's output that the
    held experts ``[moe_held_first, moe_held_first + moe_held)`` give.

    The router's logits come out of the matmul in float32; each token keeps
    its top ``moe_top_k`` of all ``n_experts`` and renormalises their
    weights over them, as a layer that holds every expert would. Each held
    expert then runs over every token, weighted by the token's weight on it
    (0 where the token did not choose it): no capacity, so the output of a
    token is the same alone as in any batch. It reads each held expert's
    weights once a call, whichever tokens chose it."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    logits = jax.lax.dot_general(xt, p["router"], (((1,), (0,)), ((), ())),
                                 preferred_element_type=F32)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                 cfg.moe_top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    n = p["w1"].shape[0]
    held = cfg.moe_held_first + jnp.arange(n)
    gate = jnp.where(top_e == held[:, None, None], top_p,
                     0.0).sum(-1)                              # [held, T]
    # every held expert over every token: a matmul batched over the held
    # experts, so each expert's weights stream once as they are stored
    xe = jnp.broadcast_to(xt, (n,) + xt.shape)
    h = activate(_emm("etd,edf->etf", xe, p["w1"]), cfg.act)
    if "w3" in p:
        h = h * _emm("etd,edf->etf", xe, p["w3"])
    h = (h * gate[:, :, None]).astype(x.dtype)
    y = _emm("etf,efd->td", h, p["w2"]).astype(x.dtype)
    return y.reshape(B, S, D), jnp.float32(0)
