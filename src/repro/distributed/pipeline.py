"""Pipeline parallelism over the 'pod' axis for decode (paper §4.2).

The paper's scaling argument: TP-only multi-module PIM collapses (aspect
distortion), so LoL-PIM groups layers into PP stages and keeps TP moderate
inside each stage, with microbatches keeping the pipeline full. Here:

* stage s = pod s (layer stack reshaped [stages, L/stages, ...], the stage
  dim sharded over 'pod');
* one decode step runs a GPipe tick loop of M + stages - 1 ticks inside a
  shard_map that is MANUAL over 'pod' and AUTO over data/model — so each
  stage's inner compute keeps the Megatron-TP weight layout and the inner
  ITPP shard_map (which inherits the partial-manual context mesh);
* microbatch b enters stage 0 at tick b; activations hop stages via
  ``ppermute``; fill/drain ticks compute garbage whose pool writes are
  masked (new_page = -1 owns nowhere) — the paper's pipeline bubbles, visible
  in the roofline as idle fraction (m/(m+S-1));
* the last stage's logits psum over 'pod' (other stages contribute zeros).

Applicable to uniform attention stacks (dense / MoE / VLM archs, incl.
gemma3's windowed pattern); hybrid/enc-dec archs use pod=dp (DESIGN.md §6).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.itpp import ItppSpec, itpp_decode_attention_shard
from repro.models import layers as L
from repro.models import model as MDL
from repro.models import moe as MOE


def stack_stages(stacked, n_stages: int):
    """[L, ...] layer-stacked params -> [stages, L/stages, ...]."""
    def r(x):
        assert x.shape[0] % n_stages == 0, (x.shape, n_stages)
        return x.reshape(n_stages, x.shape[0] // n_stages, *x.shape[1:])
    return jax.tree.map(r, stacked)


def _inner_itpp(spec: ItppSpec, max_pages_per_req: int, ring_width: int,
                mesh_axis_sizes, mesh=None):
    """ITPP shard_map that inherits the partial-manual context mesh."""
    body = partial(itpp_decode_attention_shard, spec=spec,
                   mesh_axis_sizes=mesh_axis_sizes,
                   max_pages_per_req=max_pages_per_req, ring_width=ring_width)
    b = spec.batch_axis
    pool_spec = P(spec.page_axes, None, None, None)
    in_specs = (P(b, None, None), P(b, None, None), P(b, None, None),
                pool_spec, pool_spec, P(b, None), P(b), P(b), P(b), P())
    out_specs = (P(b, None, None), pool_spec, pool_spec)
    axes = set(spec.page_axes)
    if b is not None:
        axes |= set(b) if isinstance(b, tuple) else {b}
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False, axis_names=axes)


def _inner_moe(cfg, tp_axis: str, tp_n: int, batch_axis, mesh=None):
    def body(pw, x_loc):
        Bl, S, D = x_loc.shape
        y, aux = MOE.moe_ep(pw, cfg, x_loc.reshape(-1, D), tp_axis, tp_n)
        return y.reshape(Bl, S, D), aux

    pspec = {"router": P(None, None), "w1": P(tp_axis, None, None),
             "w2": P(tp_axis, None, None)}
    xspec = P(batch_axis, None, None)

    def apply(p, cfg_, x):
        ps = dict(pspec)
        if "w3" in p:
            ps["w3"] = P(tp_axis, None, None)
        fn = shard_map(
            body, mesh=mesh, in_specs=(ps, xspec), out_specs=(xspec, P()),
            check_vma=False,
            axis_names={tp_axis} | ({batch_axis} if batch_axis else set()))
        y, aux = fn({k: p[k] for k in ps}, x)
        return y, aux

    return apply


def make_pp_decode_step(cfg, plan, parallel, pool_spec, *, n_stages: int,
                        microbatches: int):
    """Returns (step(params, state, batch) -> (logits, state), param/state
    transforms). Params must be passed through ``stage_params(params)``."""
    mesh = plan.mesh
    sizes = dict(mesh.shape)
    ispec = plan.itpp_spec(parallel.page_size)
    # inside the manual-pod region the inner axes see the same sizes
    inner_sizes = {k: v for k, v in sizes.items() if k != "pod"}
    inner_mesh = None if hasattr(jax, "shard_map") else mesh
    itpp_fn = _inner_itpp(ispec, pool_spec.max_pages_per_req,
                          pool_spec.max_pages_per_req if pool_spec.ring else 0,
                          inner_sizes, mesh=inner_mesh)
    moe_fn = _inner_moe(cfg, plan.tp_axis, plan.tp, ispec.batch_axis,
                        mesh=inner_mesh) if cfg.is_moe else None
    rt = MDL.Runtime(itpp=itpp_fn, moe=moe_fn,
                     ring_width=pool_spec.max_pages_per_req
                     if pool_spec.ring else 0)
    windows = np.asarray(MDL._window_array(cfg)).reshape(
        n_stages, cfg.n_layers // n_stages)

    def body(stage_p, embed_w, head_w, final_norm, pool_k, pool_v,
             tokens, bt, ctx, npage, noff):
        """Manual over 'pod': stage_p has leading [1, L/stages, ...]."""
        s = jax.lax.axis_index("pod")
        sp = jax.tree.map(lambda x: x[0], stage_p)
        B = tokens.shape[0]
        mb = B // microbatches
        D = cfg.d_model
        n_ticks = microbatches + n_stages - 1
        w_stage = jnp.asarray(windows)[s]                     # [L/stages]

        def mb_slice(x, i):
            return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)

        def tick(carry, t):
            reg, pk, pv, out = carry
            # my stage processes microbatch (t - s) this tick
            my_mb = t - s
            active = (my_mb >= 0) & (my_mb < microbatches)
            i = jnp.clip(my_mb, 0, microbatches - 1)
            tok_i = mb_slice(tokens, i)
            ctx_i = mb_slice(ctx, i)
            bt_i = mb_slice(bt, i)
            npage_i = jnp.where(active, mb_slice(npage, i), -1)  # mask writes
            noff_i = mb_slice(noff, i)
            x0 = L.embed(embed_w, tok_i)
            x = jnp.where(s == 0, x0, reg)
            pos = (ctx_i - 1).astype(jnp.int32)[:, None]
            if cfg.rope_kind == "mrope":
                pos = jnp.broadcast_to(pos[None], (3, mb, 1))
            cs = MDL._cos_sin(cfg, pos)

            def layer(carry2, xs):
                h, pk_, pv_ = carry2
                li, lp, w = xs
                # pool layer dim is stage-local (sharded over 'pod')
                pkl = jax.lax.dynamic_index_in_dim(pk_, li, 0, keepdims=False)
                pvl = jax.lax.dynamic_index_in_dim(pv_, li, 0, keepdims=False)
                h, pkl, pvl = MDL._attn_block_decode(
                    lp, cfg, h, cs, w, pkl, pvl, bt_i, ctx_i, npage_i,
                    noff_i, rt)
                pk_ = jax.lax.dynamic_update_index_in_dim(pk_, pkl, li, 0)
                pv_ = jax.lax.dynamic_update_index_in_dim(pv_, pvl, li, 0)
                return (h, pk_, pv_), None

            li = jnp.arange(cfg.n_layers // n_stages)
            (x, pk, pv), _ = jax.lax.scan(layer, (x, pk, pv),
                                          (li, sp, w_stage))
            # last stage: head + write logits for my_mb
            hfin = L.rms_norm(x, final_norm, cfg.norm_eps)
            w_ = embed_w if cfg.tie_embeddings else head_w
            lg = L.lm_head(hfin, w_, transpose=cfg.tie_embeddings)
            is_last = s == n_stages - 1
            valid_out = active & is_last
            upd = jnp.where(valid_out, lg, mb_slice(out, i))
            out = jax.lax.dynamic_update_slice_in_dim(out, upd, i * mb, 0)
            # hop to next stage
            perm = [(k, k + 1) for k in range(n_stages - 1)]
            reg_next = jax.lax.ppermute(x, "pod", perm)
            return (reg_next, pk, pv, out), None

        # pool arrives stage-local: [L/stages, pages, ...] (P('pod') on dim0)
        reg0 = jnp.zeros((mb, D), embed_w.dtype)
        out0 = jnp.zeros((B, cfg.padded_vocab), jnp.float32)
        (reg, pk, pv, out), _ = jax.lax.scan(
            tick, (reg0, pool_k, pool_v, out0), jnp.arange(n_ticks))
        # logits live on the last stage only; share across pods
        out = jax.lax.psum(jnp.where(s == n_stages - 1, out, 0.0), "pod")
        return out, pk, pv

    # manual only over 'pod'; data/model stay auto (the Megatron-TP weight
    # layout and ITPP page sharding flow through). The pool's layer dim is
    # stage-sharded over 'pod' — each pod holds only its stage's KV.
    in_specs = (P("pod"), P(), P(), P(), P("pod"), P("pod"),
                P(), P(), P(), P(), P())
    out_specs = (P(), P("pod"), P("pod"))
    shmap = shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False,
                      axis_names={"pod"})

    def step(params, state, batch):
        sp = stack_stages(params["layers"], n_stages)
        head = params.get("head", params["embed"])
        pool = state["pool"]
        logits, pk, pv = shmap(sp, params["embed"], head,
                               params["final_norm"], pool["k"], pool["v"],
                               batch["tokens"], batch["bt"], batch["ctx"],
                               batch["npage"], batch["noff"])
        return logits, {**state, "pool": {"k": pk, "v": pv}}

    return step
