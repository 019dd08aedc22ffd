"""ITPP — intra-module token-parallel partitioning, on a TPU mesh.

The paper's §4.3: shard the K/V-cache over the *token* dimension (not heads,
not batch), compute partial attention per shard, and aggregate the softmax
*inside the module* with a numerically stable merge. Head count and batch
size never constrain parallelism — the fix for HFA's channel imbalance.

Here a "PIM module" is a mesh shard. The paged pool's page axis is sharded
over ``page_axes`` (usually ``('data','model')``); each shard

 1. writes the incoming token's K/V if it owns the target page,
 2. translates the global Va2Pa block table to its local pages (compaction),
 3. gathers its pages and computes masked partial attention (o, l, m),
 4. merges partials across ``merge_axes`` in log-sum-exp form
    (``merge_partials`` — the EPU aggregation).

Requests either stripe pages across a data-row's model shards (decode_32k:
batch also sharded over 'data', merge over 'model' only) or across the whole
pod (long_500k: batch=1 replicated, merge over both axes) — the allocator's
``row_affine`` / ``striped`` policies (core/allocator.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.paged_kv import merge_partials, partial_decode_attention
from repro.kernels.backend import KernelConfig


@dataclass(frozen=True)
class ItppSpec:
    page_axes: tuple[str, ...]      # mesh axes sharding the pool's page dim
    merge_axes: tuple[str, ...]     # axes to merge partials over
    batch_axis: str | None          # axis sharding the request batch (or None)
    n_page_shards: int              # product of page_axes sizes
    stripe: int                     # shards each request stripes over
    page_size: int

    def max_local_pages(self, max_pages_per_req: int) -> int:
        return -(-max_pages_per_req // self.stripe) + 1


def _my_page_shard(spec: ItppSpec, mesh_axis_sizes: dict[str, int]):
    """Linear shard index over the page axes (row-major over page_axes)."""
    idx = jnp.int32(0)
    for ax in spec.page_axes:
        idx = idx * mesh_axis_sizes[ax] + jax.lax.axis_index(ax)
    return idx


def itpp_decode_attention_shard(q, k_new, v_new, pool_k, pool_v, block_table,
                                ctx_len, new_page, new_off, window=0, *,
                                spec: ItppSpec,
                                mesh_axis_sizes: dict[str, int],
                                max_pages_per_req: int,
                                ring_width: int = 0,
                                cond_window: int = 0,
                                kernels: KernelConfig | None = None,
                                layer=None):
    """shard_map body (or single-device when spec.page_axes == ()).

    q [B,H,D]; k_new/v_new [B,KVH,D]; pool_{k,v} [P_loc, page, KVH, D];
    block_table [B, maxp] (GLOBAL page ids, -1 pad); ctx_len [B] incl. the
    current token; new_page/new_off [B] global write target; ``window`` may
    be a traced scalar (0 = full attention).

    ``cond_window``: for mixed local:global stacks (gemma3, mellum2), the
    per-layer traced ``window`` selects between two widths via lax.cond —
    windowed layers touch only the pages overlapping the (static-size)
    window instead of the full context (EXPERIMENTS.md §Perf H3). With the
    Pallas kernel the windowed branch's call is ``paged_attention_window``
    and the full branch's ``paged_attention``.

    ``kernels``: when it resolves to ``use_pallas``, the shard-local compute
    is ``kernels.paged_attention.paged_attention_partials`` — K/V pages
    stream straight from the pool with dead pages (unowned / beyond ctx /
    out of window / unwritten ring slots) skipped in-kernel, so neither the
    [B, mp, page, KVH, D] gathered copy nor its HBM traffic exists. The
    incoming token's K/V scatter rides the same dispatch (the kernel reads
    the post-write pool). ``None`` (and off-TPU autodetect) keeps the
    gather-then-dense reference math below — identical semantics, tested.

    ``layer``: with a (traced) layer index the pools are the whole stacked
    ``[L, P_loc, page, KVH, D]`` pair, addressed in place: they are viewed
    as ``[L*P_loc, ...]`` (a leading-dims reshape, a bitcast), and the
    token's write target and the table's live entries shift by
    ``layer*P_loc``, so no layer's pool is sliced out or written back. The
    drop sentinel then is ``L*P_loc``, past the last layer.
    Returns (out [B,H,D], pool_k, pool_v), the pools in their given shape.
    """
    B, maxp = block_table.shape
    pool_shape = pool_k.shape
    if layer is None:
        P_loc, base = pool_k.shape[0], 0
    else:
        P_loc, base = pool_k.shape[1], layer * pool_k.shape[1]
        pool_k = pool_k.reshape((-1,) + pool_k.shape[2:])
        pool_v = pool_v.reshape((-1,) + pool_v.shape[2:])
    page = pool_k.shape[1]
    sharded = bool(spec.page_axes)
    my = _my_page_shard(spec, mesh_axis_sizes) if sharded else jnp.int32(0)

    # ---- 1. write the incoming token where owned --------------------------
    with jax.named_scope("kv_write"):
        owned_w = (new_page // P_loc) == my
        loc_w = jnp.where(owned_w, new_page - my * P_loc + base,
                          pool_k.shape[0])                     # OOB: drop
        pool_k = pool_k.at[loc_w, new_off].set(
            k_new.astype(pool_k.dtype), mode="drop")
        pool_v = pool_v.at[loc_w, new_off].set(
            v_new.astype(pool_v.dtype), mode="drop")

    owned = (block_table >= 0) & ((block_table // P_loc) == my)  # [B,maxp]
    vpage = jnp.broadcast_to(jnp.arange(maxp, dtype=jnp.int32)[None], (B, maxp))
    w = jnp.asarray(window, jnp.int32)

    kc = kernels.resolve() if kernels is not None else None
    if kc is not None and kc.use_pallas:
        from repro.kernels.paged_attention import paged_attention_partials
        from repro.kernels.ref import combine_partials
        H = q.shape[1]
        KVH = pool_k.shape[2]
        bt_loc = jnp.where(owned, block_table - my * P_loc + base, -1)

        def kernel_partial(mp_width: int, window_only: bool):
            if window_only:
                # windowed gather bound (cond_window trick): pass only the
                # table slots overlapping the window; slot j resolves to
                # virtual page lo+j in-kernel with the SAME lo formula
                lo = jnp.maximum(ctx_len - w, 0) // page          # [B]
                sel = lo[:, None] + jnp.arange(mp_width,
                                               dtype=jnp.int32)[None]
                btk = jnp.take_along_axis(bt_loc,
                                          jnp.clip(sel, 0, maxp - 1), axis=1)
                btk = jnp.where(sel < maxp, btk, -1)
            else:
                btk = bt_loc
            o4, l4, m4 = paged_attention_partials(
                q.reshape(B, KVH, H // KVH, -1), pool_k, pool_v, btk,
                ctx_len, window=w, ring_width=ring_width,
                windowed_slice=window_only, n_splits=kc.n_splits,
                interpret=kc.interpret)
            o4, l4, m4 = combine_partials(o4, l4, m4)
            return (o4.reshape(B, H, -1), l4.reshape(B, H),
                    m4.reshape(B, H))

        if cond_window > 0:
            win_pages = min(cond_window // page + 2, maxp)
            o, l, m = jax.lax.cond(
                w > 0,
                lambda: kernel_partial(win_pages, True),
                lambda: kernel_partial(maxp, False))
        else:
            o, l, m = kernel_partial(maxp, False)
    else:
        def gather_partial(mp_width: int, window_only: bool):
            """Va2Pa compaction -> gather -> masked partials, static width."""
            # ---- 2. compaction: prioritize owned (and in-window) pages ----
            pri = owned
            if window_only:
                lo_page = jnp.maximum(ctx_len[:, None] - w, 0) // page
                pri = owned & (vpage >= lo_page)
            order = jnp.argsort(jnp.where(pri, vpage, maxp + vpage), axis=1,
                                stable=True)
            sel = order[:, :mp_width]
            bt_loc = jnp.take_along_axis(block_table, sel, axis=1) \
                - my * P_loc + base
            vp_loc = jnp.take_along_axis(vpage, sel, axis=1)
            ok_loc = jnp.take_along_axis(pri, sel, axis=1)       # [B,mp]
            bt_safe = jnp.where(ok_loc, bt_loc, 0)

            # ---- 3. gather + masked partial attention --------------------
            k_pages = pool_k[bt_safe]         # [B, mp, page, KVH, D]
            v_pages = pool_v[bt_safe]
            if ring_width:
                cur_vp = ((ctx_len - 1) // page)[:, None]
                abs_vp = cur_vp - ((cur_vp - vp_loc) % ring_width)
                ok_loc2 = ok_loc & (abs_vp >= 0)
                vp_eff = abs_vp
            else:
                ok_loc2, vp_eff = ok_loc, vp_loc
            tok = vp_eff[:, :, None] * page + jnp.arange(page)[None, None, :]
            valid = ok_loc2[:, :, None] & (tok < ctx_len[:, None, None])
            valid = valid & ((w <= 0)
                             | (tok >= (ctx_len[:, None, None] - w)))
            return partial_decode_attention(q, k_pages, v_pages, valid)

        mp_full = min(spec.max_local_pages(max_pages_per_req), maxp)
        if cond_window > 0:
            win_pages = cond_window // page + 2      # pages spanning a window
            mp_win = min(-(-win_pages // spec.stripe) + 1, maxp)
            o, l, m = jax.lax.cond(
                w > 0,
                lambda: gather_partial(mp_win, True),
                lambda: gather_partial(mp_full, False))
        else:
            o, l, m = gather_partial(mp_full, False)

    # ---- 4. stable merge (EPU aggregation) -------------------------------
    if sharded and spec.merge_axes:
        out = merge_partials(o, l, m, axis=spec.merge_axes)
    else:
        out = o / jnp.maximum(l, 1e-30)[..., None]
    return (out.astype(q.dtype), pool_k.reshape(pool_shape),
            pool_v.reshape(pool_shape))


def make_prefill_writer(mesh, spec: ItppSpec, *, seq_axis: str):
    """Shard-LOCAL prefill pool writes (§Perf P1).

    With the allocator's ``blocked_chunk`` policy, virtual page v of a
    request lives on the shard owning sequence block v — exactly the shard
    that computed those tokens' K/V under sequence-parallel prefill. The
    scatter then never crosses shards: without this, XLA all-gathers the
    full K/V of every layer to every device (measured 992 GiB/device/step in
    fp32 for gemma3-27b prefill_32k).

    Returns f(pool_k_l, pool_v_l, k, v, bt) -> (pool_k_l, pool_v_l) where
    k/v are [B, S, KVH, D] sequence-sharded over ``seq_axis``.
    """
    sizes = dict(mesh.shape) if mesh is not None else {}
    n_seq = sizes.get(seq_axis, 1)

    def body(pool_k, pool_v, k, v, bt):
        B, S_loc = k.shape[0], k.shape[1]
        P_loc, page = pool_k.shape[0], pool_k.shape[1]
        my = _my_page_shard(spec, sizes) if spec.page_axes else jnp.int32(0)
        seq_i = jax.lax.axis_index(seq_axis) if spec.page_axes else 0
        t = seq_i * S_loc + jnp.arange(S_loc)
        vpage = t // page
        off = t % page
        pids = jnp.take_along_axis(
            bt, jnp.broadcast_to(vpage[None], (B, S_loc)), axis=1)
        owned = (pids >= 0) & ((pids // P_loc) == my)
        loc = jnp.where(owned, pids - my * P_loc, P_loc)        # OOB -> drop
        offs = jnp.broadcast_to(off[None], (B, S_loc))
        pool_k = pool_k.at[loc, offs].set(k.astype(pool_k.dtype), mode="drop")
        pool_v = pool_v.at[loc, offs].set(v.astype(pool_v.dtype), mode="drop")
        return pool_k, pool_v

    if mesh is None or not spec.page_axes:
        return body
    b = spec.batch_axis
    pool_spec = P(spec.page_axes, None, None, None)
    kv = P(b, seq_axis, None, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(pool_spec, pool_spec, kv, kv, P(b, None)),
        out_specs=(pool_spec, pool_spec), check_vma=False)


def make_itpp_attention(mesh, spec: ItppSpec, *, max_pages_per_req: int,
                        ring_width: int = 0, cond_window: int = 0,
                        kernels: KernelConfig | None = None):
    """Build the jit-composable sharded attention op.

    Returns f(q, k_new, v_new, pool_k, pool_v, bt, ctx, new_page, new_off,
    window, layer=None) -> (out, pool_k, pool_v), wrapped in shard_map over
    the mesh (or plain when mesh is None — single-device tests). ``window``
    and ``layer`` may be traced; with ``layer`` the pools are the stacked
    ``[L, P, ...]`` pair (see ``itpp_decode_attention_shard``), whose
    layer axis no mesh axis shards. ``kernels`` picks the shard-local
    compute.
    """
    sizes = dict(mesh.shape) if mesh is not None else {}
    body = partial(itpp_decode_attention_shard, spec=spec,
                   mesh_axis_sizes=sizes, max_pages_per_req=max_pages_per_req,
                   ring_width=ring_width, cond_window=cond_window,
                   kernels=kernels)
    if mesh is None or not spec.page_axes:
        return body

    b = spec.batch_axis
    qspec = P(b, None, None)
    bspec = P(b, None)
    cspec = P(b)

    def over_mesh(pool_spec, f, *extra):
        in_specs = (qspec, qspec, qspec, pool_spec, pool_spec, bspec, cspec,
                    cspec, cspec, P(), *extra)
        return shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=(qspec, pool_spec, pool_spec),
                         check_vma=False)

    per_layer = over_mesh(P(spec.page_axes, None, None, None), body)
    stacked = over_mesh(P(None, spec.page_axes, None, None, None),
                        lambda *a: body(*a[:-1], layer=a[-1]), P())

    def attend(*args, layer=None):
        return per_layer(*args) if layer is None else stacked(*args, layer)
    return attend
