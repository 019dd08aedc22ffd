"""Pallas TPU kernel: decode attention over a paged KV pool.

The DPA Va2Pa indirection in kernel form: block tables ride in as
scalar-prefetch operands so each grid step's ``BlockSpec`` index_map resolves
the *physical* page to stream HBM->VMEM — command-stream-free dynamic paging,
exactly the paper's Dyn-Modi operand rewriting (§5.2) mapped onto Pallas.

Two entry points:

* ``paged_attention_partials`` — the decode hot path's shard-local compute
  (``core/itpp.py``). Grid ``(n_splits, B, slots_per_split)``; each grid
  step streams one whole ``[page, KVH, D]`` pool page (every KV head, so
  each block spans the pool's trailing two dims as the TPU lowering
  requires) and scores all heads in one masked block-diagonal MXU pass.
  Each split emits an UNNORMALIZED ``(o, l, m)`` partial, exactly the
  shape the paper's §4.3 EPU aggregation merges across token partitions —
  so one kernel serves both the cross-shard ITPP merge and
  flash-decoding-style split-K parallelism on a single chip. The split-K
  axis LEADS the grid and is declared ``parallel`` in the Mosaic
  ``dimension_semantics`` (parallel axes must prefix the arbitrary ones),
  so megacore partitioning fans the splits out across TensorCores instead
  of running them sequentially on one — each (split, batch) owns its own
  scratch accumulation over the trailing ``arbitrary`` slot axis, so the
  partition is race-free and numerically identical. Nothing is gathered:
  K/V pages stream straight out of the pool (the multi-step grid
  double-buffers the page stream — the paper's ping-pong I/O, §6),
  replacing the gather-then-dense path's [B, maxp, page, KVH, D] HBM
  materialization.
* ``paged_attention`` — convenience full attention (partials merged and
  normalized), the single-shard kernel used by ``kernels/ops.py``.

Context-adaptive: a table slot whose page holds no live token for this
request — ``-1`` padding / unowned under ITPP, beyond ``ctx_len``, fully
below a sliding window, or an unwritten ring slot — is skipped with a
``pl.when`` early-out, so per-step work tracks the LIVE context rather than
the block-table width (the bandwidth fix LoL-PIM/PAM attribute to
context-aware KV streaming). The engine buckets the table width itself
(serving/engine.py) so even the grid tracks live pages.

Feature matrix (mirrors the gather-then-dense reference semantics):
  * ``window``       traced per-layer sliding window ([B] or scalar; 0=off),
  * ``ring_width``   sliding-window ring pools — table slots recycle
                     ``mod ring_width``, slot -> virtual page resolved
                     in-kernel from ``ctx_len``,
  * ``windowed_slice`` the cond_window trick: the caller passes only the
                     table slots overlapping the window; slot ``j`` maps to
                     virtual page ``max(ctx-w,0)//page + j``; this call is
                     named ``paged_attention_window``,
  * GQA ``G>=1``     q tile is [KVH*G, D]; a row scores only its own
                     head's columns; K/V never repeated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _partials_kernel(bt_ref, ctx_ref, w_ref,         # scalar prefetch
                     q_ref, k_ref, v_ref,            # VMEM tiles
                     o_ref, l_ref, m_ref,            # per-split partials
                     m_s, l_s, acc_s,                # scratch
                     *, page: int, slots_per_split: int, ring_width: int,
                     windowed_slice: bool, qpos: int = 1):
    s = pl.program_id(0)
    b = pl.program_id(1)
    j = pl.program_id(2)
    slot = s * slots_per_split + j

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    ctx = ctx_ref[b]
    w = w_ref[b]
    # slot -> virtual page (token positions), per pool policy
    if ring_width:
        cur_vp = (ctx - 1) // page
        vp = cur_vp - ((cur_vp - slot) % ring_width)   # < 0: never written
    elif windowed_slice:
        vp = jnp.maximum(ctx - w, 0) // page + slot
    else:
        vp = slot
    lo_tok = jnp.where(w > 0, ctx - w, 0)
    pid = bt_ref[b, slot]
    # context-adaptive early-out: dead pages cost neither FLOPs nor scratch.
    # qpos > 1 (multi-query verify): the deepest query row sees qpos-1 extra
    # tokens, so the liveness bound widens by that much — per-row masking
    # below keeps shallower rows exact.
    live = ((pid >= 0) & (vp >= 0) & (vp * page < ctx + qpos - 1)
            & ((vp + 1) * page > lo_tok))

    @pl.when(live)
    def _compute():
        # every KV head in one MXU pass: the [page, KVH, D] block flattens
        # to [page*KVH, D] (column c = token c // KVH of head c % KVH) and
        # the score matrix is masked block-diagonal, so K/V stream straight
        # from the [P, page, KVH, D] pool with no per-head slicing. The
        # flatten goes through f32, whose (8, 128) tiling it keeps; the
        # matmuls take the pool dtype, rounding p to it as the jnp
        # reference does, and accumulate in f32.
        kvh, d = k_ref.shape[2], k_ref.shape[3]
        kv_dt = k_ref.dtype
        q = q_ref[0].astype(kv_dt)                         # [KVH*R, D]
        k = k_ref[0].astype(jnp.float32).reshape(page * kvh, d).astype(kv_dt)
        v = v_ref[0].astype(jnp.float32).reshape(page * kvh, d).astype(kv_dt)
        rows = q.shape[0]
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc = sc / jnp.sqrt(jnp.float32(d))                 # [KVH*R, page*KVH]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, page * kvh), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        tok = vp * page + col // kvh
        # row r of the q tile is head r // R at query position
        # ctx-1 + (r % qpos): its effective context is ctx + r%qpos and its
        # window slides with it
        hi = ctx + row % qpos                              # [rows, 1]
        lo = jnp.where(w > 0, hi - w, 0)
        ok = ((row // (rows // kvh) == col % kvh)
              & (tok < hi) & (tok >= lo))
        sc = jnp.where(ok, sc, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + p.sum(axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p.astype(kv_dt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(j == slots_per_split - 1)
    def _emit():
        o_ref[0, 0] = acc_s[...]
        l_ref[0, 0] = l_s[...]
        m_ref[0, 0] = m_s[...]


def paged_attention_partials(q, k_pages, v_pages, block_tables, ctx_lens, *,
                             window=None, ring_width: int = 0,
                             windowed_slice: bool = False, n_splits: int = 1,
                             qpos: int = 1, interpret: bool | None = None):
    """Split-K decode-attention partials over a paged pool.

    q [B, KVH, G, D]; k_pages/v_pages [P, page, KVH, D];
    block_tables [B, W] int32 — physical page per table slot, ``-1`` = dead
    (pad / unowned shard-locally / out of window); ctx_lens [B] int32 tokens
    INCLUDING the current one; ``window`` traced [B] or scalar (0 = full);
    ``ring_width``/``windowed_slice`` per the module docstring (mutually
    exclusive). ``qpos > 1`` is the speculative-verify multi-query mode: the
    q axis ``G`` is read as ``G_real * qpos`` consecutive query rows, row
    ``r`` attending at position ``ctx - 1 + r % qpos`` (ctx_lens still counts
    tokens INCLUDING the FIRST query row's token). Returns fp32 UNNORMALIZED
    partials (o [S, B, KVH, G, D], l [S, B, KVH, G], m [S, B, KVH, G]) for
    the stable EPU merge (``ref.combine_partials`` locally, ``pl``
    collectives across shards).
    """
    assert not (ring_width and windowed_slice)
    assert qpos == 1 or not (ring_width or windowed_slice), \
        "multi-query verify runs on plain paged tables only"
    assert not (windowed_slice and window is None), \
        "windowed_slice slot mapping is defined by the window bound"
    B, KVH, G, D = q.shape
    page = k_pages.shape[1]
    W = block_tables.shape[1]
    S = max(1, min(int(n_splits), W))
    K = -(-W // S)
    if S * K != W:                      # pad tail split with dead slots
        block_tables = jnp.pad(block_tables, ((0, 0), (0, S * K - W)),
                               constant_values=-1)
    bt = block_tables.astype(jnp.int32)
    w_arr = (jnp.zeros((B,), jnp.int32) if window is None else
             jnp.broadcast_to(jnp.asarray(window, jnp.int32).reshape(-1),
                              (B,)))
    R = KVH * G                         # q rows: every head's G query rows

    # split-K axis first and ``parallel``: Mosaic requires parallel axes to
    # prefix arbitrary ones, and megacore partitioning then spreads the
    # splits across cores. The trailing slot axis stays ``arbitrary``: it
    # revisits the same (s, b) scratch accumulator. Every block spans the
    # array's last two dims, which the TPU lowering requires of any block
    # not tiled by (8, 128); l/m carry a unit lane axis for the same reason.
    grid = (S, B, K)
    semantics = ("parallel", "arbitrary", "arbitrary")

    def q_map(s, b, j, bt_ref, ctx_ref, w_ref):
        return (b, 0, 0)

    def kv_map(s, b, j, bt_ref, ctx_ref, w_ref):
        # dead slots clamp to page 0: the fetch is pipelined away when the
        # index repeats, and pl.when skips their compute either way
        return (jnp.maximum(bt_ref[b, s * K + j], 0), 0, 0, 0)

    def out_map(s, b, j, bt_ref, ctx_ref, w_ref):
        return (s, b, 0, 0)

    kernel = functools.partial(_partials_kernel, page=page,
                               slots_per_split=K, ring_width=ring_width,
                               windowed_slice=windowed_slice, qpos=qpos)
    o, l, m = pl.pallas_call(
        kernel,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, R, D), q_map),
                pl.BlockSpec((1, page, KVH, D), kv_map),
                pl.BlockSpec((1, page, KVH, D), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, R, D), out_map),
                pl.BlockSpec((1, 1, R, 1), out_map),
                pl.BlockSpec((1, 1, R, 1), out_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((R, 1), jnp.float32),    # m
                pltpu.VMEM((R, 1), jnp.float32),    # l
                pltpu.VMEM((R, D), jnp.float32),    # acc
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, B, R, D), jnp.float32),
            jax.ShapeDtypeStruct((S, B, R, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, B, R, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
        # the name its custom call and device-trace events carry: a window
        # layer's bounded slice apart from the full table
        name="paged_attention_window" if windowed_slice else "paged_attention",
    )(bt, ctx_lens.astype(jnp.int32), w_arr, q.reshape(B, R, D), k_pages,
      v_pages)
    return (o.reshape(S, B, KVH, G, D), l.reshape(S, B, KVH, G),
            m.reshape(S, B, KVH, G))


def paged_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                    window=None, ring_width: int = 0, n_splits: int = 1,
                    interpret: bool | None = None):
    """Full (normalized) decode attention — partials merged on-device.

    q [B, KVH, G, D]; k_pages/v_pages [P, page, KVH, D];
    block_tables [B, maxp] int32 (-1 padded); ctx_lens [B] int32.
    Returns [B, KVH, G, D] in q.dtype.
    """
    from repro.kernels.ref import combine_partials
    o, l, m = paged_attention_partials(
        q, k_pages, v_pages, block_tables, ctx_lens, window=window,
        ring_width=ring_width, n_splits=n_splits, interpret=interpret)
    o, l, _ = combine_partials(o, l, m)
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def paged_attention_verify(q, k_pages, v_pages, block_tables, ctx_lens, *,
                           window=None, n_splits: int = 1,
                           interpret: bool | None = None):
    """Multi-query verify attention for speculative decode (normalized).

    q [B, KVH, G, T, D] — ``T`` consecutive query positions per slot (the
    pending token + the draft proposals), position of query t being
    ``ctx - 1 + t``; k_pages/v_pages [P, page, KVH, D]; block_tables
    [B, maxp] int32 (-1 padded); ctx_lens [B] int32 context INCLUDING the
    FIRST query token. The T axis folds into the kernel's q-row axis
    (``qpos``) so the same split-K page stream serves all T rows — one pool
    pass verifies the whole proposal window. Returns [B, KVH, G, T, D] in
    q.dtype.
    """
    from repro.kernels.ref import combine_partials
    B, KVH, G, T, D = q.shape
    o, l, m = paged_attention_partials(
        q.reshape(B, KVH, G * T, D), k_pages, v_pages, block_tables,
        ctx_lens, window=window, n_splits=n_splits, qpos=T,
        interpret=interpret)
    o, l, _ = combine_partials(o, l, m)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, KVH, G, T, D).astype(q.dtype)
