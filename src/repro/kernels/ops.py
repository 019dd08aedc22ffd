"""jit'd wrappers around the Pallas kernels, with reference fallbacks.

Backend selection is automatic (``kernels/backend.py``): ``use_pallas=None``
resolves to True on TPU and False elsewhere, and ``interpret=None`` resolves
to False on TPU / True elsewhere. Off-TPU production paths therefore
lower the pure-jnp reference math while tests force ``interpret=True`` to
exercise the kernels themselves — the semantics are identical (tests
assert allclose).

Integration points:
  * ``paged_decode_step`` — THE decode hot path: the incoming token's K/V
    write and the context-adaptive paged-attention kernel in one dispatch
    (core/itpp.py's shard body on a single shard),
  * ``write_targets``   — per-step Va2Pa write-target resolution (npage/noff
    with idle/frozen slots routed out of bounds so the scatter drops them);
    the device-side half of the host "configuration buffer" update, used by
    the fused multi-step decode (``models.model.decode_multi``) to advance
    write positions on device between host syncs,
  * ``decode_attention`` — full-attention decode over the paged pool,
  * ``itpp_partials``   — split-K partials for the cross-shard merge,
  * ``mamba_mixer``     — Mamba2 chunk scan for train/prefill.

``KernelConfig`` (re-exported from ``kernels/backend.py``) is the single
knob object threaded from configs/launch through ``models.model.Runtime``
down to these call sites.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref as REF
from repro.kernels.backend import DEFAULT_KERNELS, KernelConfig, on_tpu
from repro.kernels.flash_decode import flash_decode
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssm_scan import ssm_chunk_scan

__all__ = ["KernelConfig", "DEFAULT_KERNELS", "decode_attention",
           "verify_attention", "paged_decode_step", "write_targets",
           "itpp_partials", "attention_fwd", "mamba_mixer",
           "merge_partials"]


def _resolve(use_pallas: bool | None) -> bool:
    return on_tpu() if use_pallas is None else bool(use_pallas)


def write_targets(block_table, ctx, run, *, page_size: int, n_pages: int,
                  ring_width: int = 0):
    """Resolve the KV write target for each slot's incoming token.

    ``block_table`` [B, W] int32 Va2Pa; ``ctx`` [B] context INCLUDING the
    incoming token; ``run`` [B] bool — slots decoding this step. Inactive /
    frozen slots target page ``n_pages`` (out of bounds) so the pool scatter
    drops their write. Traceable: the fused decode scan calls this once per
    step on device; the per-token ``serving.engine.step`` keeps a host-numpy
    twin of the same resolution (kept deliberately eager-free there) — the
    two must stay bit-identical. Returns (npage [B], noff [B]) int32.
    """
    B, W = block_table.shape
    t = jnp.maximum(jnp.asarray(ctx, jnp.int32) - 1, 0)
    vp = t // page_size
    if ring_width:
        vp = vp % ring_width
    npage = block_table[jnp.arange(B), jnp.minimum(vp, W - 1)]
    npage = jnp.where(run, npage, n_pages).astype(jnp.int32)
    noff = jnp.where(run, t % page_size, 0).astype(jnp.int32)
    return npage, noff


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "n_splits"))
def decode_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                     use_pallas: bool | None = None,
                     interpret: bool | None = None, n_splits: int = 1):
    """q [B, KVH, G, D] -> [B, KVH, G, D] (q.dtype)."""
    if _resolve(use_pallas):
        return paged_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                               n_splits=n_splits, interpret=interpret)
    return REF.paged_attention_ref(q, k_pages, v_pages, block_tables,
                                   ctx_lens).astype(q.dtype)


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "n_splits"))
def verify_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                     window=None, use_pallas: bool | None = None,
                     interpret: bool | None = None, n_splits: int = 1):
    """Speculative-verify multi-query attention over the decode table.

    q [B, KVH, G, T, D] — T consecutive query positions per slot (pending
    token + draft proposals), query t at position ``ctx - 1 + t``; ctx_lens
    counts tokens INCLUDING the first query token. One split-K pool pass
    scores all T rows (``paged_attention.paged_attention_verify``); the
    reference fallback is the gather-then-dense oracle. Returns
    [B, KVH, G, T, D] in q.dtype.
    """
    from repro.kernels.paged_attention import paged_attention_verify
    if _resolve(use_pallas):
        return paged_attention_verify(q, k_pages, v_pages, block_tables,
                                      ctx_lens, window=window,
                                      n_splits=n_splits, interpret=interpret)
    return REF.paged_attention_verify_ref(
        q, k_pages, v_pages, block_tables, ctx_lens,
        window=window).astype(q.dtype)


@partial(jax.jit, static_argnames=("ring_width", "cond_window", "kernels"))
def paged_decode_step(q, k_new, v_new, pool_k, pool_v, block_table, ctx_len,
                      new_page, new_off, window=0, *, ring_width: int = 0,
                      cond_window: int = 0,
                      kernels: KernelConfig = DEFAULT_KERNELS):
    """One decode step's attention against the paged pool, single shard:
    the incoming token's K/V scatter AND the context-adaptive attention in
    one dispatch. q [B, H, D]; k_new/v_new [B, KVH, D];
    pool_{k,v} [P, page, KVH, D]; block_table [B, maxp]; ctx_len [B]
    (INCLUDING the new token); ``window`` may be traced.
    Returns (out [B, H, D], pool_k, pool_v).
    """
    from repro.core.itpp import ItppSpec, itpp_decode_attention_shard
    spec = ItppSpec((), (), None, 1, 1, pool_k.shape[1])
    return itpp_decode_attention_shard(
        q, k_new, v_new, pool_k, pool_v, block_table, ctx_len, new_page,
        new_off, window, spec=spec, mesh_axis_sizes={},
        max_pages_per_req=block_table.shape[1], ring_width=ring_width,
        cond_window=cond_window, kernels=kernels)


@partial(jax.jit, static_argnames=("n_splits", "use_pallas", "interpret"))
def itpp_partials(q, k, v, ctx_lens, *, n_splits: int = 8,
                  use_pallas: bool | None = None,
                  interpret: bool | None = None):
    """Split-K partials (o, l, m) for the stable ITPP/EPU merge."""
    if _resolve(use_pallas):
        return flash_decode(q, k, v, ctx_lens, n_splits=n_splits,
                            interpret=interpret)
    return REF.flash_decode_ref(q, k, v, ctx_lens, n_splits)


@partial(jax.jit, static_argnames=("causal", "window", "use_pallas",
                                   "interpret"))
def attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                  use_pallas: bool | None = None,
                  interpret: bool | None = None):
    """Forward flash attention (prefill/training fwd): [B,S,H,D] -> same."""
    if _resolve(use_pallas):
        from repro.kernels.flash_attention import flash_attention_fwd
        return flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   interpret=interpret)
    from repro.models.layers import flash_attention
    return flash_attention(q, k, v, causal=causal, window=window)


@partial(jax.jit, static_argnames=("chunk", "use_pallas", "interpret"))
def mamba_mixer(q, k, v, log_a, log_g, *, chunk: int = 128, state=None,
                valid_len=None, use_pallas: bool | None = None,
                interpret: bool | None = None):
    """Chunked selective scan -> (y [B,S,H,P] f32, state [B,H,N,P] f32).

    ``state`` [B,H,N,P] resumes a sequence at a chunk boundary (the
    serving engine's state-carrying chunked prefill); ``valid_len`` [B]
    masks length-bucketed end-padding out of the returned state."""
    if _resolve(use_pallas):
        return ssm_chunk_scan(q, k, v, log_a, log_g, chunk=chunk,
                              state=state, valid_len=valid_len,
                              interpret=interpret)
    if valid_len is not None:
        from repro.models.ssm import mask_log_gates_tail
        log_a, log_g = mask_log_gates_tail(log_a, log_g, valid_len)
    h0 = None if state is None else (
        state, jnp.zeros(state.shape[:-1], state.dtype),
        jnp.zeros(state.shape[:-2], state.dtype))
    y, (C, _, _) = REF.ssm_chunk_scan_ref(q, k, v, log_a, log_g, h0, chunk)
    return y, C


def merge_partials(o, l, m):
    return REF.merge_flash_partials(o, l, m)
