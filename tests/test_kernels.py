"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:              # graceful fallback: example-based driver
    from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.backend import KernelConfig, default_interpret
from repro.kernels.flash_decode import flash_decode
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssm_scan import ssm_chunk_scan
from repro.serving import Request as Req

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,KVH,G,D,page,maxp", [
    (2, 1, 1, 8, 4, 3),
    (3, 2, 4, 16, 8, 4),
    (1, 4, 2, 32, 16, 2),
])
def test_paged_attention_sweep(dtype, B, KVH, G, D, page, maxp):
    key = jax.random.PRNGKey(B + D)
    P_ = B * maxp + 2
    q = jax.random.normal(key, (B, KVH, G, D), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(1), (P_, page, KVH, D))
    vp = jax.random.normal(jax.random.PRNGKey(2), (P_, page, KVH, D))
    bt = jnp.asarray(np.random.default_rng(0).permutation(P_)[:B * maxp]
                     .reshape(B, maxp).astype(np.int32))
    ctx = jnp.asarray(np.random.default_rng(1).integers(
        1, maxp * page + 1, B).astype(np.int32))
    out = paged_attention(q.astype(dtype), kp.astype(dtype), vp.astype(dtype),
                          bt, ctx, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, ctx)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_splits", [1, 3])
@pytest.mark.parametrize("window", [None, 7])
@pytest.mark.parametrize("B,KVH,G,T,D,page,maxp", [
    (2, 1, 1, 3, 8, 4, 4),
    (3, 2, 2, 5, 16, 4, 5),
])
def test_paged_attention_verify_sweep(dtype, n_splits, window, B, KVH, G, T,
                                      D, page, maxp):
    """Multi-query verify kernel vs the gather-then-dense oracle: T query
    rows per slot at positions ctx-1..ctx+T-2 (speculative verify), causal
    frontier advancing per row, partial last pages, optional window."""
    from repro.kernels.paged_attention import paged_attention_verify
    P_ = B * maxp + 2
    q = jax.random.normal(jax.random.PRNGKey(3), (B, KVH, G, T, D),
                          jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(1), (P_, page, KVH, D))
    vp = jax.random.normal(jax.random.PRNGKey(2), (P_, page, KVH, D))
    bt = jnp.asarray(np.random.default_rng(0).permutation(P_)[:B * maxp]
                     .reshape(B, maxp).astype(np.int32))
    # ctx counts tokens INCLUDING the first query row; leave T-1 slots of
    # page headroom so the verify rows all fit in the table
    ctx = jnp.asarray(np.random.default_rng(1).integers(
        1, maxp * page - T + 2, B).astype(np.int32))
    w = None if window is None else jnp.full((B,), window, jnp.int32)
    out = paged_attention_verify(
        q.astype(dtype), kp.astype(dtype), vp.astype(dtype), bt, ctx,
        window=w, n_splits=n_splits, interpret=True)
    want = ref.paged_attention_verify_ref(q, kp, vp, bt, ctx, window=w)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_attention_verify_degenerates_to_decode():
    """T=1 verify must be bit-comparable to the plain decode kernel (same
    math, qpos=1 mask degenerates to tok < ctx)."""
    from repro.kernels.paged_attention import paged_attention_verify
    B, KVH, G, D, page, maxp = 2, 2, 3, 16, 4, 4
    P_ = B * maxp + 1
    q = jax.random.normal(jax.random.PRNGKey(0), (B, KVH, G, 1, D))
    kp = jax.random.normal(jax.random.PRNGKey(1), (P_, page, KVH, D))
    vp = jax.random.normal(jax.random.PRNGKey(2), (P_, page, KVH, D))
    bt = jnp.asarray(np.random.default_rng(0).permutation(P_)[:B * maxp]
                     .reshape(B, maxp).astype(np.int32))
    ctx = jnp.asarray([5, maxp * page], np.int32)
    out = paged_attention_verify(q, kp, vp, bt, ctx, interpret=True)
    want = paged_attention(q[:, :, :, 0], kp, vp, bt, ctx, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, :, :, 0]), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,KVH,G,D,T,S", [
    (2, 2, 3, 16, 32, 4),
    (1, 1, 8, 32, 64, 8),
    (4, 2, 1, 8, 16, 2),
])
def test_flash_decode_sweep(dtype, B, KVH, G, D, T, S):
    key = jax.random.PRNGKey(T)
    q = jax.random.normal(key, (B, KVH, G, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, KVH, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, KVH, D))
    ctx = jnp.asarray(np.random.default_rng(0).integers(1, T + 1, B),
                      jnp.int32)
    o, l, m = flash_decode(q.astype(dtype), k.astype(dtype), v.astype(dtype),
                           ctx, n_splits=S, interpret=True)
    oref, lref, mref = ref.flash_decode_ref(q, k, v, ctx, S)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref),
                               atol=TOL[dtype] * 5, rtol=TOL[dtype] * 5)
    # merged partials == dense attention (the ITPP/EPU merge identity)
    merged = ref.merge_flash_partials(o, l, m)
    from repro.models.layers import decode_attention_ref
    dense = decode_attention_ref(q.reshape(B, KVH * G, D), k, v, ctx)
    np.testing.assert_allclose(np.asarray(merged.reshape(B, KVH * G, D)),
                               np.asarray(dense), atol=TOL[dtype] * 5,
                               rtol=TOL[dtype] * 5)


@pytest.mark.parametrize("B,S,H,N,P,chunk", [
    (2, 32, 3, 8, 16, 8),
    (1, 64, 1, 4, 4, 16),
    (3, 16, 2, 16, 8, 4),
])
def test_ssm_scan_sweep(B, S, H, N, P, chunk):
    key = jax.random.PRNGKey(S)
    q = jax.random.normal(key, (B, S, H, N))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, N))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, P))
    la = -jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(3), (B, S, H)))
    lg = jax.random.normal(jax.random.PRNGKey(4), (B, S, H)) * 0.1
    y, st_ = ssm_chunk_scan(q, k, v, la, lg, chunk=chunk, interpret=True)
    yref, (Cref, _, _) = ref.ssm_chunk_scan_ref(q, k, v, la, lg, None, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_), np.asarray(Cref), atol=1e-4)


def test_ssm_scan_state_carry_and_tail_mask():
    """Chunk-boundary continuation: feeding the returned state back in
    resumes the scan exactly (split-invariance), and ``valid_len`` masks a
    length-bucketed pad tail into identity steps so the returned state
    stops at each row's true last token."""
    B, S, H, N, P = 2, 16, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    q = jax.random.normal(ks[0], (B, S, H, N))
    k = jax.random.normal(ks[1], (B, S, H, N))
    v = jax.random.normal(ks[2], (B, S, H, P))
    la = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    lg = jax.random.normal(ks[4], (B, S, H)) * 0.2
    h0 = jax.random.normal(ks[5], (B, H, N, P)) * 0.3
    h0_ref = (h0, jnp.zeros((B, H, N)), jnp.zeros((B, H)))

    # carry in/out: two half-scans == one full scan from the same state
    y1, s1 = ssm_chunk_scan(q[:, :8], k[:, :8], v[:, :8], la[:, :8],
                            lg[:, :8], chunk=4, state=h0, interpret=True)
    y2, s2 = ssm_chunk_scan(q[:, 8:], k[:, 8:], v[:, 8:], la[:, 8:],
                            lg[:, 8:], chunk=4, state=s1, interpret=True)
    yref, (Cref, _, _) = ref.ssm_chunk_scan_ref(q, k, v, la, lg, h0_ref, 4)
    np.testing.assert_allclose(np.concatenate([y1, y2], 1),
                               np.asarray(yref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(Cref), atol=1e-4)

    # masked tail: row 0 valid to 10, row 1 full — pow2 bucketing stays
    # valid because pads never touch the carry
    _, sm = ssm_chunk_scan(q, k, v, la, lg, chunk=4, state=h0,
                           valid_len=jnp.asarray([10, S]), interpret=True)
    _, (C10, _, _) = ref.ssm_chunk_scan_ref(
        q[:1, :10], k[:1, :10], v[:1, :10], la[:1, :10], lg[:1, :10],
        tuple(x[:1] for x in h0_ref), 2)
    np.testing.assert_allclose(np.asarray(sm[0]), np.asarray(C10[0]),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(sm[1]), np.asarray(Cref[1]),
                               atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,H,KVH,D,causal,window", [
    (2, 16, 4, 2, 16, True, 0),
    (1, 32, 8, 2, 8, True, 8),
    (2, 16, 4, 4, 16, False, 0),
    (1, 24, 6, 3, 8, True, 5),
])
def test_flash_attention_fwd_sweep(dtype, B, Sq, H, KVH, D, causal, window):
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.models.layers import flash_attention
    key = jax.random.PRNGKey(Sq)
    q = jax.random.normal(key, (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, Sq, KVH, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, Sq, KVH, D))
    out = flash_attention_fwd(q.astype(dtype), k.astype(dtype),
                              v.astype(dtype), causal=causal, window=window,
                              q_blk=8, kv_blk=8, interpret=True)
    ref = flash_attention(q, k, v, causal=causal, window=window, kv_chunk=8)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=TOL[dtype] * 3, rtol=TOL[dtype] * 3)


# ---------------------------------------------------------------------------
# decode hot path: kernel vs gathered-dense reference, full feature matrix
# ---------------------------------------------------------------------------

def _decode_case(*, G, ring, window, partial_ctx, seed=0):
    """A paged decode step: pool, tables (with -1 pads and one dead batch
    row), per-request ctx (spanning partial pages when asked), and the
    incoming token's K/V + write target."""
    from repro.core.allocator import PageAllocator
    page, maxp, KVH, D, B = 4, 5, 2, 8, 3
    H = KVH * G
    P = B * maxp + 2
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    pool_k = jax.random.normal(key, (P, page, KVH, D), jnp.float32)
    pool_v = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (P, page, KVH, D), jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(seed + 2), (B, H, D))
    k_new = jax.random.normal(jax.random.PRNGKey(seed + 3), (B, KVH, D))
    v_new = jax.random.normal(jax.random.PRNGKey(seed + 4), (B, KVH, D))
    ring_width = maxp if ring else 0
    if ring:
        # wrapped ring: more context than the ring holds
        ctx = np.asarray([maxp * page + 3, maxp * page + 1, 0], np.int32)
    elif partial_ctx:
        ctx = np.asarray([7, maxp * page, 0], np.int32)   # mid-page + full
    else:
        ctx = np.asarray([page, 2 * page, 0], np.int32)
    perm = rng.permutation(P)
    bt = np.full((B, maxp), -1, np.int32)
    npage = np.full((B,), P, np.int32)                    # dead rows drop
    noff = np.zeros((B,), np.int32)
    pos = 0
    for b in range(B):
        if ctx[b] == 0:
            continue
        n_alloc = min(-(-int(ctx[b]) // page), maxp)
        bt[b, :n_alloc] = perm[pos:pos + n_alloc]
        pos += n_alloc
        t = int(ctx[b]) - 1
        vp = (t // page) % ring_width if ring else t // page
        npage[b] = bt[b, vp]
        noff[b] = t % page
    return dict(q=q, k_new=k_new, v_new=v_new, pool_k=pool_k, pool_v=pool_v,
                bt=jnp.asarray(bt), ctx=jnp.asarray(ctx),
                npage=jnp.asarray(npage), noff=jnp.asarray(noff),
                window=window, ring_width=ring_width, page=page, maxp=maxp)


def _run_shard(case, kernels, *, cond_window=0, window=None):
    from repro.core.itpp import ItppSpec, itpp_decode_attention_shard
    spec = ItppSpec((), (), None, 1, 1, case["page"])
    w = case["window"] if window is None else window
    return itpp_decode_attention_shard(
        case["q"], case["k_new"], case["v_new"], case["pool_k"],
        case["pool_v"], case["bt"], case["ctx"], case["npage"], case["noff"],
        w, spec=spec, mesh_axis_sizes={},
        max_pages_per_req=case["maxp"], ring_width=case["ring_width"],
        cond_window=cond_window, kernels=kernels)


@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("ring,window,partial_ctx", [
    (False, 0, False),            # plain, page-aligned ctx
    (False, 0, True),             # ctx mid-page + exactly-full table
    (False, 6, True),             # sliding-window mask
    (True, 9, False),             # ring pool (slots recycle mod width)
    (True, 0, False),             # ring, unwindowed mask
])
@pytest.mark.parametrize("n_splits", [1, 3])
def test_itpp_kernel_matches_gathered_dense(G, ring, window, partial_ctx,
                                            n_splits):
    """The Pallas decode hot path is numerically identical to the
    gather-then-dense reference across the pool feature matrix, including
    the folded-in token write."""
    case = _decode_case(G=G, ring=ring, window=window, partial_ctx=partial_ctx)
    out_d, pk_d, pv_d = _run_shard(case, None)
    out_k, pk_k, pv_k = _run_shard(
        case, KernelConfig(use_pallas=True, interpret=True,
                           n_splits=n_splits))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_array_equal(np.asarray(pk_k), np.asarray(pk_d))
    np.testing.assert_array_equal(np.asarray(pv_k), np.asarray(pv_d))


@pytest.mark.parametrize("window", [0, 6])
def test_itpp_kernel_cond_window_branches(window):
    """cond_window: the windowed-slice kernel (only the table slots
    overlapping the window ride the grid) agrees with the dense path for
    both lax.cond branches of a mixed local:global stack."""
    case = _decode_case(G=2, ring=False, window=window, partial_ctx=True)
    out_d, *_ = _run_shard(case, None, cond_window=8)
    out_k, *_ = _run_shard(
        case, KernelConfig(use_pallas=True, interpret=True), cond_window=8)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d),
                               atol=3e-5, rtol=3e-5)


def test_itpp_kernel_traced_window_scan():
    """Per-layer window flags ride a scan as data (gemma3-style): the
    kernel path must accept a TRACED window scalar."""
    case = _decode_case(G=2, ring=False, window=0, partial_ctx=True)
    kc = KernelConfig(use_pallas=True, interpret=True)

    def body(carry, w):
        out, *_ = _run_shard(case, kc, window=w)
        return carry, out

    _, outs = jax.jit(lambda ws: jax.lax.scan(body, 0, ws))(
        jnp.asarray([0, 6], jnp.int32))
    for i, w in enumerate((0, 6)):
        ref_out, *_ = _run_shard(case, None, window=jnp.int32(w))
        np.testing.assert_allclose(np.asarray(outs[i]), np.asarray(ref_out),
                                   atol=3e-5, rtol=3e-5)


def test_flash_decode_tail_split():
    """T that does not divide n_splits: the tail split is padded+masked."""
    B, KVH, G, D, T, S = 2, 2, 2, 8, 21, 4
    q = jax.random.normal(jax.random.PRNGKey(0), (B, KVH, G, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, KVH, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, KVH, D))
    ctx = jnp.asarray([T, 5], jnp.int32)
    o, l, m = flash_decode(q, k, v, ctx, n_splits=S, interpret=True)
    oref, lref, mref = ref.flash_decode_ref(q, k, v, ctx, S)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(lref), atol=1e-5)
    merged = ref.merge_flash_partials(o, l, m)
    from repro.models.layers import decode_attention_ref
    dense = decode_attention_ref(q.reshape(B, KVH * G, D), k, v, ctx)
    np.testing.assert_allclose(
        np.asarray(merged.reshape(B, KVH * G, D)), np.asarray(dense),
        atol=1e-5, rtol=1e-5)


def test_backend_autodetect(monkeypatch):
    """Defaults ride the backend: on TPU the kernel compiles through
    Mosaic unless the caller explicitly asks for interpret mode."""
    import repro.kernels.backend as BK
    assert default_interpret() == (jax.default_backend() != "tpu")
    kc = KernelConfig().resolve()
    assert kc.use_pallas == BK.on_tpu() and kc.interpret == (not BK.on_tpu())
    monkeypatch.setattr(BK, "on_tpu", lambda: True)
    kc = KernelConfig().resolve()
    assert kc.use_pallas is True and kc.interpret is False
    assert KernelConfig(interpret=True).resolve().interpret is True


# ---------------------------------------------------------------------------
# end-to-end: engine greedy decode, kernel path on vs off
# ---------------------------------------------------------------------------

def _serve(cfg, params, **ecfg_kw):
    from repro.serving import DecodeEngine, EngineConfig
    kw = dict(n_slots=2, page_size=4, n_pages=48, max_context=32,
              eos_token=-1, prefill_mode="batched")
    kw.update(ecfg_kw)
    eng = DecodeEngine(cfg, EngineConfig(**kw), params)
    rng = np.random.default_rng(3)
    for r in range(3):
        eng.submit(Req(r, rng.integers(0, cfg.vocab_size,
                                   size=int(rng.integers(4, 14))), 4))
    outs = eng.run(300)
    assert eng.batcher.stats.completed == 3
    return {k: list(v) for k, v in outs.items()}


@pytest.mark.slow
def test_engine_kernel_token_identity():
    """Greedy decode through the serving engine is token-identical with the
    Pallas decode hot path on vs the gathered-dense path."""
    from repro.configs import get_config, reduced
    from dataclasses import replace
    from repro.models import model as MDL
    cfg = replace(reduced(get_config("llama3.2-1b")), dtype="float32")
    params = MDL.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    dense = _serve(cfg, params, use_pallas=False)
    kernel = _serve(cfg, params, use_pallas=True, kernel_interpret=True)
    assert kernel == dense


@pytest.mark.slow
def test_engine_decode_bucketing_token_identity():
    """pow2 live-page bucketing of the decode table (maxp > 16) does not
    change greedy outputs."""
    from repro.configs import get_config, reduced
    from dataclasses import replace
    from repro.models import model as MDL
    cfg = replace(reduced(get_config("llama3.2-1b")), dtype="float32")
    params = MDL.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    kw = dict(page_size=2, n_pages=96, max_context=60)   # maxp = 31 > 16
    full = _serve(cfg, params, decode_bucket=False, **kw)
    bucketed = _serve(cfg, params, decode_bucket=True, **kw)
    assert bucketed == full


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_merge_partials_property(data):
    """Property: stable merge of ANY split of the KV is split-invariant."""
    B = data.draw(st.integers(1, 3))
    KVH = data.draw(st.sampled_from([1, 2]))
    G = data.draw(st.sampled_from([1, 2, 4]))
    D = data.draw(st.sampled_from([8, 16]))
    T = 32
    key = jax.random.PRNGKey(data.draw(st.integers(0, 100)))
    q = jax.random.normal(key, (B, KVH, G, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, KVH, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, KVH, D))
    ctx = jnp.asarray(data.draw(st.lists(st.integers(1, T), min_size=B,
                                         max_size=B)), jnp.int32)
    merged = {}
    for s in (1, 2, 4, 8):
        o, l, m = ref.flash_decode_ref(q, k, v, ctx, s)
        merged[s] = np.asarray(ref.merge_flash_partials(o, l, m))
    for s in (2, 4, 8):
        np.testing.assert_allclose(merged[s], merged[1], atol=2e-5, rtol=2e-5)


def test_partials_split_axis_leads_grid_and_is_split_invariant():
    """The split-K axis now LEADS the pallas grid (parallel dimension
    semantics for megacore partitioning): the partials land split-major
    [S, B, KVH, G, ...] and the MERGED attention is numerically identical
    for every n_splits — the 'no numeric change' contract of threading
    dimension_semantics through."""
    from repro.kernels.paged_attention import paged_attention_partials
    B, KVH, G, D, page, maxp = 2, 2, 3, 16, 4, 6
    P_ = B * maxp + 1
    q = jax.random.normal(jax.random.PRNGKey(0), (B, KVH, G, D))
    kp = jax.random.normal(jax.random.PRNGKey(1), (P_, page, KVH, D))
    vp = jax.random.normal(jax.random.PRNGKey(2), (P_, page, KVH, D))
    bt = jnp.asarray(np.random.default_rng(0).permutation(P_)[:B * maxp]
                     .reshape(B, maxp).astype(np.int32))
    ctx = jnp.asarray([maxp * page, 7], jnp.int32)
    merged = {}
    for s in (1, 2, 3, 6):
        o, l, m = paged_attention_partials(q, kp, vp, bt, ctx, n_splits=s,
                                           interpret=True)
        assert o.shape == (s, B, KVH, G, D)
        assert l.shape == m.shape == (s, B, KVH, G)
        oo, ll, _ = ref.combine_partials(o, l, m)
        merged[s] = np.asarray(oo / np.maximum(np.asarray(ll), 1e-30)[..., None])
    for s in (2, 3, 6):
        np.testing.assert_allclose(merged[s], merged[1], atol=2e-5, rtol=2e-5)
