"""Speculative decoding on the fused horizon scan: draft-propose + one-pass
multi-query verify (serving.engine draft plumbing, models.model
draft_propose/decode_verify, sampling.make_verifier).

The load-bearing invariant: GREEDY speculative output is token-identical to
target-only decoding for ANY draft — acceptance is longest-matching-prefix
against the target's own argmax, and every rejected proposal's KV is dead
(masked then overwritten) by construction. The matrix below drives that
through every prefill mode, EOS/budget truncation mid-round, preemption +
resume, prefix sharing and a never-accepting draft (pure rollback).
"""
import functools
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import reduced, validate_draft_pair
from repro.models import model as MDL
from repro.serving import DecodeEngine, EngineConfig
from repro.serving import Request as Req

BUDGETS = [3, 12, 5, 12, 2, 9]


@functools.lru_cache(maxsize=None)
def _setup():
    cfg = replace(reduced(get_config("llama3.2-1b")), dtype="float32")
    params = MDL.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


@functools.lru_cache(maxsize=None)
def _draft_setup():
    """A REAL small draft: 1 layer vs the target's 2, independent weights."""
    cfg, _ = _setup()
    dcfg = replace(reduced(get_config("llama3.2-1b"), layers=1),
                   dtype="float32")
    dparams = MDL.init_params(dcfg, jax.random.PRNGKey(7), jnp.float32)
    return dcfg, dparams


def _prompts(nreq=6, shared=0, seed=3):
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, 256, size=shared).astype(np.int32) if shared else None
    out = []
    for _ in range(nreq):
        p = rng.integers(0, 256, size=int(rng.integers(3, 20))).astype(np.int32)
        out.append(np.concatenate([pre, p]) if shared else p)
    return out


def _run(mode="batched", *, draft=None, spec_horizon=3, n_pages=96,
         cache=False, eos=-1, budgets=None, nreq=6, sampler="greedy",
         seed=0, shared=0, gentle=False):
    cfg, params = _setup()
    dcfg = dparams = None
    if draft == "real":
        dcfg, dparams = _draft_setup()
    elif draft == "oracle":          # draft == target: accepts everything
        dcfg, dparams = cfg, params
    ecfg = EngineConfig(
        n_slots=3, page_size=4, n_pages=n_pages, max_context=64,
        prefill_mode=mode, prefill_chunk=5, eos_token=eos, sampler=sampler,
        temperature=0.8, top_k=8 if sampler == "top_k" else 0,
        sample_seed=seed, prefix_cache=cache, reserve_gentle=gentle,
        decode_horizon=spec_horizon + 1 if dcfg is None else 1,
        draft_config=dcfg, spec_horizon=spec_horizon)
    eng = DecodeEngine(cfg, ecfg, params=params, draft_params=dparams)
    for i, (p, b) in enumerate(zip(_prompts(nreq, shared),
                                   budgets or BUDGETS[:nreq])):
        eng.submit(Req(i, p, b))
    out = eng.run()
    return {k: list(v) for k, v in out.items()}, eng


# ---------------------------------------------------------------------------
# greedy equivalence matrix: every prefill mode x draft quality
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["slot", "batched", "chunked"])
@pytest.mark.parametrize("draft", ["real", "oracle"])
def test_greedy_token_identity(mode, draft):
    base, _ = _run(mode)
    spec, eng = _run(mode, draft=draft)
    assert spec == base, (mode, draft)
    assert eng.spec_rounds > 0
    if draft == "oracle":            # identical logits -> full acceptance
        assert eng.spec_accepted == eng.spec_proposed > 0
    assert eng.alloc.pages_in_use == 0


def test_spec_sync_budget():
    """One host sync per speculative round — the draft scan, catch-up and
    verify ride the same dispatch window, so syncs-per-token beats the
    non-spec engine at equal horizon when the draft accepts."""
    _, base = _run("batched", spec_horizon=3)
    _, spec = _run("batched", draft="oracle", spec_horizon=3)
    assert spec.timing.device_syncs <= base.timing.device_syncs
    assert spec.timing.decode_tokens == base.timing.decode_tokens


# ---------------------------------------------------------------------------
# mid-round EOS / budget truncation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("draft", ["real", "oracle"])
def test_mid_round_eos(draft):
    probe, _ = _run("batched")
    eos = probe[1][2]                # forces an EOS mid-stream for req 1
    base, _ = _run("batched", eos=eos)
    spec, eng = _run("batched", draft=draft, eos=eos)
    assert spec == base
    # truncation means the round emitted fewer tokens than it accepted —
    # outputs stop AT the EOS token
    assert spec[1][-1] == eos and eos not in spec[1][:-1]


def test_budget_truncation_exact():
    """Budgets cut rounds mid-acceptance (BUDGETS has 2/3/5-token runs
    against a 4-token round); emitted counts must equal the engine's
    budget + 1 convention (prefill's first token + max_new decode steps),
    exactly as the non-spec path does."""
    spec, eng = _run("batched", draft="oracle")
    for rid, b in enumerate(BUDGETS):
        assert len(spec[rid]) == b + 1, rid
    assert eng.alloc.pages_in_use == 0


# ---------------------------------------------------------------------------
# preemption + resume, prefix sharing, rejection rollback
# ---------------------------------------------------------------------------

def test_preemption_resume():
    kw = dict(n_pages=12, nreq=3, budgets=[12, 12, 12])
    base, _ = _run("batched", **kw)
    spec, eng = _run("batched", draft="oracle", **kw)
    assert spec == base
    assert eng.batcher.stats.preempted > 0
    assert eng.alloc.pages_in_use == 0
    # re-admission reset the draft coverage and caught up from scratch
    assert eng.spec_accepted == eng.spec_proposed > 0


def test_prefix_sharing():
    base, _ = _run("batched", cache=True, shared=38)
    spec, eng = _run("batched", draft="oracle", cache=True, shared=38)
    assert spec == base
    assert eng.cache.stats.hits > 0
    # shared radix pages get bit-identical draft KV from every borrower
    assert eng.spec_accepted == eng.spec_proposed > 0


def test_rejection_rollback():
    """A draft that never matches (random weights, greedy target) exercises
    the full-rollback path every round: stale KV beyond the accepted prefix
    must never leak into later logits, and no pages may leak."""
    kw = dict(n_pages=12, nreq=3, budgets=[12, 12, 12])
    base, _ = _run("batched", **kw)
    spec, eng = _run("batched", draft="real", **kw)
    assert spec == base
    assert eng.spec_proposed > 0
    assert eng.alloc.pages_in_use == 0


# ---------------------------------------------------------------------------
# accept-length bookkeeping
# ---------------------------------------------------------------------------

def test_accept_counters_deterministic():
    """Oracle draft: acceptance is total and the counters are an exact
    function of the trajectory — every round accepts its full proposal, and
    mean accept length exceeds 1 (the CI bench gate's invariant)."""
    r1 = _run("batched", draft="oracle")[1]
    r2 = _run("batched", draft="oracle")[1]
    assert (r1.spec_rounds, r1.spec_proposed, r1.spec_accepted) == \
           (r2.spec_rounds, r2.spec_proposed, r2.spec_accepted)
    assert r1.spec_accepted == r1.spec_proposed > 0
    mean_accept = 1 + r1.spec_accepted / r1.spec_rounds
    assert mean_accept > 1.5
    # tokens emitted = sum over rounds of (accept + 1), minus truncation:
    # never more than the counters allow
    assert r1.timing.decode_tokens <= r1.spec_rounds + r1.spec_accepted \
        + sum(1 for _ in BUDGETS)    # + one first token per request


# ---------------------------------------------------------------------------
# stochastic verification (residual rejection sampling)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampler", ["temperature", "top_k"])
def test_stochastic_deterministic_and_exact_on_match(sampler):
    s1, e1 = _run("batched", draft="oracle", sampler=sampler)
    s2, e2 = _run("batched", draft="oracle", sampler=sampler)
    assert s1 == s2                  # seed-deterministic
    # p == q -> u*q <= p always -> acceptance is total even stochastically
    assert e1.spec_accepted == e1.spec_proposed > 0


def test_stochastic_mismatched_draft_runs():
    """Residual resampling path (acc < nprop): must produce valid tokens
    and stay deterministic; qlogits row at the rejection point is used, the
    stale row beyond it never is."""
    s1, e1 = _run("batched", draft="real", sampler="top_k")
    s2, e2 = _run("batched", draft="real", sampler="top_k")
    assert s1 == s2
    assert e1.spec_rounds > 0
    assert all(0 <= t < 256 for ts in s1.values() for t in ts)
    assert e1.alloc.pages_in_use == 0


# ---------------------------------------------------------------------------
# construction-time validation (the tokenizer-compat bugfix)
# ---------------------------------------------------------------------------

def test_vocab_mismatch_fails_at_construction():
    """Full-size cross-family configs (genuinely different tokenizers) must
    fail in EngineConfig validation BEFORE any params are allocated — not
    as a shape error inside the verify jit."""
    target = get_config("qwen1.5-7b")
    draft = get_config("llama3.2-1b")
    with pytest.raises(ValueError, match="tokenizer mismatch"):
        validate_draft_pair(target, draft)
    ecfg = EngineConfig(n_slots=2, page_size=4, n_pages=16, max_context=32,
                        draft_config=draft)
    with pytest.raises(ValueError, match="tokenizer mismatch"):
        DecodeEngine(target, ecfg)   # full 7B config: must not init params


def test_recurrent_draft_rejected():
    cfg, _ = _setup()
    with pytest.raises(ValueError, match="attention-only"):
        validate_draft_pair(cfg, reduced(get_config("xlstm-350m")))
    with pytest.raises(ValueError, match="attention-only"):
        validate_draft_pair(reduced(get_config("zamba2-1.2b")), cfg)


def test_draft_by_registry_name():
    cfg, params = _setup()
    ecfg = EngineConfig(n_slots=2, page_size=4, n_pages=48, max_context=32,
                        eos_token=-1, draft_config="llama3.2-1b")
    with pytest.raises(ValueError, match="tokenizer mismatch"):
        # reduced target (vocab 256) vs full registry draft (128256)
        DecodeEngine(cfg, ecfg, params=params)


# ---------------------------------------------------------------------------
# gentle horizon reservation
# ---------------------------------------------------------------------------

def test_gentle_reservation_spares_cache():
    """gentle=True must never call the reclaimer for speculative growth —
    the horizon degrades instead — while aggressive reservation does."""
    from repro.core.allocator import PageAllocator
    from repro.core.scheduler import ContinuousBatcher, Request

    class Reclaimer:
        def __init__(self):
            self.calls = 0

        def reclaimable(self):
            return 4

        def reclaim(self, n):
            self.calls += 1
            return 0

    def batcher():
        alloc = PageAllocator(8, 1, 4)
        alloc.reclaimer = Reclaimer()
        b = ContinuousBatcher(alloc, 2, max_context=256, bt_width=8)
        b.submit(Request(0, 10, 50))
        b.submit(Request(1, 10, 50))
        b.step(None)
        for r in b.slots:
            r.prefill_done = True
        b.step(None)
        return b, alloc

    b, alloc = batcher()
    allow = b.reserve_horizon([0, 1], 8, gentle=True)
    assert alloc.reclaimer.calls == 0
    assert allow[0] >= 1 and allow[1] >= 1      # degraded, never starved
    b2, alloc2 = batcher()
    b2.reserve_horizon([0, 1], 8, gentle=False)
    assert alloc2.reclaimer.calls > 0


def test_gentle_end_to_end_identical():
    """Degrading the horizon never changes tokens (greedy horizons are
    trajectory-invariant), with or without a draft."""
    base, _ = _run("batched", n_pages=12, nreq=3, budgets=[12, 12, 12])
    for draft in (None, "oracle"):
        gentle, eng = _run("batched", draft=draft, gentle=True,
                           n_pages=12, nreq=3, budgets=[12, 12, 12])
        assert gentle == base, draft
        assert eng.alloc.pages_in_use == 0


# ---------------------------------------------------------------------------
# async recurrent-carry snapshots (dispatch at preempt, drain next tick)
# ---------------------------------------------------------------------------

def test_snapshot_async_drain():
    """The preemption hook must store DEVICE arrays (no sync at preempt
    time); the drain converts them to host numpy within a tick. Outputs
    stay identical to the ample-pool run (covered by
    test_recurrent_prefill); here we pin the asynchrony itself."""
    cfg = replace(reduced(get_config("xlstm-350m")), dtype="float32")
    params = MDL.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    ecfg = EngineConfig(n_slots=2, page_size=4, n_pages=9, max_context=64,
                        eos_token=-1, prefill_mode="batched")
    eng = DecodeEngine(cfg, ecfg, params)

    seen = {"device": 0, "drained": 0}
    orig = DecodeEngine._drain_snapshots

    def spy(self):
        for rid in self._snap_pending:
            snap = self.rsnaps.get(rid)
            if snap is not None:
                leaves = jax.tree.leaves(snap["rows"])
                if leaves and isinstance(leaves[0], jax.Array):
                    seen["device"] += 1
        orig(self)
        for snap in self.rsnaps.values():
            leaves = jax.tree.leaves(snap["rows"])
            if leaves and isinstance(leaves[0], np.ndarray):
                seen["drained"] += 1

    eng._drain_snapshots = spy.__get__(eng)
    for i, p in enumerate(_prompts(2)):
        eng.submit(Req(i, p, 12))
    eng.run()
    assert eng.batcher.stats.preempted > 0
    assert eng.rstate_snapshots > 0
    assert seen["device"] > 0        # parked as device futures at preempt
    assert seen["drained"] > 0       # materialized by the overlap drain
    assert not eng._snap_pending
