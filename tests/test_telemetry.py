"""Telemetry correctness: deterministic-counter exactness against the
subsystems' own ground truth across prefill modes / preemption / spec
decoding, per-request records reproducing the engine's TTFT, the Prometheus
exposition round-trip, the profiler's view of the engine (phase spans,
named programs, program scopes), the decode-work and compile counters
against hand counts, and the no-op-sink identity (a telemetry-disabled
engine is token- and sync-count-identical)."""
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import model as MDL
from repro.serving import DecodeEngine, EngineConfig
from repro.telemetry import (NULL, PROGRAMS, TelemetryConfig,
                             make_telemetry, parse_exposition, percentile)
from repro.telemetry.profile import read_profile
from repro.serving import Request as Req

PAGE = 4
BUDGETS = [3, 12, 5, 12, 2, 9]
_SHARED = {}


def _setup():
    if "cfg" not in _SHARED:
        cfg = replace(reduced(get_config("llama3.2-1b")), dtype="float32")
        _SHARED["cfg"] = cfg
        _SHARED["params"] = MDL.init_params(cfg, jax.random.PRNGKey(0),
                                            jnp.float32)
    return _SHARED["cfg"], _SHARED["params"]


def _run(K=4, mode="batched", *, telemetry="on", n_pages=96, cache=False,
         host_pages=0, budgets=BUDGETS, nreq=6, spec=False, spec_horizon=3):
    cfg, params = _setup()
    tel = TelemetryConfig(metrics=True) if telemetry == "on" else None
    ecfg = EngineConfig(n_slots=3, page_size=PAGE, n_pages=n_pages,
                        max_context=64, eos_token=-1, prefill_mode=mode,
                        prefill_chunk=5,
                        decode_horizon=1 if spec else K,
                        prefix_cache=cache, host_pages=host_pages,
                        draft_config=cfg if spec else None,
                        spec_horizon=spec_horizon, telemetry=tel)
    eng = DecodeEngine(cfg, ecfg, params,
                       draft_params=params if spec else None)
    rng = np.random.default_rng(3)
    for r in range(nreq):
        p = rng.integers(0, cfg.vocab_size, size=int(rng.integers(3, 20)))
        eng.submit(Req(r, p, budgets[r % len(budgets)]))
    outs = eng.run(3000)
    return {k: list(v) for k, v in outs.items()}, eng


# ---------------------------------------------------------------------------
# deterministic counter exactness vs subsystem ground truth
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K,mode", [(1, "batched"), (4, "slot"),
                                    (4, "batched"), (4, "chunked")])
def test_counters_match_ground_truth(K, mode):
    """Every registry sample equals the authoritative counter it binds:
    decode tokens, device syncs, scheduler admit/complete, per-request
    token totals — across the per-token and fused paths in every prefill
    mode."""
    outs, eng = _run(K, mode)
    g = eng.tel.registry.get
    t, st = eng.timing, eng.batcher.stats
    assert g("engine_decode_tokens_total") == t.decode_tokens
    assert g("engine_device_syncs_total") == t.device_syncs
    assert g("engine_steps_total") == t.steps
    assert g("sched_admitted_total") == st.admitted
    assert g("sched_completed_total") == st.completed == len(outs)
    assert g("sched_preempted_total") == st.preempted
    # tracker-side totals: one record per request, tokens add up exactly
    recs = {r.req_id: r for r in eng.tel.tracker.records}
    assert len(recs) == len(outs) and all(r.finished for r in recs.values())
    for rid, toks in outs.items():
        assert recs[rid].tokens == len(toks), rid
    total = sum(len(v) for v in outs.values())
    assert g("requests_finished_total") == len(outs)
    assert g("request_tokens_total") == total
    assert g("requests_live") == 0
    # pool drained, peak high-water recorded
    assert g("kv_pages_in_use", {"tier": "device"}) == 0
    assert g("kv_pages_in_use_peak", {"tier": "device"}) > 0


def test_preemption_resume_counters():
    """A pool-starved run preempts; the tracker's per-request preemption /
    resume counts reconcile exactly with SchedulerStats (every preempted
    request that finished was re-admitted)."""
    outs, eng = _run(4, n_pages=10, nreq=2, budgets=[12, 12])
    st = eng.batcher.stats
    assert st.preempted > 0 and st.completed == 2
    recs = eng.tel.tracker.records
    assert sum(r.preemptions for r in recs) == st.preempted
    assert sum(r.resumes for r in recs) == st.preempted
    assert st.admitted == len(recs) + sum(r.resumes for r in recs)
    assert eng.tel.registry.get("sched_preempted_total") == st.preempted
    assert eng.tel.summary()["preemptions"] == st.preempted


def test_spec_accept_counters():
    """Speculative run with an oracle draft (draft == target): accepted ==
    proposed > 0, and the registry / per-record accounting both equal the
    engine's own spec counters."""
    outs, eng = _run(spec=True)
    assert eng.spec_rounds > 0
    assert eng.spec_accepted == eng.spec_proposed > 0
    g = eng.tel.registry.get
    assert g("spec_rounds_total") == eng.spec_rounds
    assert g("spec_proposed_total") == eng.spec_proposed
    assert g("spec_accepted_total") == eng.spec_accepted
    recs = eng.tel.tracker.records
    assert sum(r.spec_accepted for r in recs) == eng.spec_accepted
    assert sum(r.spec_proposed for r in recs) == eng.spec_proposed
    accl = [r.accept_len_mean for r in recs if r.accept_len_mean is not None]
    assert accl and all(a > 1.0 for a in accl)   # oracle accepts everything


def test_cache_and_host_tier_counters():
    """Prefix-cache + host-tier bindings mirror CacheStats / TierStats
    exactly (swap in/out, lookups, device pages across tiers)."""
    outs, eng = _run(4, cache=True, host_pages=16)
    g = eng.tel.registry.get
    cs, hs = eng.cache.stats, eng.cache.host.stats
    assert g("kv_cache_lookups") == cs.lookups > 0
    assert g("kv_cache_hits") == cs.hits
    assert g("kv_cache_hit_tokens") == cs.hit_tokens
    assert g("kv_cache_evicted_pages") == cs.evicted_pages
    assert g("kv_swapped_out_pages") == hs.swapped_out_pages
    assert g("kv_swapped_in_pages") == hs.swapped_in_pages
    assert g("kv_pages_total", {"tier": "host"}) == eng.cache.host.capacity
    assert g("kv_pages_in_use", {"tier": "host"}) == eng.cache.host.used


def test_modeled_pim_counters():
    """Modeled HBM bytes accumulate as exact multiples of the model's
    kv_bytes_per_token; channel util stays in [0, 1]; the pow2 bucket
    high-water is a real bucket width."""
    outs, eng = _run(4)
    g = eng.tel.registry.get
    bpt = eng.tel.pim.kv_bytes_per_token()
    cfg = eng.cfg
    assert bpt == 2 * cfg.n_kv_heads * cfg.d_head * 2 * cfg.n_layers
    v = g("pim_modeled_hbm_bytes_total")
    assert v > 0
    assert abs(v / bpt - round(v / bpt)) < 1e-6   # integer token-ctx sum
    assert 0.0 <= g("pim_channel_util") <= 1.0
    hw = int(g("decode_table_bucket_highwater"))
    assert hw >= 1 and (hw & (hw - 1)) == 0 or hw == eng.batcher._bt_width
    assert 0.0 <= g("dpa_page_waste_ratio") <= 1.0


# ---------------------------------------------------------------------------
# per-request records == the bench's latency source of truth
# ---------------------------------------------------------------------------

def test_records_reproduce_engine_ttft():
    """Record-derived TTFT equals the engine's legacy first_tok_t-submit_t
    to float identity, and queue/ttft/tpot orderings are sane."""
    outs, eng = _run(4)
    for r in eng.tel.tracker.records:
        legacy = eng.first_tok_t[r.req_id] - eng.submit_t[r.req_id]
        assert abs(r.ttft_s - legacy) < 1e-9, r.req_id
        assert r.queue_s is not None and 0 <= r.queue_s <= r.ttft_s
        if r.tokens >= 2:
            assert r.tpot_s is not None and r.tpot_s >= 0
            assert r.finish_t >= r.first_token_t >= r.submit_t
    sm = eng.tel.summary()
    assert sm["finished"] == len(outs)
    assert sm["ttft_p50_ms"] <= sm["ttft_p99_ms"]


def test_percentile_nearest_rank():
    assert percentile([], 50) == 0.0
    assert percentile([7.0], 99) == 7.0
    vs = [50.0, 10.0, 40.0, 20.0, 30.0]
    assert percentile(vs, 0) == 10.0
    assert percentile(vs, 50) == 30.0
    assert percentile(vs, 100) == 50.0


# ---------------------------------------------------------------------------
# exposition + trace round-trips
# ---------------------------------------------------------------------------

def test_prometheus_render_parses():
    outs, eng = _run(4, cache=True, host_pages=16)
    text = eng.tel.registry.render()
    samples = parse_exposition(text)
    assert len(samples) > 40
    # per-tier PIM pool samples present with labels
    assert 'repro_kv_pages_total{tier="device"}' in samples
    assert 'repro_kv_pages_total{tier="host"}' in samples
    assert samples["repro_engine_decode_tokens_total"] == \
        eng.timing.decode_tokens
    # histogram series integrity (bucket monotonicity spot check)
    buckets = sorted((k, v) for k, v in samples.items()
                     if k.startswith("repro_request_ttft_seconds_bucket"))
    assert buckets
    assert samples["repro_request_ttft_seconds_count"] == len(outs)


def test_parse_exposition_rejects_malformed():
    with pytest.raises(ValueError):
        parse_exposition("this is not a metric line !!!\n")
    with pytest.raises(ValueError):
        parse_exposition("# TYPE foo banana\nfoo 1\n")
    with pytest.raises(ValueError):
        parse_exposition("# TYPE h histogram\nh_sum 1\nh_count 1\n")


def test_work_counters_in_exposition():
    """The decode-step, compile and paged-grid counters are bound for
    scraping and equal EngineTiming's, a compile sample per program."""
    outs, eng = _run(4)
    samples = parse_exposition(eng.tel.registry.render())
    t = eng.timing
    assert samples["repro_engine_decode_steps_total"] == t.decode_steps > 0
    assert samples["repro_paged_grid_cells_total"] == t.paged_grid_cells
    assert samples["repro_paged_live_cells_total"] == t.paged_live_cells
    for prog in PROGRAMS:
        key = f'repro_engine_compiles_total{{program="{prog}"}}'
        assert samples[key] == t.compiles.get(prog, 0), prog
    assert t.compiles["decode_horizon"] >= 1


# ---------------------------------------------------------------------------
# the profiler's view: engine phase spans, named programs, program scopes
# ---------------------------------------------------------------------------

def test_profile_has_engine_phases_and_named_programs(tmp_path):
    """Under jax.profiler the fused tick's phases show as ``engine.*``
    spans on the host, and the fused program's ops carry its name."""
    _run(4)                              # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        outs, eng = _run(4)
    finally:
        jax.profiler.stop_trace()
    spans, ops = read_profile(str(tmp_path))
    names = {n for n, _, _ in spans}
    for phase in ("overlap", "collect", "fold", "faults", "schedule",
                  "prefill", "config", "dispatch"):
        assert f"engine.{phase}" in names, phase
    assert all(e >= s for _, s, e in spans)
    modules = {m for _, m, _, _ in ops}
    assert "jit_decode_horizon" in modules, sorted(modules)
    assert "jit_prefill_batch" in modules, sorted(modules)


def test_step_path_phases_are_spans(tmp_path):
    """The per-token ``step()`` path's phases are spans too, and its
    program is ``jit_decode_step``."""
    cfg, params = _setup()
    ecfg = EngineConfig(n_slots=2, page_size=PAGE, n_pages=32,
                        max_context=48, eos_token=-1)
    eng = DecodeEngine(cfg, ecfg, params)
    eng.submit(Req(0, np.arange(1, 6), 3))
    eng.step()                           # prefill + first decode compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    spans, ops = read_profile(str(tmp_path))
    names = {n for n, _, _ in spans}
    for phase in ("schedule", "config", "decode_sample", "bookkeep"):
        assert f"engine.{phase}" in names, phase
    assert "jit_decode_step" in {m for _, m, _, _ in ops}
    t = eng.timing
    assert t.compiles["decode_step"] == 1
    # three decode steps (budget 3) at contexts 6, 7, 8: two pages each
    assert t.decode_steps == t.device_syncs == t.decode_tokens == 3
    assert t.paged_live_cells == 6
    assert t.paged_grid_cells == 3 * 2 * eng.pool_spec.max_pages_per_req


def test_decode_program_scopes():
    """The compiled fused program tags its compute with the model's
    scopes: every one of ``DECODE_SCOPES`` and the layer scan ``layers``
    appear in its ops' ``op_name`` metadata, under the program's name."""
    outs, eng = _run(4)
    d = eng.dev
    allow = jnp.zeros((eng.ecfg.n_slots,), jnp.int32)
    text = eng._fused_jit.lower(
        eng.params, eng.state, d.tokens, d.bt, d.ctx, d.rem, allow, d.key,
        horizon=4, width=d.bt.shape[1]).compile().as_text()
    paths = [p for p in re.findall(r'op_name="([^"]+)"', text)
             if p.startswith("jit(decode_horizon)/")]
    parts = {c for p in paths for c in p.split("/")}
    for scope in MDL.DECODE_SCOPES + ("layers",):
        assert scope in parts, scope


def test_decode_work_counters_hand_count():
    """Decode steps, the paged grid and its live cells equal a hand count.
    Every request runs its ``max_new`` decode steps at contexts p+1 ...
    p+max_new (p its prompt), holding ceil(ctx / page) live pages at each;
    each dispatch runs K scan steps over n_slots x W table slots (W = 13
    here: 48 / 4 + 1 pages, too few to bucket)."""
    cfg, params = _setup()
    K, prompts, budgets = 4, [10, 5, 17], [9, 3, 6]
    ecfg = EngineConfig(n_slots=3, page_size=PAGE, n_pages=64,
                        max_context=48, eos_token=-1, decode_horizon=K)
    eng = DecodeEngine(cfg, ecfg, params)
    rng = np.random.default_rng(5)
    for r, (p, n) in enumerate(zip(prompts, budgets)):
        eng.submit(Req(r, rng.integers(0, cfg.vocab_size, size=p), n))
    outs = eng.run(100)
    assert [len(outs[r]) for r in range(3)] == [n + 1 for n in budgets]
    t = eng.timing
    W = eng.pool_spec.max_pages_per_req
    assert W == 13
    live = sum(-(-c // PAGE) for p, n in zip(prompts, budgets)
               for c in range(p + 1, p + n + 1))
    assert t.device_syncs == 3           # ceil(9 / 4) horizons, all at once
    assert t.decode_steps == K * t.device_syncs == 12
    assert t.paged_grid_cells == 12 * 3 * W
    assert t.paged_live_cells == live == 76
    assert 0 < t.paged_live_cells / t.paged_grid_cells <= 1


def test_compiles_count_new_widths_once():
    """``compiles`` rises once per new decode table width and not when a
    width comes round again: one request grows from the 16-page bucket
    into the 32-page one, a second repeats both widths."""
    cfg, params = _setup()
    ecfg = EngineConfig(n_slots=2, page_size=PAGE, n_pages=128,
                        max_context=256, eos_token=-1, decode_horizon=4)
    eng = DecodeEngine(cfg, ecfg, params)
    rng = np.random.default_rng(6)
    eng.submit(Req(0, rng.integers(0, cfg.vocab_size, size=60), 12))
    eng.run(100)
    c = dict(eng.timing.compiles)
    assert c["decode_horizon"] == 2, c   # widths 16 and 32
    assert c["prefill_batch"] >= 1 and c["patch_slots"] >= 1
    eng.submit(Req(1, rng.integers(0, cfg.vocab_size, size=60), 12))
    eng.run(100)
    assert eng.timing.compiles["decode_horizon"] == 2
    assert eng.timing.compiles["prefill_batch"] == c["prefill_batch"]


# ---------------------------------------------------------------------------
# no-op sink: disabled telemetry is behavior-identical
# ---------------------------------------------------------------------------

def test_disabled_telemetry_identity():
    """telemetry=None produces token-identical outputs with the SAME
    device-sync and decode-token counts as an instrumented run — the
    telemetry layer adds no rendezvous — and installs nothing: no events
    hook, no registry entries, shared NULL facade."""
    base, e_off = _run(4, telemetry="off")
    got, e_on = _run(4, telemetry="on")
    assert got == base
    assert e_on.timing.device_syncs == e_off.timing.device_syncs
    assert e_on.timing.decode_tokens == e_off.timing.decode_tokens
    assert e_off.tel is NULL and not e_off.tel.enabled
    assert e_off.batcher.events is None
    assert e_off.tel.registry.render() == "\n"        # renders empty
    e_off.tel.close()                                  # no-ops don't raise


def test_make_telemetry_dispatch():
    from repro.telemetry import Telemetry
    assert make_telemetry(None) is NULL
    assert make_telemetry(False) is NULL
    assert make_telemetry(TelemetryConfig(metrics=False)) is NULL
    live = make_telemetry(TelemetryConfig(metrics=True))
    assert isinstance(live, Telemetry) and live.enabled
    assert make_telemetry(live) is live
    assert make_telemetry(NULL) is NULL
    with pytest.raises(TypeError):
        make_telemetry(42)
