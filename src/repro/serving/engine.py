"""Decode engine: thin orchestration of tick = schedule -> prefill ->
decode -> sample.

The host loop mirrors the paper's Fig. 2(c): each iteration the host updates
the "configuration buffer" (block tables, context lengths, write targets)
and dispatches compiled decode work; EOS requests release their pages and
their slot refills from the queue (Fig. 2(b)). The layers are split so each
is replaceable:

* scheduling — ``core.scheduler.ContinuousBatcher`` with a pluggable
  admission policy (``serving.policies``: FCFS / SJF / memory-aware);
* prefill   — ``serving.prefill``: per-slot (seed), length-bucketed batched,
  or chunked DCS-style interleave with decode;
* sampling  — ``serving.sampling``: jitted greedy / temperature / top-k;
* KV reuse  — ``repro.kvcache.PrefixCache`` (optional): radix prefix
  sharing across requests plus a host-DRAM offload tier.

Two decode paths share the scheduler and prefillers:

* ``step()`` — the per-token tick (seed semantics): rebuild the config
  buffers, dispatch ONE decode step, block on the logits, sample. Kept as
  the reference path and for callers driving the engine token-by-token.
* the fused multi-step path (``run()``) — ``EngineConfig.decode_horizon``
  decode steps run inside one jit (``models.model.decode_multi``): decode,
  on-device sampling (legacy per-row ``sample=`` callables ride along
  through an ordered host-callback adapter), KV
  write-position advance and per-slot EOS/budget masking all stay on
  device, so the host syncs once per horizon instead of once per token.
  The per-slot state (block table, context, current token, remaining
  budget) is device-resident, patched incrementally from the scheduler's
  dirty-set on admission/growth/preemption — never rebuilt per step — and
  the tick is pipelined DCS-style: the scan is dispatched asynchronously,
  the next tick's result-independent host work (cache ping-pong drain,
  radix peek prefetch) overlaps device compute, and the only host<->device
  rendezvous is the horizon's token readback. Greedy outputs are
  token-identical for every horizon (each slot replays the exact per-token
  trajectory; finished slots freeze and their KV writes drop).

This engine is the single-host functional version (used by tests, examples
and the lazy-allocation benchmark); launch/serve.py wraps it with the mesh
sharding plan for the production layout.
"""
from __future__ import annotations

import time
import warnings
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.allocator import PageAllocator
from repro.core.paged_kv import PoolSpec
from repro.core.scheduler import ContinuousBatcher, Request
from repro.models import model as MDL
from repro.serving.policies import make_policy
from repro.serving.prefill import make_prefiller
from repro.serving.request import Request as RequestSpec
from repro.serving.sampling import make_sampler, make_scan_sampler


@dataclass
class EngineConfig:
    n_slots: int
    page_size: int
    n_pages: int
    max_context: int
    n_shards: int = 1
    n_rows: int = 1
    policy: str = "striped"           # page placement: striped | row_affine
    static_alloc: bool = False        # baseline-PIM static max-ctx allocation
    eos_token: int = 1
    max_prefill: int = 64             # batched-prefill bucket cap
    prefill_mode: str = "batched"     # slot | batched | chunked
    prefill_chunk: int = 32           # tokens per chunk in chunked mode
    sched_policy: str = "fcfs"        # fcfs | sjf | memory_aware
    sampler: str = "greedy"           # greedy | temperature | top_k
    temperature: float = 1.0
    top_k: int = 0
    sample_seed: int = 0
    # ---- fused multi-step decode ----
    # decode steps run under ONE jit per tick (host syncs once per horizon).
    # 1 = per-token dispatch trajectory (still fused-path plumbing). Greedy
    # outputs are horizon-invariant; the cost of raising it is one extra jit
    # specialization per (horizon, table-bucket) pair and up to
    # decode_horizon-1 speculatively reserved pages per slot. Clamped to 1
    # while chunked prefill is streaming so the DCS interleave granularity
    # (one chunk between consecutive decode steps) is preserved.
    decode_horizon: int = 1
    # ---- speculative decoding (draft-propose, one-pass verify) ----
    # a small draft config (name or ModelConfig) proposes up to spec_horizon
    # tokens per slot per tick via its own fused scan and (smaller) paged KV
    # pool — indexed by the TARGET's block tables, so no second allocator —
    # and ONE multi-query target pass verifies them all (greedy acceptance
    # is token-identical to target-only decoding; stochastic uses residual
    # rejection sampling, distribution-exact). Supersedes decode_horizon on
    # the fused path: each tick still costs one host sync but can emit up
    # to spec_horizon + 1 tokens per slot. Attention-only stacks on both
    # sides (configs.base.validate_draft_pair enforces tokenizer compat and
    # rollback-ability at construction).
    draft_config: Any = None
    spec_horizon: int = 4
    # gentle horizon reservation: decline to evict radix-cached pages for
    # speculative (beyond-next-token) growth, degrading the horizon instead
    # — sharing-heavy load keeps its prefix cache, at worst costing horizon
    # depth, never correctness (committed per-token growth still reclaims)
    reserve_gentle: bool = False
    # ---- KV-cache hierarchy (repro.kvcache) ----
    prefix_cache: bool = False        # radix prefix sharing across requests
    prefill_dedup: bool = True        # same-tick prefix dedup at admission
    host_pages: int = 0               # host offload tier capacity (0 = none)
    offload_high: float = 0.85        # device watermarks driving offload
    offload_low: float = 0.60
    cache_evict: str = "lru"
    # ---- decode hot path (kernels/backend.py KernelConfig) ----
    use_pallas: bool | None = None    # None = autodetect (pallas on TPU)
    kernel_interpret: bool | None = None
    kernel_splits: int = 1
    # pow2 bucketing of the decode block-table width by live-page count:
    # per-step attention work tracks actual context, not max_context, with
    # at most log2(maxp) extra jit specializations (engines with <=16-page
    # tables skip it — nothing to win there)
    decode_bucket: bool = True
    # ---- recurrent-state preemption snapshots ----
    # recurrent/enc-dec families: preemption snapshots the per-slot carry
    # (SSM/xLSTM hidden + conv states, enc-dec cross KV) AND the written KV
    # pages to host memory, so re-admission restores instead of
    # re-prefilling — the kvcache swap story applied to dense state. Off =
    # seed semantics (full recompute on resume). Slot-mode prefill never
    # consumes snapshots (it is the recompute reference path).
    state_resume: bool = True
    # ---- telemetry (repro.telemetry) ----
    # None (default) = the shared no-op facade: no registry, no scheduler
    # events hook — behavior and device-sync count are identical to a
    # build without telemetry. A TelemetryConfig (or an already-built
    # Telemetry, e.g. serve.py's) turns on the metrics registry and
    # per-request tracing; all of it is host-side bookkeeping riding the
    # existing horizon readback. The engine's phases are profiler spans
    # either way (``_phase``).
    telemetry: Any = None
    # ---- fault injection (repro.runtime.faults) ----
    # None (default) = the shared no-op injector: fire() short-circuits on
    # one bool check, outputs and device-sync count are bit-identical to a
    # build without the subsystem. A FaultConfig (or a live FaultInjector,
    # e.g. shared across engines by a chaos driver) arms the seeded plan:
    # allocator exhaustion, swap-tier failure/stall, serving-row death,
    # NaN logits, slow ticks, client aborts — all replayable from the seed.
    faults: Any = None
    # ---- request lifecycle hardening ----
    # bounded admission queue: submit() load-sheds (terminal, reason
    # "shed") once this many requests are waiting. 0 = unbounded (seed
    # behavior).
    max_queue: int = 0
    # per-request wall-clock deadline applied when submit() is not given
    # one explicitly; 0 = none. Expired requests are torn down at the next
    # tick's safe point wherever they are (queued, prefilling, decoding).
    default_deadline_s: float = 0.0
    # injectable time source (zero-arg callable -> seconds). None = the
    # wall clock (time.perf_counter). Deadlines, submit/first-token
    # timestamps, the scheduler's SLO policies and the request tracker all
    # read THIS clock, so a runtime.clock.VirtualClock makes trace replay
    # and deadline expiry fully deterministic. Performance accounting
    # (EngineTiming, profiler spans) stays on the wall clock regardless —
    # it measures the machine, not the workload.
    clock: Any = None
    # ---- graceful degradation ----
    # faults observed (injected pressure, repeated swap failures, NaN
    # quarantines) before the engine downgrades a tier: spec decoding ->
    # plain fused decode, horizon -> 1, offload tier -> device-only.
    # Sticky bits land in DecodeEngine.degraded_mode. 0 disables the
    # ladder.
    degrade_after: int = 3
    # transient swap-in failures absorbed by retry + capped exponential
    # backoff (TierStats.swap_retries) before each one starts counting
    # toward the degrade_after ladder above (kvcache/cache.py)
    swap_retry_limit: int = 3
    swap_backoff_cap: int = 8
    # ---- crash-consistent serving snapshots ----
    # snapshot_every > 0: every N ticks run() writes a serving checkpoint
    # (scheduler + slot + written-KV + recurrent-carry state) under
    # snapshot_dir using the manifest-gated runtime/checkpoint.py layout;
    # restore_snapshot() on a fresh engine resumes and finishes in-flight
    # requests token-identically (greedy).
    snapshot_dir: str | None = None
    snapshot_every: int = 0
    snapshot_keep: int = 3
    # Real-logits NaN quarantine. None (auto) arms it together with fault
    # injection; True forces it on for hardened deployments. Off by
    # default because greedy argmax over a non-finite row is still
    # deterministic — callers that feed garbage ids (e.g. stress tests
    # with out-of-vocab prompts) keep the pre-hardening sample-as-is
    # behavior unless they opt in.
    nan_guard: bool | None = None
    # ---- disaggregated serving (serving/cluster.py) ----
    # the engine's role in a cluster: "prefill" engines run requests to
    # their first token and hand them off, "decode" engines adopt and
    # finish them, "both" is the colocated single-engine behavior. The
    # engine itself only records the role — the cluster's router enforces
    # it (a standalone engine ignores this field entirely).
    role: str = "both"


@dataclass
class EngineTiming:
    """Wall-clock split of the serving loop (host bookkeeping vs device),
    and counts of the decode work dispatched and of the programs compiled."""
    steps: int = 0
    host_s: float = 0.0               # schedule + config-buffer assembly
    prefill_s: float = 0.0
    decode_s: float = 0.0             # compiled decode step + sampling
    device_syncs: int = 0             # host<->device decode rendezvous
    decode_tokens: int = 0            # tokens emitted by decode dispatches
    # decode steps dispatched (a fused horizon's scan steps, or step()'s
    # one; speculative rounds are not counted), the paged kernel's grid
    # over them, n_slots x table width cells a step, and the cells that
    # hold live KV: a running slot at step j of a dispatch with context
    # ctx has ceil((ctx + j) / page) live pages
    decode_steps: int = 0
    paged_grid_cells: int = 0
    paged_live_cells: int = 0
    # program name -> specializations the engine's calls added to its jit
    # cache (a compile, or a load from the persistent compile cache)
    compiles: dict = field(default_factory=dict)

    @contextmanager
    def compiling(self, fn):
        """Count what the calls of jitted ``fn`` inside the block add to
        its cache, under the program's name."""
        n = fn._cache_size()
        yield
        grown = fn._cache_size() - n
        if grown > 0:                 # (a cleared cache shrinks it)
            self.compiles[fn.__name__] = \
                self.compiles.get(fn.__name__, 0) + grown

    def as_dict(self) -> dict:
        n = max(1, self.steps)
        return {"steps": self.steps, "host_us_per_step": 1e6 * self.host_s / n,
                "prefill_s": self.prefill_s, "decode_s": self.decode_s,
                "host_s": self.host_s, "device_syncs": self.device_syncs,
                "decode_tokens": self.decode_tokens,
                "syncs_per_token": self.device_syncs
                / max(1, self.decode_tokens),
                "decode_steps": self.decode_steps,
                "paged_grid_cells": self.paged_grid_cells,
                "paged_live_cells": self.paged_live_cells,
                "compiles": dict(self.compiles)}


class DeviceSlotState:
    """Device-resident per-slot decode state for the fused multi-step path.

    Holds the block table [n_slots, W], context lengths, current tokens and
    remaining budgets as jax arrays plus the sampler's PRNG key chain. The
    fused scan advances them ON DEVICE; the host only patches the rows the
    scheduler marked dirty (admission / page growth / free) — the
    incremental "configuration buffer" update of the paper's host loop, at
    horizon rather than token granularity. Patch row-counts are pow2-padded
    (repeating the last entry — idempotent) so the donated-buffer scatter
    jit compiles O(log n_slots) variants.
    """

    def __init__(self, n_slots: int, width: int, seed: int, donate: bool):
        self.bt = jnp.full((n_slots, width), -1, jnp.int32)
        self.ctx = jnp.zeros((n_slots,), jnp.int32)
        self.tokens = jnp.zeros((n_slots,), jnp.int32)
        self.rem = jnp.zeros((n_slots,), jnp.int32)
        self.key = jax.random.PRNGKey(seed)
        self._patch = jax.jit(
            DeviceSlotState.patch_slots,
            donate_argnums=(0, 1, 2, 3) if donate else ())

    @staticmethod
    def patch_slots(bt, ctx, tok, rem, idx, bt_rows, ctx_v, tok_v, rem_v):
        return (bt.at[idx].set(bt_rows), ctx.at[idx].set(ctx_v),
                tok.at[idx].set(tok_v), rem.at[idx].set(rem_v))

    def patch(self, slots: list[int], bt_rows, ctx_v, tok_v, rem_v) -> None:
        n, m = len(slots), 1
        while m < n:
            m *= 2
        pad = [slots[-1]] * (m - n)
        idx = np.asarray(slots + pad, np.int32)
        rep = [bt_rows[-1:]] * (m - n)
        self.bt, self.ctx, self.tokens, self.rem = self._patch(
            self.bt, self.ctx, self.tokens, self.rem, jnp.asarray(idx),
            jnp.asarray(np.concatenate([bt_rows] + rep) if pad else bt_rows),
            jnp.asarray(np.concatenate([ctx_v, ctx_v[-1:].repeat(m - n)])),
            jnp.asarray(np.concatenate([tok_v, tok_v[-1:].repeat(m - n)])),
            jnp.asarray(np.concatenate([rem_v, rem_v[-1:].repeat(m - n)])))


class DecodeEngine:
    def __init__(self, cfg, ecfg: EngineConfig, params=None, rt=None,
                 *, sample: Callable | None = None, policy=None,
                 draft_params=None):
        self.cfg = cfg
        self.ecfg = ecfg
        if rt is None:
            from repro.kernels.backend import KernelConfig
            rt = MDL.Runtime(kernels=KernelConfig(
                use_pallas=ecfg.use_pallas,
                interpret=ecfg.kernel_interpret,
                n_splits=ecfg.kernel_splits),
                cond_window=MDL.mixed_window(cfg))
        self.rt = rt
        # draft/target compat is validated BEFORE any params are allocated:
        # a tokenizer (vocab) mismatch must fail here, loudly, not as a
        # shape error inside the compiled verify pass
        self.draft_cfg = None
        if ecfg.draft_config is not None:
            from repro.configs.base import validate_draft_pair
            dcfg = ecfg.draft_config
            if isinstance(dcfg, str):
                from repro.configs import get_config
                dcfg = get_config(dcfg)
            validate_draft_pair(cfg, dcfg)
            if rt.ring_width or rt.write_pool is not None:
                raise ValueError(
                    "speculative decode rides the fused batchable path; "
                    "ring-buffer / sharded-writer runtimes are per-slot")
            if sample is not None:
                raise ValueError(
                    "speculative decode needs the jitted sampler kinds "
                    "(greedy/temperature/top_k) so the draft's proposal "
                    "distribution is known to the verifier; legacy per-row "
                    "sample= callables cannot be verified against")
            self.draft_cfg = dcfg
        # params, pool and per-slot state all take the model config's dtype
        self.params = params if params is not None else MDL.init_params(
            cfg, jax.random.PRNGKey(0))
        kinds = cfg.block_kinds()
        n_attn = cfg.n_layers if cfg.family == "encdec" else \
            sum(1 for k in kinds if k in ("attn", "local"))
        maxp = -(-ecfg.max_context // ecfg.page_size) + 1
        self.pool_spec = PoolSpec(
            max(n_attn, 1), ecfg.n_pages, ecfg.page_size, cfg.n_kv_heads,
            cfg.d_head, maxp, dtype=cfg.dtype)
        static_pages = maxp if ecfg.static_alloc else None
        self.alloc = PageAllocator(
            ecfg.n_pages, ecfg.n_shards, ecfg.page_size, policy=ecfg.policy,
            n_rows=ecfg.n_rows, static_max_pages=static_pages)
        # behavioral time source (deadlines, SLO budgets, request
        # timestamps); see EngineConfig.clock
        self.clock = ecfg.clock if ecfg.clock is not None \
            else time.perf_counter
        self.batcher = ContinuousBatcher(
            self.alloc, ecfg.n_slots, max_context=ecfg.max_context,
            n_rows=ecfg.n_rows, policy=make_policy(policy or ecfg.sched_policy),
            bt_width=self.pool_spec.max_pages_per_req)
        self.batcher.clock = self.clock
        self.state = MDL.init_decode_state(cfg, self.pool_spec, ecfg.n_slots)
        self.tokens = np.zeros((ecfg.n_slots,), np.int32)
        self.prompts: dict[int, np.ndarray] = {}
        self.outputs: dict[int, list[int]] = {}
        # TTFT bookkeeping (benchmarks): wall-clock of submit and of the
        # request's first emitted token
        self.submit_t: dict[int, float] = {}
        self.first_tok_t: dict[int, float] = {}
        # ``sample``: legacy per-row host callable (seed API); otherwise the
        # jitted batch sampler from the config. Legacy callables ride the
        # fused scan through the ordered host-callback adapter
        # (sampling.make_callback_sampler), so run() stays on the fused
        # multi-step path either way.
        self.sample = sample
        self.sampler = make_sampler(ecfg.sampler, temperature=ecfg.temperature,
                                    top_k=ecfg.top_k, seed=ecfg.sample_seed)
        # batched/chunked prefill: attention stacks keep the whole decode
        # state in the shared pool; recurrent and enc-dec families thread
        # their per-slot state rows through the group call as an explicit
        # carry (gather -> prefill -> scatter). Only ring / sharded-writer
        # runtimes stay on the slot path — their prefill branches ignore
        # valid_len (pad-write masking).
        self.batchable = not self.rt.ring_width and self.rt.write_pool is None
        self.chunkable = self.batchable
        # recurrent / cross-attention per-slot state rows ([L, n_slots, ...]
        # leaves of self.state) and their preemption snapshots
        self.has_rstate = bool(MDL.rstate_entries(self.state))
        self._zero_rows = (MDL.init_rstate(cfg, 1)
                           if self.has_rstate else None)
        self.rsnaps: dict[int, dict] = {}   # req_id -> {len, rows, kv?}
        self.rstate_snapshots = 0
        self.rstate_restores = 0
        self.batcher.rstate_hook = self._rstate_hook
        # prefix cache: uniform-attention stacks with plain lazy allocation
        # only (static reservations and ring pools can't share pages,
        # row-affine placement would break borrowing across rows, and
        # recurrent/enc-dec families can't resume from shared pages without
        # the matching dense carry)
        self.cacheable = self.chunkable and "layers" in self.params \
            and cfg.family != "encdec"
        self.cache = None
        if ecfg.prefix_cache and self.cacheable and not ecfg.static_alloc \
                and ecfg.policy == "striped":
            from repro.kvcache import PrefixCache, WatermarkConfig, \
                make_cache_policy
            self.cache = PrefixCache(
                self.alloc,
                policy=make_cache_policy(ecfg.cache_evict,
                                         watermark=WatermarkConfig(
                                             ecfg.offload_high,
                                             ecfg.offload_low)),
                host_pages=ecfg.host_pages,
                pool_ref=lambda: self.state["pool"],
                swap_retry_limit=ecfg.swap_retry_limit,
                swap_backoff_cap=ecfg.swap_backoff_cap)
            self.batcher.cache = self.cache
            self.batcher.cache_tokens = self._cache_tokens
            self.batcher.dedup = ecfg.prefill_dedup
        self.prefiller = make_prefiller(ecfg.prefill_mode, self)
        self.timing = EngineTiming()
        self._decode_jit = None
        self._slot_ids = np.arange(ecfg.n_slots)
        # ---- fused multi-step decode machinery ----
        # buffer donation only where the runtime honors it (TPU/GPU); on CPU
        # it is a no-op that warns per compile
        self._donate = jax.default_backend() not in ("cpu",)
        self.dev = DeviceSlotState(ecfg.n_slots,
                                   self.pool_spec.max_pages_per_req,
                                   ecfg.sample_seed, self._donate)
        self._fused_jit = None
        # in-flight horizon: (toks, emit, fin, [(slot, req)], spec) — device
        # futures; collected at the next tick's sync point. ``spec`` is None
        # on plain horizons, (accept_len_device, nprop_host) on speculative
        # ones.
        self._inflight: tuple | None = None
        # finished mask collected by a drain outside the tick loop, consumed
        # by the next tick's scheduler call
        self._pending_fin: np.ndarray | None = None
        # snapshots taken as DEVICE futures at preempt time, drained to host
        # numpy in the next tick's overlap window (kvcache ping-pong style)
        self._snap_pending: list[int] = []
        # ---- speculative-decode machinery ----
        self.draft_params = None
        self._dstate = None
        if self.draft_cfg is not None:
            dcfg = self.draft_cfg
            # the draft pool is indexed by the TARGET's block tables — same
            # page ids, smaller per-page payload (draft layers/heads), no
            # second allocator. Draft KV at (page, offset) is a pure
            # function of the token prefix at that position, so pages
            # shared by the radix cache stay coherent: every borrower
            # recomputes bit-identical rows.
            self.draft_spec = PoolSpec(
                dcfg.n_layers, ecfg.n_pages, ecfg.page_size, dcfg.n_kv_heads,
                dcfg.d_head, maxp, dtype=dcfg.dtype)
            self.draft_params = draft_params if draft_params is not None \
                else MDL.init_params(dcfg, jax.random.PRNGKey(1))
            self._dstate = MDL.init_decode_state(
                dcfg, self.draft_spec, ecfg.n_slots)
            self._dkey = jax.random.PRNGKey(ecfg.sample_seed + 1)
            # req_id -> tokens the draft pool has absorbed (its KV covers
            # positions [0, dlen)); reset to 0 at every (re)admission —
            # swap-ins, CoW copies and preemption resumes only restore the
            # TARGET's pages, so the draft catches up by recomputing
            self._dlen: dict[int, int] = {}
            self._spec_jits = None
            self._catchup_jit = None
            self.spec_rounds = 0        # verify passes over running slots
            self.spec_proposed = 0      # draft tokens offered
            self.spec_accepted = 0      # draft tokens accepted
        # ---- fault injection + request lifecycle hardening (PR 8) ----
        # one injector threaded through scheduler and cache so every
        # subsystem's injection decisions share the seeded plan
        from repro.runtime.faults import make_faults
        self.faults = make_faults(ecfg.faults)
        self.nan_guard = (self.faults.enabled if ecfg.nan_guard is None
                          else ecfg.nan_guard)
        self.batcher.faults = self.faults
        if self.cache is not None:
            self.cache.faults = self.faults
        # terminal-but-not-finished requests: req_id -> reason
        # (client / deadline / nan / shed / chaos)
        self.aborted: dict[int, str] = {}
        self.abort_counts: dict[str, int] = {
            "client": 0, "deadline": 0, "nan": 0, "shed": 0, "chaos": 0,
            "handoff": 0}
        # aborts requested mid-tick; torn down at the next safe point (a
        # teardown while a horizon is in flight would free pages its KV
        # writes still target — re-admitted, they'd be corrupted)
        self._abort_req: dict[int, str] = {}
        # req_id -> absolute wall-clock deadline (perf_counter frame)
        self.deadline_t: dict[int, float] = {}
        # sticky degradation bitmask: 1 = horizon->1, 2 = spec off,
        # 4 = host tier dropped
        self.degraded_mode = 0
        # serving snapshot bookkeeping (save_snapshot / restore_snapshot)
        self.snapshot_saves = 0
        self.snapshot_restores = 0
        self.snapshot_rejects = 0       # torn/corrupt steps skipped
        self._tick_no = 0
        # ---- telemetry (must come last: bindings read everything above).
        # Disabled -> the shared NULL facade; the scheduler's events hook
        # stays None and every tel.* call below is a bound no-op.
        from repro.telemetry import make_telemetry
        self.tel = make_telemetry(ecfg.telemetry)
        self.tel.attach_engine(self)
        # dispatch-time ctx snapshot of the in-flight horizon — feeds the
        # modeled-bytes counter at collect; stays None when tel is off
        self._horizon_ctx: np.ndarray | None = None

    # ---- unified timing phase helper -----------------------------------
    @contextmanager
    def _phase(self, acc: str, name: str):
        """Accumulate one timed segment into ``EngineTiming.<acc>`` —
        the SINGLE bookkeeping path both ``step()`` and the fused tick use,
        so host/prefill/decode splits stay consistent when the APIs
        interleave — inside a profiler span ``engine.<name>``. With no
        profiler active the span costs about a microsecond; under
        ``jax.profiler`` it puts the segment on the device trace's clock,
        beside the device ops it waits for or dispatches."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"engine.{name}"):
                yield
        finally:
            dt = time.perf_counter() - t0
            setattr(self.timing, acc, getattr(self.timing, acc) + dt)

    def _count_decode_work(self, allow: np.ndarray, steps: int,
                           width: int) -> None:
        """Fold one decode dispatch into the work counters: ``steps``
        decode steps over the ``width``-page table, slot s running the
        first ``allow[s]`` of them from its dispatch-time context."""
        t, page = self.timing, self.ecfg.page_size
        t.decode_steps += steps
        t.paged_grid_cells += steps * self.ecfg.n_slots * width
        j = np.arange(steps)
        live = -(-(self.batcher._ctx[:, None] + j[None, :]) // page)
        t.paged_live_cells += int(live[j[None, :] < allow[:, None]].sum())

    # ------------------------------------------------------------------
    def submit(self, req: RequestSpec | int, prompt: np.ndarray = None,
               max_new_tokens: int | None = None, *,
               deadline_s: float | None = None) -> bool:
        """Enqueue a request described by a ``serving.Request`` spec.
        Returns False when the bounded queue is full and the request was
        load-shed instead (terminal immediately, reason ``shed``, empty
        output). ``spec.deadline_s`` (or the engine default) arms a
        deadline in the engine's clock frame; an expired request is torn
        down at the next tick wherever it is in its lifecycle. Priority
        and TTFT/TPOT targets ride the spec into the scheduling policies
        and the request tracker.

        The legacy positional form ``submit(req_id, prompt,
        max_new_tokens, deadline_s=...)`` survives as a deprecated shim.
        """
        if not isinstance(req, RequestSpec):
            warnings.warn(
                "Engine.submit(req_id, prompt, max_new_tokens, ...) is "
                "deprecated; pass a serving.Request spec",
                DeprecationWarning, stacklevel=2)
            req = RequestSpec(req, prompt, max_new_tokens,
                              deadline_s=deadline_s)
        spec = req
        req_id = spec.req_id
        prompt = np.asarray(spec.prompt, np.int32)
        self.prompts[req_id] = prompt
        self.outputs[req_id] = []
        now = self.submit_t[req_id] = self.clock()
        self.tel.on_submit(req_id, len(prompt), spec.max_new_tokens, now,
                           spec=spec)
        sreq = Request(req_id, len(prompt), spec.max_new_tokens,
                       priority=spec.priority, submit_t=now, spec=spec)
        E = self.ecfg
        if E.max_queue and len(self.batcher.queue) >= E.max_queue:
            self.aborted[req_id] = "shed"
            self.abort_counts["shed"] += 1
            self.tel.on_abort(sreq, -1, "shed")
            return False
        dl = E.default_deadline_s if spec.deadline_s is None \
            else spec.deadline_s
        if dl and dl > 0:
            self.deadline_t[req_id] = now + dl
        if self.prefiller.name == "chunked":
            sreq.chunked_prefill = True
            sreq.prefill_done = False
        self.batcher.submit(sreq)
        return True

    def abort(self, req_id: int, reason: str = "client") -> bool:
        """Client-side cancel. The teardown is DEFERRED to the next tick's
        safe point (post-collect quiescence): freeing a slot's pages while
        a decode horizon is still in flight would let its KV writes land in
        pages a re-admission now owns. Returns True if the request was
        live (queued or running) when the abort was recorded."""
        live = any(r is not None and r.req_id == req_id
                   for r in self.batcher.slots) \
            or any(r.req_id == req_id for r in self.batcher.queue)
        if live:
            self._abort_req.setdefault(req_id, reason)
        return live

    # ---- helpers shared with the prefillers ---------------------------
    def _prompt_seq(self, req) -> tuple[np.ndarray, bool]:
        """Token sequence to prefill and whether a first token should be
        emitted. After a preemption the re-prefill covers the original
        prompt plus every generated token except the last sampled one
        (whose KV was never written; it re-enters as the next decode
        input)."""
        prompt = self.prompts[req.req_id]
        out = self.outputs[req.req_id]
        if req.prompt_len == len(prompt):
            # emit only when no first token exists yet: a re-admission at
            # exactly prompt depth with output already streamed (a handoff
            # or engine-death re-drive at generated == 0) must not sample a
            # duplicate — the existing first token re-enters as the
            # pending decode input instead
            return prompt, not out
        return np.concatenate(
            [prompt, np.asarray(out[:-1], np.int32)])[:req.prompt_len], False

    def _cache_tokens(self, req, finished: bool = False) -> np.ndarray:
        """Token-sequence oracle for the prefix cache (the batcher holds no
        token ids). ``finished=False``: the context a (re)admission must
        cover — exactly ``_prompt_seq``. ``finished=True``: every token
        whose KV was written — prompt plus all generated tokens except the
        final sample (EOS / budget hit), whose KV never landed."""
        if not finished:
            return self._prompt_seq(req)[0]
        prompt = self.prompts[req.req_id]
        out = np.asarray(self.outputs[req.req_id], np.int32)
        return np.concatenate([prompt, out])[:req.total_len - 1]

    def _emit_first(self, slot: int, req, tok: int | None,
                    emit: bool) -> None:
        # the whole prompt's KV is in the pool now: publish the prefix to
        # the radix cache so later same-prefix admissions hit while this
        # request is still running
        req.kv_written = True
        if self.cache is not None:
            self.cache.insert(req.req_id, self._prompt_seq(req)[0])
        if emit:
            self.tokens[slot] = tok
            self.outputs[req.req_id].append(int(tok))
            self.first_tok_t.setdefault(req.req_id, self.clock())
            if self.tel.enabled:
                self.tel.on_tokens(req.req_id, 1,
                                   self.first_tok_t[req.req_id])
        else:
            self.tokens[slot] = self.outputs[req.req_id][-1]
        self.batcher.dirty.add(slot)

    def _sample_rows(self, logits) -> np.ndarray:
        """[B, V] -> [B] int32, one device call for the whole batch. Legacy
        per-row callables keep per-row semantics, but over a single
        host-gathered array (one transfer, not one per slot)."""
        if self.sample is not None:
            rows = np.asarray(logits)
            return np.fromiter((int(self.sample(r)) for r in rows),
                               np.int32, len(rows))
        return np.asarray(self.sampler(logits), np.int32)

    def _first_tokens(self, logits, emits) -> np.ndarray:
        """Sample the first token for a prefill group in ONE batched call
        (greedy-invariant; only rows that emit are sampled, preserving the
        resumed-request no-sample semantics)."""
        toks = np.zeros((len(emits),), np.int32)
        idx = [i for i, e in enumerate(emits) if e]
        if idx:
            toks[idx] = self._sample_rows(np.asarray(logits)[idx])
        return toks

    # ---- recurrent state rows: snapshots, restore, group gather ---------
    def _rstate_hook(self, req, slot: int, finished: bool) -> None:
        """Scheduler callback at page release. Preemption (finished=False)
        snapshots the slot's recurrent/cross state rows plus its written KV
        pages to host memory — the dense-state analogue of the kvcache
        swap-out — so re-admission restores instead of recomputing.
        Completion drops any stored snapshot."""
        if finished:
            self.rsnaps.pop(req.req_id, None)
            return
        if not (self.has_rstate and self.ecfg.state_resume
                and req.kv_written and self.prefiller.name != "slot"):
            return
        if not self.outputs.get(req.req_id):
            # no token ever sampled (prefill finished but the finish-line
            # growth page failed): a pure restore could never produce the
            # first token — no logits without a model call — so resume
            # must recompute; snapshotting would strand the request
            return
        # written context: the last sampled token's KV/state never landed
        # (it re-enters as the next decode input), and ``generated`` was
        # pre-incremented this tick — mirrors _preempt's total_len - 1
        depth = req.total_len - (1 if req.generated else 0)
        if depth <= 0:
            return
        # the gathers are DISPATCHED here (they must read the pool before
        # the released pages are rewritten — device-stream order guarantees
        # that) but NOT synced: the device arrays park in the snapshot and
        # the host copy happens in the next tick's overlap window
        # (_drain_snapshots), so snapshot latency hides under decode exactly
        # like the kvcache swap-out ping-pong. A restore that arrives
        # before the drain consumes the device arrays directly.
        snap = {"len": depth,
                "rows": MDL.gather_rstate(self.state, [slot])}
        if "pool" in self.state:
            from repro.core.paged_kv import gather_pages
            n = -(-depth // self.ecfg.page_size)
            pages = np.asarray(self.batcher.block_table_row(slot)[:n])
            k, v = gather_pages(self.state["pool"]["k"],
                                self.state["pool"]["v"], jnp.asarray(pages))
            snap["kv"] = (k, v)
        self.rsnaps[req.req_id] = snap
        self._snap_pending.append(req.req_id)
        self.rstate_snapshots += 1

    def _drain_snapshots(self) -> None:
        """Materialize pending preemption snapshots to host numpy (the
        drain half of the snapshot ping-pong). Snapshots restored before
        their drain were consumed as device arrays and are gone from
        ``rsnaps`` — skip them."""
        for rid in self._snap_pending:
            snap = self.rsnaps.get(rid)
            if snap is None:
                continue
            snap["rows"] = jax.tree.map(np.asarray, snap["rows"])
            if "kv" in snap:
                snap["kv"] = tuple(np.asarray(x) for x in snap["kv"])
        self._snap_pending.clear()

    def _take_snapshot(self, req) -> dict | None:
        if not self.ecfg.state_resume:
            return None
        return self.rsnaps.pop(req.req_id, None)

    def _begin_prefill_group(self, admitted) -> tuple[dict, set]:
        """Prepare the tick's admitted slots for prefill in ONE rows
        scatter: preemption snapshots restore the carry (and their KV
        pages) at their depth, everything else resets to zero so group
        prefill gathers a clean carry (the row may hold a freed request's
        state). Returns ``({slot: resume_depth}, {restored slots})`` —
        depth is the snapshot depth or the prefix-cache depth (0 when
        cold). Enc-dec cross-KV rows are NOT materialized here — batched
        prefill computes them inside the group call, chunked prefill
        batches them per tick (``_init_cross_rows``)."""
        starts: dict[int, int] = {}
        fresh: list[int] = []
        restores: list[tuple[int, dict]] = []
        for slot, req in admitted:
            snap = self._take_snapshot(req)
            if snap is not None:
                restores.append((slot, snap))
                starts[slot] = snap["len"]
            else:
                fresh.append(slot)
                starts[slot] = req.cached_len
        if self.has_rstate and (fresh or restores):
            parts = []
            if fresh:
                parts.append(jax.tree.map(
                    lambda z: jnp.repeat(z, len(fresh), axis=1),
                    self._zero_rows))
            parts += [jax.tree.map(jnp.asarray, snap["rows"])
                      for _, snap in restores]
            self.state = MDL.scatter_rstate(
                self.state, fresh + [s for s, _ in restores],
                jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                             *parts))
        for slot, snap in restores:
            if "kv" in snap:
                from repro.core.paged_kv import scatter_pages
                k, v = snap["kv"]
                pages = self.batcher.block_table_row(slot)[:k.shape[1]]
                pk, pv = scatter_pages(self.state["pool"]["k"],
                                       self.state["pool"]["v"],
                                       jnp.asarray(np.asarray(pages)),
                                       jnp.asarray(k), jnp.asarray(v))
                self.state["pool"] = {"k": pk, "v": pv}
        self.rstate_restores += len(restores)
        return starts, {s for s, _ in restores}

    def _init_cross_rows(self, slots: list[int]) -> None:
        """Encoder pass + cross-KV projection for enc-dec chunked prefill
        (stub zero frames, matching the slot path)."""
        frames = jnp.zeros((len(slots), self.cfg.enc_seq, self.cfg.d_model),
                           self.cfg.dtype)
        enc_out = MDL.encode(self.cfg, self.params, frames)
        ck, cv = MDL.make_cross_kv(self.cfg, self.params, enc_out)
        self.state = MDL.scatter_rstate(self.state, slots,
                                        {"cross_k": ck, "cross_v": cv})

    def _group_prefill_state(self, slots: list[int]) -> dict:
        """State for a group prefill call: the shared pool plus the group's
        recurrent/cross rows gathered from the engine state (zeroed /
        restored by ``_begin_prefill``, or mid-stream carries for chunked
        prefill)."""
        gs: dict[str, Any] = {}
        if "pool" in self.state:
            gs["pool"] = self.state["pool"]
        if self.has_rstate:
            gs.update(MDL.gather_rstate(self.state,
                                        np.asarray(slots, np.int32)))
        return gs

    def _merge_group_state(self, slots: list[int], gstate: dict) -> None:
        """Fold a group prefill's result back: adopt the pool, scatter the
        group's state rows into their slots."""
        if "pool" in gstate:
            self.state["pool"] = gstate["pool"]
        if self.has_rstate:
            self.state = MDL.scatter_rstate(
                self.state, np.asarray(slots, np.int32),
                MDL.rstate_entries(gstate))

    # ---- fault processing + terminal teardown (the tick safe point) ----
    def _find_request(self, req_id: int):
        """``(slot, req)`` for a running request, ``(None, req)`` for a
        queued one, ``(None, None)`` when the id is not live."""
        for s, r in enumerate(self.batcher.slots):
            if r is not None and r.req_id == req_id:
                return s, r
        for r in self.batcher.queue:
            if r.req_id == req_id:
                return None, r
        return None, None

    def _teardown(self, req_id: int, reason: str) -> bool:
        """Terminal teardown of a live request: scheduler state (pages,
        pins, pending swap ops) via abort_slot/abort_queued, then every
        engine-side reference — carry snapshots, draft-pool coverage,
        deadline tracking. Zero leaks is the contract the robustness tests
        assert at drain."""
        s, req = self._find_request(req_id)
        if req is None:
            return False
        if s is not None:
            self.batcher.abort_slot(s, reason)
        else:
            self.batcher.abort_queued(req, reason)
        self.rsnaps.pop(req_id, None)
        if self._dstate is not None:
            self._dlen.pop(req_id, None)
        self.deadline_t.pop(req_id, None)
        self.aborted[req_id] = reason
        self.abort_counts[reason] = self.abort_counts.get(reason, 0) + 1
        return True

    def _process_row_death(self, finished) -> None:
        """Injected serving-row death: every non-finishing request whose
        pages live on a dead row (physical row under ``row_affine``
        placement, the slot's logical row group under striped) is drained —
        KV on a dead row is garbage, so ``drain_slot`` frees without a
        cache insert and requeues for a full re-prefill of the
        reconstructable context (``elastic.plan_request_migration`` picks
        the victims)."""
        E = self.ecfg
        dead = {row for row in range(max(1, E.n_rows))
                if self.faults.fire("row_death", key=row)}
        if not dead:
            return
        from repro.runtime.elastic import plan_request_migration
        row_of: dict[int, int] = {}
        slot_of: dict[int, int] = {}
        for s, r in enumerate(self.batcher.slots):
            if r is None or (finished is not None and finished[s]):
                continue                # finishing this tick: output is done
            row = self.alloc.row_of_request(r.req_id)
            if row is None:             # striped: logical serving rows
                row = self.batcher._row_of_slot(s)
            row_of[r.req_id] = row
            slot_of[r.req_id] = s
        for rid in plan_request_migration(row_of, dead):
            s = slot_of[rid]
            req = self.batcher.slots[s]
            out = self.outputs[rid]
            if req.prefill_done and out:
                # normalize to the really-emitted frame before the requeue
                # arithmetic (a zero-emission horizon can leave
                # ``generated`` pre-incremented for an unsampled token)
                P = len(self.prompts[rid])
                req.generated = min(
                    req.generated,
                    max(0, len(out) - 1 - (req.prompt_len - P)))
            else:
                req.generated = 0
            self.batcher.drain_slot(s)
            self.rsnaps.pop(rid, None)
            if self._dstate is not None:
                self._dlen.pop(rid, None)

    def _update_degradation(self) -> None:
        """Sticky degradation ladder, driven ONLY by injected pressure and
        real fault observations (never by ordinary preemption — a healthy
        loaded engine must keep its exact perf profile): at degrade_after
        faults, drop speculative decoding (or the horizon, draft-less); at
        2x, the horizon too; repeated swap-tier failures drop the host
        tier (cached host pages invalidated, device-only from then on)."""
        E = self.ecfg
        if not E.degrade_after:
            return
        inj = self.faults.counts
        pressure = (inj.get("alloc_exhaust", 0) + inj.get("row_death", 0)
                    + self.abort_counts.get("nan", 0))
        if pressure >= E.degrade_after:
            if self._dstate is not None:
                self.degraded_mode |= 2
            else:
                self.degraded_mode |= 1
        if pressure >= 2 * E.degrade_after:
            self.degraded_mode |= 1
        if (self.cache is not None and self.cache.host is not None
                and self.cache.stats.swap_in_fails >= E.degrade_after):
            self.cache.drop_host_tier()
            self.degraded_mode |= 4

    def _process_faults(self, finished) -> None:
        """The tick's SAFE POINT: post-collect quiescence (no horizon in
        flight, ``generated`` counts only really-emitted tokens for every
        slot that emitted), before the scheduler reuses anything. Advances
        the fault clock, injects this tick's plan (straggler sleeps,
        seeded client aborts, row deaths), expires deadlines, tears down
        every requested abort, and updates the degradation ladder.
        ``finished`` is the tick's natural-finish mask — a finish beats a
        same-tick abort (the output is already complete), except NaN
        quarantine, whose tokens are invalid by definition."""
        self._tick_no += 1
        F = self.faults
        F.on_tick()
        if F.enabled:
            if F.fire("slow_tick"):
                time.sleep(F.cfg.slow_tick_s)
            live = [r for r in self.batcher.slots if r is not None] \
                + list(self.batcher.queue)
            for r in live:
                if F.fire("client_abort", key=r.req_id):
                    self._abort_req.setdefault(r.req_id, "chaos")
            self._process_row_death(finished)
        if self.deadline_t:
            now = self.clock()
            for rid, t in list(self.deadline_t.items()):
                s, req = self._find_request(rid)
                if req is None or (s is not None and finished is not None
                                   and finished[s]):
                    # terminal, or finishing this very tick (the natural
                    # finish beats a same-tick expiry): stop tracking
                    self.deadline_t.pop(rid)
                elif now >= t:
                    self._abort_req.setdefault(rid, "deadline")
        if self._abort_req:
            for rid, reason in list(self._abort_req.items()):
                s, req = self._find_request(rid)
                if req is None:
                    continue               # already terminal
                if s is not None and finished is not None and finished[s] \
                        and reason != "nan":
                    continue               # natural finish this tick wins
                self._teardown(rid, reason)
            self._abort_req.clear()
        self._update_degradation()

    # ------------------------------------------------------------------
    def step(self, finished_mask=None):
        """One per-token engine tick: schedule -> prefill -> decode ->
        sample, blocking on the step's logits (seed semantics; the fused
        multi-step path in ``run()`` supersedes this on the hot path).

        Interleaves safely with the fused path: a pending fused finished
        mask is consumed when the caller passes none, active slots are
        marked dirty (this tick advances tokens/ctx host-side only, so the
        device mirror must re-sync before the next horizon), and the
        returned mask is also stashed for a later ``run()``."""
        E = self.ecfg
        with self._phase("host_s", "schedule"):
            self._drain_snapshots()
            if self._pending_fin is not None:
                finished_mask = self._pending_fin if finished_mask is None \
                    else (np.asarray(finished_mask, bool) | self._pending_fin)
                self._pending_fin = None
            self._process_faults(finished_mask)
            admitted, active = self.batcher.step(finished_mask)
            if self.cache is not None:
                # drain last tick's swap-outs + watermark offload
                # (ping-pong), then replay queued device ops (swap-in
                # scatters, CoW copies) so prefill and decode read fully
                # materialized pages — unless an injected swap-tier stall
                # skips the drain for this tick
                if not (self.faults.enabled
                        and self.faults.fire("swap_stall")):
                    self.cache.maintain()
                if self.cache.has_pending:
                    self.state["pool"] = self.cache.apply_pending(
                        self.state["pool"])
        if admitted or self.prefiller.busy:
            with self._phase("prefill_s", "prefill"):
                active = self.prefiller.run(admitted, active)
        self.timing.steps += 1
        if not active:
            return np.zeros((E.n_slots,), bool)

        # ---- config-buffer assembly, vectorized over slots ------------
        with self._phase("host_s", "config"):
            ctx = self.batcher.context_lens()
            bt = self.batcher.block_tables(self.pool_spec.max_pages_per_req)
            W = self.pool_spec.max_pages_per_req
            active_mask = np.zeros((E.n_slots,), bool)
            active_mask[active] = True
            # host-numpy twin of kernels.ops.write_targets (the fused scan's
            # device-side resolution) — the two must stay bit-identical for
            # step() and run() to agree (regression: mixed step/run test)
            t = ctx - 1                # slot of the token being written
            vp = np.clip(t, 0, None) // E.page_size
            if self.rt.ring_width:
                vp = vp % self.rt.ring_width
            # idle slots target page n_pages (out of bounds) -> scatter drops
            npage = np.where(active_mask,
                             bt[self._slot_ids, np.minimum(vp, W - 1)],
                             E.n_pages).astype(np.int32)
            noff = np.where(active_mask, np.clip(t, 0, None) % E.page_size,
                            0).astype(np.int32)
            # context-adaptive table width: slice the Va2Pa table to a pow2
            # bucket of the batch's live-page high-water mark so decode
            # attention (kernel grid or gathered width alike) tracks actual
            # context, not max_context (reuses the prefill bucketing)
            if E.decode_bucket and W > 16:
                from repro.serving.prefill import decode_table_bucket
                bt = bt[:, :decode_table_bucket(self.batcher.max_live_pages(),
                                                W)]
            if self._decode_jit is None:
                def decode_step(params, state, tokens, bt, ctx, npage, noff,
                                run):
                    return MDL.decode_step(self.cfg, params, state, tokens,
                                           bt, ctx, npage, noff, run=run,
                                           rt=self.rt)
                self._decode_jit = jax.jit(decode_step)
            self._count_decode_work(active_mask.astype(np.int32), 1,
                                    bt.shape[1])

        # ``run`` masks the recurrent-state advance: idle and mid-chunk-
        # prefill slots must not absorb their stale pending token (their
        # attention KV writes already drop via the out-of-bounds npage)
        with self._phase("decode_s", "decode_sample"), \
                self.timing.compiling(self._decode_jit):
            logits, self.state = self._decode_jit(
                self.params, self.state, jnp.asarray(self.tokens),
                jnp.asarray(bt), jnp.asarray(ctx), jnp.asarray(npage),
                jnp.asarray(noff), jnp.asarray(active_mask))
            logits = np.asarray(logits)
            self.timing.device_syncs += 1
            if self.sample is not None:  # legacy per-row callable: active only
                nxt = np.zeros((E.n_slots,), np.int32)
                nxt[active] = self._sample_rows(logits[active])
            else:
                nxt = self._sample_rows(logits)

        # ---- EOS / budget bookkeeping, vectorized ----------------------
        with self._phase("host_s", "bookkeep"):
            # invalid-logits quarantine (this path sees the real host
            # logits): a non-finite row — or an injected NaN plan — means
            # the sample is garbage; the token is not emitted and the
            # request goes terminal at the next tick's safe point
            quar = np.zeros((E.n_slots,), bool)
            finite = (np.isfinite(logits[active]).all(axis=1)
                      if self.nan_guard
                      else np.ones((len(active),), bool))
            for i, s in enumerate(active):
                rid = self.batcher.slots[s].req_id
                if (self.faults.enabled
                        and self.faults.fire("nan_logits", key=rid)) \
                        or not finite[i]:
                    quar[s] = True
                    self._abort_req.setdefault(rid, "nan")
            gen = np.asarray([0 if r is None else r.generated
                              for r in self.batcher.slots], np.int32)
            budget = np.asarray([1 if r is None else r.max_new_tokens
                                 for r in self.batcher.slots], np.int32)
            self.tokens = np.where(active_mask & ~quar, nxt,
                                   self.tokens).astype(np.int32)
            finished = active_mask & ~quar \
                & ((nxt == E.eos_token) | (gen >= budget))
            emitted = [s for s in active if not quar[s]]
            for s in emitted:
                self.outputs[self.batcher.slots[s].req_id].append(int(nxt[s]))
            self.timing.decode_tokens += len(emitted)
            if self.tel.enabled:
                tnow = self.clock()
                for s in emitted:
                    self.tel.on_tokens(self.batcher.slots[s].req_id, 1, tnow)
                self.tel.on_horizon(float(ctx[emitted].sum()))
            # the device slot mirror did not see this host-side advance; a
            # later fused run() must re-upload these rows (and process this
            # mask)
            self.batcher.dirty.update(active)
            self._pending_fin = finished
        return finished

    # ---- fused multi-step path ---------------------------------------
    def _make_fused(self):
        E, cfg, rt = self.ecfg, self.cfg, self.rt
        if self.sample is not None:
            # legacy per-row host callable: adapted into the scan-sampler
            # signature via an ordered host callback, so run() keeps the
            # fused multi-step path instead of pinning to per-token decode
            from repro.serving.sampling import make_callback_sampler
            sample = make_callback_sampler(self.sample)
        else:
            sample = make_scan_sampler(E.sampler, temperature=E.temperature,
                                       top_k=E.top_k)

        def decode_horizon(params, state, tokens, bt, ctx, rem, allow, key,
                           *, horizon, width):
            return MDL.decode_multi(
                cfg, params, state, tokens, bt, ctx, rem, allow, key,
                horizon=horizon, table_width=width, page_size=E.page_size,
                n_pages=E.n_pages, eos_token=E.eos_token, sample=sample,
                rt=rt)

        donate = (1, 2, 4, 5, 7) if self._donate else ()
        return jax.jit(decode_horizon, static_argnames=("horizon", "width"),
                       donate_argnums=donate)

    # ---- speculative decode: propose / catch-up / verify ----------------
    def _make_spec(self):
        """Compile the speculative pair: the draft's proposal scan and the
        target's one-pass multi-query verify. Argument donation mirrors the
        fused scan but is split across the two dispatches: propose may only
        donate the draft state and key (tokens/ctx are re-read by verify);
        verify donates everything it replaces. Single-stream execution
        order (propose enqueued first) makes the verify-side aliasing of
        shared inputs safe."""
        from repro.serving.sampling import make_verifier
        E, rt = self.ecfg, self.rt
        dcfg = self.draft_cfg
        sample = make_scan_sampler(E.sampler, temperature=E.temperature,
                                   top_k=E.top_k)
        verifier = make_verifier(E.sampler, temperature=E.temperature,
                                 top_k=E.top_k)
        need_q = E.sampler != "greedy"

        def spec_propose(dparams, dstate, tokens, bt, ctx, allow, dkey, *,
                         horizon, width):
            return MDL.draft_propose(
                dcfg, dparams, dstate, tokens, bt, ctx, allow, dkey,
                horizon=horizon, table_width=width, page_size=E.page_size,
                n_pages=E.n_pages, sample=sample, need_q=need_q, rt=rt)

        def spec_verify(params, state, tokens, proposals, qlogits, bt, ctx,
                        rem, allow, key, *, horizon, width):
            return MDL.decode_verify(
                self.cfg, params, state, tokens, proposals, qlogits, bt,
                ctx, rem, allow, key, horizon=horizon, table_width=width,
                page_size=E.page_size, n_pages=E.n_pages,
                eos_token=E.eos_token, verifier=verifier, rt=rt)

        dp = (1, 6) if self._donate else ()
        dv = (1, 2, 4, 6, 7, 9) if self._donate else ()
        return (jax.jit(spec_propose, static_argnames=("horizon", "width"),
                        donate_argnums=dp),
                jax.jit(spec_verify, static_argnames=("horizon", "width"),
                        donate_argnums=dv))

    def _draft_catchup(self, active) -> None:
        """Bring the draft pool level with the target before proposing:
        batched draft prefill of every active slot's tokens in
        ``[dlen, ctx-1)`` (positions the draft has not absorbed — fresh
        admissions, preemption resumes, prefix-cache hits and post-swap-in
        pages all land here because (re)admission resets dlen to 0; steady
        state needs nothing or one token after a partially-accepted round).
        One async dispatch, shapes bucketed like ``prefill_suffix``."""
        from repro.serving.prefill import _make_chunk_fn, _suffix_bucket
        E = self.ecfg
        ctx = self.batcher.context_lens()
        needy, needs = [], []
        for s in active:
            req = self.batcher.slots[s]
            dlen = self._dlen.get(req.req_id, 0)
            need = int(ctx[s]) - 1 - dlen
            if need > 0:
                needy.append((s, req, dlen))
                needs.append(need)
        if not needy:
            return
        if self._catchup_jit is None:
            self._catchup_jit = _make_chunk_fn(self.draft_cfg, self.rt)
        blen = _suffix_bucket(max(needs), max(E.max_prefill, E.page_size))
        rows = 1
        while rows < len(needy):
            rows *= 2
        toks = np.zeros((rows, blen), np.int32)
        starts = np.zeros((rows,), np.int32)
        lens = np.zeros((rows,), np.int32)
        bt_rows = np.zeros((rows, self.pool_spec.max_pages_per_req),
                           np.int32)
        W = self.pool_spec.max_pages_per_req
        host_bt = self.batcher.block_tables(W)
        for i, ((s, req, dlen), need) in enumerate(zip(needy, needs)):
            full = np.concatenate(
                [self.prompts[req.req_id],
                 np.asarray(self.outputs[req.req_id], np.int32)])
            toks[i, :need] = full[dlen:dlen + need]
            starts[i] = dlen
            lens[i] = need
            bt_rows[i] = host_bt[s]
            self._dlen[req.req_id] = dlen + need
        # pad rows repeat the last real row with valid_len 0 — their pool
        # writes drop, exactly like group-prefill end padding
        for i in range(len(needy), rows):
            bt_rows[i] = bt_rows[len(needy) - 1]
        with self.timing.compiling(self._catchup_jit):
            _, dstate = self._catchup_jit(
                self.draft_params, {"pool": self._dstate["pool"]},
                jnp.asarray(toks), jnp.asarray(bt_rows), jnp.asarray(starts),
                jnp.asarray(np.maximum(lens - 1, 0)), jnp.asarray(lens))
        self._dstate["pool"] = dstate["pool"]

    def _dispatch_spec(self, active, allow, K: int, width: int) -> None:
        """Dispatch one speculative round (draft scan + verify pass) without
        blocking — the tick's single sync stays at next tick's collect."""
        if self._spec_jits is None:
            self._spec_jits = self._make_spec()
        propose, verify = self._spec_jits
        G = K - 1
        allow_j = jnp.asarray(allow)
        with self.timing.compiling(propose):
            prop, qlog, self._dstate, self._dkey = propose(
                self.draft_params, self._dstate, self.dev.tokens,
                self.dev.bt, self.dev.ctx, allow_j, self._dkey, horizon=G,
                width=width)
        with self.timing.compiling(verify):
            toks, emit, fin, self.state, self.dev.tokens, self.dev.ctx, \
                self.dev.rem, self.dev.key, acc = verify(
                    self.params, self.state, self.dev.tokens, prop, qlog,
                    self.dev.bt, self.dev.ctx, self.dev.rem, allow_j,
                    self.dev.key, horizon=G, width=width)
        nprop = np.clip(allow - 1, 0, G).astype(np.int32)
        self._inflight = (toks, emit, fin,
                          [(s, self.batcher.slots[s]) for s in active],
                          (acc, nprop))

    def _sync_device_slots(self) -> None:
        """Mirror the scheduler's dirty rows into the device-resident slot
        state — the incremental config-buffer update (rows touched by
        admission, page growth, chunk completion or frees; continuing slots
        were already advanced ON DEVICE by the previous horizon)."""
        dirty = self.batcher.take_dirty()
        if not dirty:
            return
        W = self.pool_spec.max_pages_per_req
        rows = np.ascontiguousarray(self.batcher.block_tables(W)[dirty])
        ctx_v = self.batcher.context_lens()[dirty]
        tok_v = self.tokens[dirty]
        rem_v = np.zeros((len(dirty),), np.int32)
        for i, s in enumerate(dirty):
            req = self.batcher.slots[s]
            if req is not None and req.prefill_done:
                rem_v[i] = max(0, req.max_new_tokens - req.generated + 1)
        with self.timing.compiling(self.dev._patch):
            self.dev.patch(dirty, rows, ctx_v.astype(np.int32),
                           tok_v.astype(np.int32), rem_v)

    def _collect_horizon(self):
        """Sync point: block on the in-flight horizon's token readback (the
        ONE host<->device rendezvous per K decode steps) and fold the
        emissions into outputs / request bookkeeping."""
        if self._inflight is None:
            return None
        toks, emit, fin, pairs, spec = self._inflight
        self._inflight = None
        hctx, self._horizon_ctx = self._horizon_ctx, None
        with self._phase("decode_s", "collect"):
            toks, emit, fin = (np.asarray(toks), np.asarray(emit),
                               np.asarray(fin))
            acc = np.asarray(spec[0]) if spec is not None else None
        # the fold of the horizon's emissions into host bookkeeping: a
        # phase of its own, so a device gap that spans it is named
        with self._phase("decode_s", "fold"):
            self.timing.device_syncs += 1
            tel = self.tel.enabled
            # one readback timestamp for the whole horizon: every emission
            # in it became host-visible at the same sync, and the
            # per-request records must reproduce the first_tok_t-based TTFT
            # exactly
            tnow = self.clock()
            tok_ctx = 0.0
            finished = np.zeros((self.ecfg.n_slots,), bool)
            for slot, req in pairs:
                ts = toks[emit[:, slot], slot]
                if not len(ts):            # pool-starved to zero steps
                    continue
                # invalid-logits quarantine: an injected NaN plan, or sampled
                # ids outside the logits width (the fused path cannot see the
                # device-side logits, so garbage shows up as out-of-range ids).
                # The horizon's tokens are NOT folded — the request goes
                # terminal at this tick's safe point with reason "nan"
                if (self.faults.enabled
                        and self.faults.fire("nan_logits", key=req.req_id)) \
                        or (self.nan_guard
                            and (int(ts.min()) < 0
                                 or int(ts.max()) >= self.cfg.padded_vocab)):
                    self._abort_req.setdefault(req.req_id, "nan")
                    continue
                self.outputs[req.req_id].extend(int(t) for t in ts)
                self.first_tok_t.setdefault(req.req_id, tnow)
                if tel:
                    self.tel.on_tokens(req.req_id, int(len(ts)), tnow)
                    if hctx is not None:
                        tok_ctx += len(ts) * float(hctx[slot])
                if spec is not None:
                    # draft-pool coverage after the round: the draft
                    # absorbed its proposals' KV up to the accepted/emitted
                    # frontier (req.total_len is still the dispatch-time
                    # context here — ``generated`` advances below)
                    nprop = int(spec[1][slot])
                    self._dlen[req.req_id] = req.total_len - 1 \
                        + min(len(ts), nprop)
                    self.spec_rounds += 1
                    self.spec_proposed += nprop
                    self.spec_accepted += int(acc[slot])
                    if tel:
                        self.tel.on_spec(req.req_id, nprop, int(acc[slot]))
                # the tick's step() already reserved one token; the rest of the
                # horizon's emissions land here
                req.generated += len(ts) - 1
                self.tokens[slot] = int(ts[-1])
                finished[slot] = bool(fin[slot])
                if fin[slot] and self._dstate is not None:
                    self._dlen.pop(req.req_id, None)
                self.timing.decode_tokens += int(len(ts))
            if tel:
                self.tel.on_horizon(tok_ctx)
        return finished

    def _step_fused(self) -> None:
        """One pipelined tick of the fused multi-step path.

        Order is the DCS ping-pong applied to the host loop: with the
        previous horizon still in flight, do the host work that does NOT
        depend on its results (cache swap-out drain / watermark offload,
        radix-peek prefetch for queued candidates), only then sync, and end
        by dispatching the next horizon WITHOUT blocking on it.
        """
        E = self.ecfg
        # ---- overlap window: result-independent host work --------------
        with self._phase("host_s", "overlap"):
            if self.cache is not None:
                # an injected swap-tier stall skips the maintenance drain
                # for the tick — pending swap-outs queue up, exactly the
                # back-pressure a stalled host DMA engine produces
                if not (self.faults.enabled
                        and self.faults.fire("swap_stall")):
                    self.cache.maintain()
            self._drain_snapshots()
            if self._inflight is not None and self.batcher.queue:
                self.batcher.prefetch_peeks(limit=2 * E.n_slots)

        # ---- sync: fold the horizon's tokens into host bookkeeping -----
        finished = self._collect_horizon()
        if finished is None:
            finished, self._pending_fin = self._pending_fin, None

        # ---- safe point: injection, deadlines, aborts, degradation -----
        with self._phase("host_s", "faults"):
            self._process_faults(finished)

        # ---- schedule + prefill ----------------------------------------
        with self._phase("host_s", "schedule"):
            admitted, active = self.batcher.step(finished)
            if self.cache is not None and self.cache.has_pending:
                # swap-in scatters / CoW copies queued by this tick's
                # admissions must land before prefill or decode read the
                # pages
                self.state["pool"] = self.cache.apply_pending(
                    self.state["pool"])
        if admitted or self.prefiller.busy:
            with self._phase("prefill_s", "prefill"):
                active = self.prefiller.run(admitted, active)
        self.timing.steps += 1
        if not active:
            return

        # ---- horizon reservation + incremental config update -----------
        with self._phase("host_s", "config"):
            # degradation bit 2 demotes speculative decode to the plain
            # fused scan (draft state parks; _dlen goes stale but is only
            # consulted behind ``spec``)
            spec = self._dstate is not None and not (self.degraded_mode & 2)
            if spec:
                # the draft must re-absorb any context it did not write —
                # every (re)admission starts from zero (swap-in / CoW /
                # snapshot restore only rebuild the target's pages)
                for _s, req in admitted:
                    self._dlen[req.req_id] = 0
                K = max(1, E.spec_horizon + 1)
            else:
                K = max(1, E.decode_horizon)
            cap = self.prefiller.max_horizon
            if cap is not None:
                K = min(K, cap)
            if (self.degraded_mode & 1) and not spec:
                K = 1              # bit 1: per-token trajectory, no reserve
            allow = self.batcher.reserve_horizon(active, K,
                                                 gentle=E.reserve_gentle)
            self._sync_device_slots()
            W = self.pool_spec.max_pages_per_req
            width = W
            if E.decode_bucket and W > 16:
                from repro.serving.prefill import decode_table_bucket
                width = decode_table_bucket(self.batcher.max_live_pages(), W)

        # ---- dispatch; do NOT block ------------------------------------
        with self._phase("decode_s", "spec_dispatch" if spec else "dispatch"):
            if self.tel.enabled:
                self._horizon_ctx = self.batcher._ctx.copy()
            if spec:
                self._draft_catchup(active)
                self._dispatch_spec(active, allow, int(K), int(width))
            else:
                if self._fused_jit is None:
                    self._fused_jit = self._make_fused()
                with self.timing.compiling(self._fused_jit):
                    toks, emit, fin, self.state, self.dev.tokens, \
                        self.dev.ctx, self.dev.rem, self.dev.key = \
                        self._fused_jit(
                            self.params, self.state, self.dev.tokens,
                            self.dev.bt, self.dev.ctx, self.dev.rem,
                            jnp.asarray(allow), self.dev.key,
                            horizon=int(K), width=int(width))
                # counted once the horizon is on its way to the device
                self._count_decode_work(allow, int(K), int(width))
                self._inflight = (toks, emit, fin,
                                  [(s, self.batcher.slots[s])
                                   for s in active],
                                  None)

    def tick(self) -> None:
        """One pipelined fused tick plus the serving-snapshot cadence
        (public driver API; chaos drivers call this instead of run() so
        they can kill the engine between ticks)."""
        self._step_fused()
        E = self.ecfg
        if E.snapshot_every and E.snapshot_dir \
                and self._tick_no % E.snapshot_every == 0:
            self.save_snapshot()

    def run(self, max_steps: int = 10_000) -> dict[int, list[int]]:
        for _ in range(max_steps):
            if self._inflight is None and self.batcher.done():
                break
            self.tick()
        if self._inflight is not None:   # max_steps hit mid-horizon
            self._pending_fin = self._collect_horizon()
        return self.outputs

    # ---- cross-engine request movement (serving/cluster.py) -----------
    def quiesce(self) -> None:
        """Bring the engine to the post-collect quiescent frame: fold any
        in-flight horizon into host bookkeeping (its finish mask joins the
        pending one for the next scheduler step) and drain pending device
        snapshots. Snapshots, handoffs and teardowns all require this
        frame; costs one extra device sync when a horizon was in flight."""
        if self._inflight is not None:
            fin = self._collect_horizon()
            if fin is not None:
                self._pending_fin = fin if self._pending_fin is None \
                    else (self._pending_fin | fin)
        self._drain_snapshots()

    def extract_request(self, req_id: int):
        """Pull a live request out of this engine for a cross-engine
        handoff: quiesce, capture its snapshot-entry frame (written KV
        pages + recurrent carry when warm), then tear it down locally
        (reason ``handoff`` — its private pages free; prefixes it already
        published to the radix cache survive under the tree's own refs).
        Returns ``(entry, arrays)``, or None when the request is not live
        or finished during the quiesce (its output is already complete —
        nothing to move)."""
        self.quiesce()
        s, req = self._find_request(req_id)
        if req is None:
            return None
        if s is not None and self._pending_fin is not None \
                and self._pending_fin[s]:
            return None
        ent, arrs = self._snapshot_entry(req, s)
        self._teardown(req_id, "handoff")
        return ent, arrs

    def adopt_request(self, req_id: int, ent: dict, prompt, out, *,
                      kv=None, rows=None):
        """Register a request arriving from OUTSIDE the submit() path — a
        snapshot restore or a cross-engine handoff. ``ent`` is the scalar
        snapshot-entry frame (``_snapshot_entry``); ``out`` the tokens
        already streamed to the client. Warm entries seed the preemption-
        snapshot machinery so the prefiller restores the KV/carry instead
        of recomputing; slot-mode prefill (the recompute reference) and
        cold entries re-prefill deterministically — token-identical either
        way. Returns the constructed Request (already queued)."""
        self.prompts[req_id] = np.asarray(prompt, np.int32)
        self.outputs[req_id] = [int(t) for t in out]
        now = self.submit_t[req_id] = self.clock()
        # re-synthesize a minimal spec so policies/tracker see the adopted
        # request's tier (SLO latency targets don't survive a handoff —
        # the timestamps restart in the adopting engine's clock frame)
        spec = RequestSpec(req_id, self.prompts[req_id],
                           int(ent["max_new"]),
                           priority=int(ent.get("priority", 0)))
        self.tel.on_submit(req_id, len(self.prompts[req_id]),
                           int(ent["max_new"]), now, spec=spec)
        req = Request(req_id, int(ent["prompt_len"]), int(ent["max_new"]),
                      priority=spec.priority, submit_t=now, spec=spec)
        if self.prefiller.name == "chunked":
            req.chunked_prefill = True
            req.prefill_done = False
        warm_ok = self.ecfg.state_resume and self.prefiller.name != "slot"
        if ent.get("state") == "warm" and warm_ok:
            snap: dict[str, Any] = {"len": int(ent["depth"])}
            if kv is not None:
                snap["kv"] = tuple(kv)
            if rows is not None:
                snap["rows"] = rows
            self.rsnaps[req_id] = snap
        self.batcher.submit(req)
        return req

    # ---- crash-consistent serving snapshots ---------------------------
    def _snapshot_entry(self, req, s: int | None):
        """(scalar-manifest entry, array dict) for one live request.
        Running warm slots save their written KV pages (and recurrent
        carry) at the quiescent depth; the requeue arithmetic mirrors
        ``drain_slot`` — saved ``prompt_len`` equals the restore depth, so
        a warm restore rides the prefiller's full-restore path (no model
        call) and continues token-identically. Everything else is saved
        cold: a deterministic re-prefill of the reconstructable context."""
        E = self.ecfg
        rid = req.req_id
        out = self.outputs[rid]
        arrs: dict[str, Any] = {
            "prompt": self.prompts[rid],
            "out": np.asarray(out, np.int32)}
        ent = {"prompt_len": int(req.prompt_len),
               "max_new": int(req.max_new_tokens), "state": "cold"}
        if s is not None and req.prefill_done and out and req.kv_written:
            P = len(self.prompts[rid])
            g = min(int(req.generated),
                    max(0, len(out) - 1 - (req.prompt_len - P)))
            depth = req.prompt_len + g
            ent = {"prompt_len": int(depth),
                   "max_new": max(1, int(req.max_new_tokens) - g),
                   "state": "warm", "depth": int(depth)}
            if "pool" in self.state:
                from repro.core.paged_kv import gather_pages
                n = -(-depth // E.page_size)
                pages = np.asarray(self.batcher.block_table_row(s)[:n])
                k, v = gather_pages(self.state["pool"]["k"],
                                    self.state["pool"]["v"],
                                    jnp.asarray(pages))
                arrs["kv_k"], arrs["kv_v"] = np.asarray(k), np.asarray(v)
            if self.has_rstate:
                arrs["rows"] = jax.tree.map(
                    np.asarray, MDL.gather_rstate(self.state, [s]))
        elif s is None and req.req_id in self.rsnaps:
            # queued with a preemption snapshot (already host numpy after
            # the drain): persist it so the restore resumes, not recomputes
            snap = self.rsnaps[rid]
            ent = {"prompt_len": int(req.prompt_len),
                   "max_new": int(req.max_new_tokens),
                   "state": "warm", "depth": int(snap["len"])}
            if "kv" in snap:
                arrs["kv_k"], arrs["kv_v"] = snap["kv"]
            if "rows" in snap:
                arrs["rows"] = snap["rows"]
        if req.priority:
            ent["priority"] = int(req.priority)
        return ent, arrs

    def save_snapshot(self, ckpt_dir=None):
        """Write a crash-consistent serving checkpoint: every live
        request's prompt/output tokens plus, for warm slots, the written KV
        pages and recurrent carry at the quiescent depth — enough for a
        fresh engine to finish every in-flight request token-identically
        (greedy). Quiesces the in-flight horizon first (one extra sync on
        ticks that snapshot); uses the manifest-gated
        ``runtime/checkpoint.py`` layout, so a crash mid-save can never
        corrupt the latest restorable step."""
        E = self.ecfg
        d = ckpt_dir or E.snapshot_dir
        if d is None:
            return None
        self.quiesce()
        order: list[int] = []
        ents: dict[str, dict] = {}
        arrs: dict[str, dict] = {}
        for s, req in enumerate(self.batcher.slots):
            if req is None:
                continue
            if self._pending_fin is not None and self._pending_fin[s]:
                ent = {"state": "done", "max_new": int(req.max_new_tokens),
                       "prompt_len": int(req.prompt_len)}
                a = {"prompt": self.prompts[req.req_id],
                     "out": np.asarray(self.outputs[req.req_id], np.int32)}
            else:
                ent, a = self._snapshot_entry(req, s)
            order.append(req.req_id)
            ents[str(req.req_id)] = ent
            arrs[str(req.req_id)] = a
        for req in self.batcher.queue:
            ent, a = self._snapshot_entry(req, None)
            order.append(req.req_id)
            ents[str(req.req_id)] = ent
            arrs[str(req.req_id)] = a
        from repro.runtime import checkpoint as CKPT
        tree = {"reqs": arrs, "dev_key": np.asarray(self.dev.key)}
        path = CKPT.save(d, self._tick_no, tree,
                         extra={"order": order, "reqs": ents,
                                "tick": self._tick_no},
                         keep=E.snapshot_keep)
        self.snapshot_saves += 1
        return path

    def _rows_from_nested(self, nd):
        """Rebuild a one-slot recurrent-carry pytree from its "/"-keyed
        nested-dict form (a snapshot shard or handoff payload). The carry
        contains tuples/lists the nesting flattened to string indices —
        unflatten against a live one-slot gather so the structure
        round-trips exactly."""
        like = MDL.gather_rstate(self.state, [0])
        flat, treedef = jax.tree_util.tree_flatten_with_path(like)
        leaves = []
        for path, _leaf in flat:
            d = nd
            for p in path:
                d = d[str(getattr(p, "key", getattr(p, "idx", p)))]
            leaves.append(d)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def restore_snapshot(self, ckpt_dir=None, step: int | None = None):
        """Rebuild the serving state of the latest (or given) snapshot into
        THIS engine — call on a freshly constructed engine with the same
        model/engine config, then ``run()``: warm requests restore their KV
        (and carry) and continue mid-stream, cold ones re-prefill
        deterministically, done ones just republish their outputs.

        Every candidate step is FULLY validated (manifest parse, shard
        load, per-array crc32) before anything is applied: a torn or
        bit-flipped snapshot is counted in ``snapshot_rejects`` and
        skipped, falling back to the next-older step — restore degrades,
        it never half-applies. Returns the restored step, or None when no
        intact snapshot exists (the caller's cold-start path)."""
        from repro.runtime import checkpoint as CKPT
        d = ckpt_dir or self.ecfg.snapshot_dir
        if d is None:
            return None
        cands = ([step] if step is not None
                 else sorted(CKPT.valid_steps(d), reverse=True))
        for cand in cands:
            if not CKPT.verify_step(d, cand):
                self.snapshot_rejects += 1
                continue
            return self._restore_step(d, cand)
        return None

    def _restore_step(self, ckpt_dir, step: int):
        """Apply one verified snapshot step (see ``restore_snapshot``)."""
        import json as _json
        from pathlib import Path as _Path
        step_dir = _Path(ckpt_dir) / f"step_{step:08d}"
        extra = _json.loads(
            (step_dir / "manifest.json").read_text())["extra"]
        data = np.load(step_dir / "shard_00000.npz")
        nested: dict = {}
        for key in data.files:                 # "/"-joined tree paths back
            parts = key.split("/")             # into per-request dicts
            dd = nested
            for p in parts[:-1]:
                dd = dd.setdefault(p, {})
            dd[parts[-1]] = data[key]
        if "dev_key" in nested:
            self.dev.key = jnp.asarray(nested["dev_key"])
        reqs = nested.get("reqs", {})
        for rid_s in map(str, extra["order"]):
            ent = extra["reqs"][rid_s]
            rid = int(rid_s)
            a = reqs.get(rid_s, {})
            prompt = np.asarray(a["prompt"], np.int32)
            out = [int(t) for t in np.asarray(a.get("out", ()), np.int32)]
            if ent["state"] == "done":         # finished during quiesce:
                self.prompts[rid] = prompt     # republish, don't re-run
                self.outputs[rid] = out
                self.submit_t[rid] = self.clock()
                continue
            kv = (a["kv_k"], a["kv_v"]) if "kv_k" in a else None
            rows = (self._rows_from_nested(a["rows"])
                    if "rows" in a else None)
            self.adopt_request(rid, ent, prompt, out, kv=kv, rows=rows)
        self.snapshot_restores += 1
        return step
