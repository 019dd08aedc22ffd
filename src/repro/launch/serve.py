"""End-to-end serving driver: continuous-batching decode with the DPA paged
cache over a LongBench-like request trace.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --requests 16 --task musique --max-context 256

runs the architecture's ``reduced()`` float32 twin (CPU-sized); ``--full``
builds the published config at its own widths and dtype instead, e.g. on
one TPU v5e:

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --full \
      --requests 8 --slots 8 --pages 2048 --max-context 8192

Reports achieved average batch (the paper's Fig. 4(b) metric), token
throughput, host overhead, preemptions, and page-pool balance. ``--static``
switches to baseline-PIM static allocation for the comparison;
``--prefill-mode`` picks slot / batched / chunked prefill (every arch
family, including recurrent hybrids like xlstm/zamba2 via state-carrying
chunk prefill) and ``--sched-policy`` the admission policy (see
repro.serving). Recurrent/enc-dec archs snapshot their carry on preemption
and restore on resume (``--no-state-resume`` reverts to full recompute).
``--decode-horizon K`` fuses K decode steps (decode + on-device sampling)
under one jit per tick — the host syncs once per horizon; greedy outputs
are identical for every K.

``--shared-frac f`` makes every request start with a common system prompt
covering fraction ``f`` of its tokens (multi-tenant shared-prefix traffic);
``--prefix-cache`` turns on the radix KV sharing and ``--host-pages N``
adds the host offload tier below the device pool (see repro.kvcache /
docs/kvcache.md). Cache hit/swap counters are reported alongside.

``--disagg`` serves through a disaggregated prefill/decode fleet
(``--engines P+D``, see docs/serving.md): prefill-role engines hand each
finished-prefill request's KV + recurrent carry to a decode-role engine
over a checksummed handoff blob, with router-owned retry/backoff,
timeouts, engine-death recovery (``--snapshot-dir`` enables warm
restores) and backpressure (``--max-queue`` bounds the decode backlog).
``--fault-engine-death`` / ``--fault-handoff-corrupt`` /
``--fault-handoff-torn`` drive the cluster's seeded fault injector.

Telemetry (repro.telemetry, docs/observability.md): ``--metrics-port N``
serves Prometheus text on ``http://127.0.0.1:N/metrics`` (0 = pick an
ephemeral port, printed at startup), ``--request-log records.jsonl``
exports one JSON record per finished request, and ``--stats-every S``
prints a one-line summary every S seconds while serving. With none of
these flags the telemetry layer is the shared no-op: zero extra work, zero
extra device syncs. ``--trace-out DIR`` captures a ``jax.profiler`` trace
of the run into DIR: the engine's ``engine.<phase>`` spans on the host and
the device ops of its named programs (``jit_decode_horizon``, ...), on one
clock; it opens in Perfetto or xprof.
"""
from __future__ import annotations

import argparse
import os
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

from repro.configs import get_config, reduced
from repro.data.pipeline import request_trace
from repro.serving import DecodeEngine, EngineConfig, Request
from repro.serving.policies import available_policies


def make_serve_tel_cfg(args):
    """TelemetryConfig from the CLI flags, or None when every telemetry
    flag is off."""
    from repro.telemetry import TelemetryConfig
    want_metrics = args.metrics_port >= 0 or args.stats_every > 0
    if not (want_metrics or args.request_log):
        return None
    return TelemetryConfig(metrics=True, request_log=args.request_log or None)


def make_serve_telemetry(args):
    """Build the Telemetry facade from the CLI flags — the shared no-op
    when every telemetry flag is off (EngineConfig.telemetry=None path)."""
    from repro.telemetry import make_telemetry
    return make_telemetry(make_serve_tel_cfg(args))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at one fixed path inside
    the checkout (``.jax_cache``, ignored by git): the directory is part of
    what a later run must find again, so it never carries a temporary name,
    a process id or a time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def model_config(arch: str, full: bool, layers: int | None = None):
    """The published config at its own widths and dtype (``full``), or its
    ``reduced()`` float32 twin for CPU runs; ``layers`` cuts the depth."""
    cfg = get_config(arch)
    if full:
        return cfg if layers is None else replace(cfg, n_layers=layers)
    return replace(reduced(cfg, layers=layers), dtype="float32")


def build_engine(args, telemetry=None) -> DecodeEngine:
    cfg = model_config(args.arch, args.full)
    draft_cfg = None
    if args.draft:
        # reduced() drafts share the reduced target's 256-token vocab, so
        # any attention-only arch pairs with any other; full-size cross-arch
        # pairs are vetted by validate_draft_pair at engine construction
        draft_cfg = model_config(args.draft, args.full, layers=1)
    ecfg = EngineConfig(n_slots=args.slots, page_size=args.page,
                        n_pages=args.pages, max_context=args.max_context,
                        static_alloc=args.static, eos_token=-1,
                        prefill_mode=args.prefill_mode,
                        prefill_chunk=args.chunk,
                        sched_policy=args.sched_policy,
                        prefix_cache=args.prefix_cache,
                        host_pages=args.host_pages,
                        use_pallas={"auto": None, "on": True,
                                    "off": False}[args.kernel],
                        kernel_splits=args.kernel_splits,
                        decode_bucket=not args.no_decode_bucket,
                        decode_horizon=args.decode_horizon,
                        draft_config=draft_cfg,
                        spec_horizon=args.spec_horizon,
                        reserve_gentle=args.reserve_gentle,
                        state_resume=not args.no_state_resume,
                        telemetry=telemetry,
                        faults=make_serve_faults(args),
                        max_queue=args.max_queue,
                        default_deadline_s=args.deadline,
                        degrade_after=args.degrade_after,
                        nan_guard=True if args.nan_guard else None,
                        snapshot_dir=args.snapshot_dir or None,
                        snapshot_every=args.snapshot_every)
    return DecodeEngine(cfg, ecfg)


def make_serve_faults(args):
    """FaultConfig from the --fault-* flags; None when no probability is
    set (the engine keeps the shared no-op injector)."""
    ps = dict(alloc_exhaust_p=args.fault_alloc, swap_fail_p=args.fault_swap,
              row_death_p=args.fault_row_death, nan_logits_p=args.fault_nan,
              slow_tick_p=args.fault_slow_tick,
              client_abort_p=args.fault_abort)
    if not any(ps.values()):
        return None
    from repro.runtime.faults import FaultConfig
    return FaultConfig(seed=args.fault_seed, **ps)


def make_cluster_faults(args):
    """Cluster-level FaultConfig (engine death + handoff damage) from the
    --fault-engine-death / --fault-handoff-* flags; None when all are 0."""
    ps = dict(engine_death_p=args.fault_engine_death,
              handoff_corrupt_p=args.fault_handoff_corrupt,
              handoff_torn_p=args.fault_handoff_torn)
    if not any(ps.values()):
        return None
    from repro.runtime.faults import FaultConfig
    return FaultConfig(seed=args.fault_seed, **ps)


def serve_cluster(args) -> int:
    """--disagg path: route the trace through an EngineCluster fleet
    (``--engines P+D`` prefill/decode members) instead of one engine.
    Greedy outputs are token-identical to the single-engine run; the
    summary reports the router's handoff/recovery counters."""
    from repro.serving import ClusterConfig, EngineCluster
    try:
        n_p, n_d = (int(x) for x in args.engines.split("+"))
    except ValueError:
        raise SystemExit(f"--engines wants P+D (e.g. 1+1), "
                         f"got {args.engines!r}")
    tel_cfg = make_serve_tel_cfg(args)
    cfg = model_config(args.arch, args.full)
    ecfg = EngineConfig(n_slots=args.slots, page_size=args.page,
                        n_pages=args.pages, max_context=args.max_context,
                        static_alloc=args.static, eos_token=-1,
                        prefill_mode=args.prefill_mode,
                        prefill_chunk=args.chunk,
                        sched_policy=args.sched_policy,
                        decode_horizon=args.decode_horizon,
                        state_resume=not args.no_state_resume,
                        telemetry=tel_cfg,
                        faults=make_serve_faults(args),
                        degrade_after=args.degrade_after)
    ccfg = ClusterConfig(n_prefill=n_p, n_decode=n_d,
                         max_backlog=args.max_queue,
                         snapshot_dir=args.snapshot_dir or None,
                         snapshot_every=args.snapshot_every,
                         faults=make_cluster_faults(args),
                         telemetry=tel_cfg)
    cl = EngineCluster(cfg, ecfg, ccfg)
    if cl.tel.enabled and args.metrics_port >= 0:
        from repro.telemetry.prom import MetricsServer
        srv = MetricsServer(cl.tel.registry, args.metrics_port)
        print(f"[serve] metrics: {srv.url}", flush=True)
    submit_trace(cl, args)
    t0 = time.time()
    cl.run(100_000)
    dt = time.time() - t0
    toks = sum(len(v) for v in cl.outputs.values())
    done = sum(1 for r in cl.reqs.values() if r["state"] == "done")
    c = cl.counters
    print(f"[serve] mode=disagg engines={n_p}p+{n_d}d "
          f"prefill={args.prefill_mode} "
          f"completed={done}/{args.requests} aborted={len(cl.aborted)} "
          f"tokens={toks} tok/s={toks / max(dt, 1e-9):.1f}", flush=True)
    print(f"[serve] cluster: handoffs={c['handoffs']} ok={c['handoff_ok']} "
          f"retries={c['handoff_retries']} timeouts={c['handoff_timeouts']} "
          f"redispatches={c['handoff_redispatches']} "
          f"redrives={c['handoff_redrives']} deaths={c['engine_deaths']} "
          f"restores={c['engine_restores']} "
          f"redispatched_requests={c['redispatched_requests']} "
          f"shed={c['shed']} degraded_mode={cl.degraded_mode}", flush=True)
    if cl.tel.enabled:
        print(f"[serve] {cl.tel.stats_line()}", flush=True)
        cl.tel.close()
    return done


def submit_trace(eng: DecodeEngine, args) -> list[Request]:
    """Submit ``args.requests`` LongBench-like requests; returns them."""
    rng = np.random.default_rng(0)
    # scale the LongBench length distribution into this toy max_context so
    # its VARIABILITY survives (clamping would park every prompt at the cap,
    # hiding exactly the effect DPA exploits — paper Table 2 / §5.4)
    from repro.data.pipeline import LONGBENCH_STATS
    factor = (args.max_context / 2) / LONGBENCH_STATS[args.task]["mean"]
    trace = request_trace(args.task, args.requests, seed=0,
                          mean_new_tokens=args.mean_new)
    # common system prompt: every request opens with the same token run —
    # the multi-tenant traffic shape radix prefix sharing pays off on
    system = rng.integers(0, eng.cfg.vocab_size,
                          size=args.max_context) if args.shared_frac else None
    reqs = []
    for i, (plen, new) in enumerate(trace):
        plen = max(1, min(int(plen * factor),
                          args.max_context - new - 1))
        prompt = rng.integers(0, eng.cfg.vocab_size, size=plen)
        if system is not None:
            k = min(int(plen * args.shared_frac), plen - 1)
            prompt[:k] = system[:k]
        reqs.append(Request(i, prompt, new))
        eng.submit(reqs[-1])
    return reqs


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--task", default="musique")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--pages", type=int, default=256)
    ap.add_argument("--max-context", type=int, default=512)
    ap.add_argument("--mean-new", type=int, default=24)
    ap.add_argument("--static", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="serve the published config at its own widths and "
                         "dtype instead of its reduced() float32 twin")
    ap.add_argument("--prefill-mode", default="batched",
                    choices=["slot", "batched", "chunked"])
    ap.add_argument("--chunk", type=int, default=32)
    # choices come from the policy registry (serving.policies) — a policy
    # registered with @register_policy is immediately launchable here
    ap.add_argument("--sched-policy", "--policy", dest="sched_policy",
                    default="fcfs", choices=available_policies())
    ap.add_argument("--shared-frac", type=float, default=0.0,
                    help="fraction of each prompt drawn from a common "
                         "system prompt")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix sharing across requests")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="host offload tier capacity in pages (0 = none)")
    ap.add_argument("--kernel", default="auto", choices=["auto", "on", "off"],
                    help="decode-attention pallas kernel path: auto = on "
                         "TPU only (compiled there, interpret mode "
                         "elsewhere)")
    ap.add_argument("--kernel-splits", type=int, default=1,
                    help="split-K partitions of the page axis per kernel "
                         "call")
    ap.add_argument("--no-decode-bucket", action="store_true",
                    help="disable pow2 live-page bucketing of the decode "
                         "block table")
    ap.add_argument("--no-state-resume", action="store_true",
                    help="recurrent/enc-dec archs: disable preemption "
                         "snapshots of the recurrent carry (+written KV), "
                         "falling back to full re-prefill on resume")
    from repro.configs.base import ParallelConfig
    ap.add_argument("--decode-horizon", type=int,
                    default=ParallelConfig().decode_horizon,
                    help="fused decode steps per engine tick (one jit, one "
                         "host sync per horizon); 1 = per-token dispatch")
    ap.add_argument("--draft", default="",
                    help="speculative decoding: arch name for a 1-layer "
                         "draft model proposing tokens the target "
                         "verifies in one multi-query pass (greedy outputs "
                         "stay token-identical)")
    ap.add_argument("--spec-horizon", type=int, default=4,
                    help="max draft proposals per slot per tick (emits up "
                         "to spec-horizon+1 tokens per sync)")
    ap.add_argument("--reserve-gentle", action="store_true",
                    help="horizon reservation declines to evict radix-"
                         "cached pages, degrading the horizon instead")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="serve Prometheus text on this port (0 = "
                         "ephemeral, printed; -1 = off)")
    ap.add_argument("--trace-out", default="",
                    help="capture a jax.profiler trace of the run (engine "
                         "phase spans and device ops) into this directory")
    ap.add_argument("--request-log", default="",
                    help="append one JSON record per finished request to "
                         "this path")
    ap.add_argument("--stats-every", type=float, default=0.0,
                    help="print a telemetry stats line every S seconds "
                         "while serving (0 = off)")
    # ---- disaggregation (docs/serving.md) ----
    ap.add_argument("--disagg", action="store_true",
                    help="serve through a disaggregated prefill/decode "
                         "engine fleet (EngineCluster) with crash-safe KV "
                         "handoff instead of one colocated engine")
    ap.add_argument("--engines", default="1+1",
                    help="fleet shape for --disagg: P+D prefill/decode "
                         "member counts (e.g. 2+2)")
    # ---- robustness (docs/robustness.md) ----
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue: load-shed beyond this "
                         "many waiting requests (0 = unbounded)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="default per-request wall-clock deadline in "
                         "seconds; expired requests are torn down at the "
                         "next tick (0 = none)")
    ap.add_argument("--degrade-after", type=int, default=3,
                    help="fault events before the degradation ladder "
                         "downgrades a tier (spec off, horizon 1, host "
                         "tier dropped); 0 disables")
    ap.add_argument("--nan-guard", action="store_true",
                    help="quarantine requests whose logits/sampled ids go "
                         "non-finite or out of range (auto-armed when "
                         "fault injection is on)")
    ap.add_argument("--snapshot-dir", default="",
                    help="write crash-consistent serving snapshots here")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot cadence in engine ticks (0 = off)")
    ap.add_argument("--restore", action="store_true",
                    help="restore the latest snapshot from --snapshot-dir "
                         "before serving (resumes in-flight requests)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic fault injector")
    for flag, kind in (("--fault-alloc", "page-pool exhaustion"),
                       ("--fault-swap", "host swap-in failure"),
                       ("--fault-row-death", "serving-row death"),
                       ("--fault-nan", "NaN-logits quarantine"),
                       ("--fault-slow-tick", "straggler tick"),
                       ("--fault-abort", "client abort"),
                       ("--fault-engine-death", "pool engine death "
                        "(--disagg)"),
                       ("--fault-handoff-corrupt", "handoff byte flip "
                        "(--disagg)"),
                       ("--fault-handoff-torn", "handoff truncation "
                        "(--disagg)")):
        ap.add_argument(flag, type=float, default=0.0,
                        help=f"per-decision injection probability: {kind}")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if not args.trace_out:
        return serve_engine(args)
    import jax
    jax.profiler.start_trace(args.trace_out)
    try:
        return serve_engine(args)
    finally:
        jax.profiler.stop_trace()
        print(f"[serve] trace: {args.trace_out} (jax.profiler)", flush=True)


def serve_engine(args):
    if args.disagg:
        return serve_cluster(args)
    tel = make_serve_telemetry(args)
    eng = build_engine(args, telemetry=tel)
    if tel.enabled and args.metrics_port >= 0:
        from repro.telemetry.prom import MetricsServer
        srv = MetricsServer(tel.registry, args.metrics_port)
        print(f"[serve] metrics: {srv.url}", flush=True)
    stop_stats = None
    if tel.enabled and args.stats_every > 0:
        import threading
        stop_stats = threading.Event()

        def _ticker():
            while not stop_stats.wait(args.stats_every):
                print(f"[serve] {tel.stats_line()}", flush=True)

        threading.Thread(target=_ticker, name="stats-line",
                         daemon=True).start()
    if args.restore and args.snapshot_dir:
        step = eng.restore_snapshot()
        print(f"[serve] restored snapshot step={step} from "
              f"{args.snapshot_dir}" if step is not None else
              f"[serve] no snapshot in {args.snapshot_dir}; cold start",
              flush=True)
    else:
        submit_trace(eng, args)

    t0 = time.time()
    eng.run(100_000)
    dt = time.time() - t0
    if stop_stats is not None:
        stop_stats.set()
    st = eng.batcher.stats
    toks = sum(len(v) for v in eng.outputs.values())
    tm = eng.timing.as_dict()
    print(f"[serve] mode={'static' if args.static else 'lazy(DPA)'} "
          f"prefill={eng.prefiller.name} policy={eng.batcher.policy.name} "
          f"completed={st.completed}/{args.requests} "
          f"avg_batch={st.avg_batch:.2f} preempted={st.preempted} "
          f"tokens={toks} tok/s={toks / max(dt, 1e-9):.1f} "
          f"host_us/step={tm['host_us_per_step']:.0f} "
          f"horizon={args.decode_horizon} "
          f"syncs/tok={tm['syncs_per_token']:.3f}", flush=True)
    bal = eng.alloc.shard_balance()
    print(f"[serve] page balance per shard: max={bal.max()} min={bal.min()}",
          flush=True)
    if eng.has_rstate:
        print(f"[serve] rstate: snapshots={eng.rstate_snapshots} "
              f"restores={eng.rstate_restores}", flush=True)
    if eng.draft_cfg is not None:
        acc = 1 + eng.spec_accepted / max(1, eng.spec_rounds)
        print(f"[serve] spec: draft={args.draft} rounds={eng.spec_rounds} "
              f"accepted={eng.spec_accepted}/{eng.spec_proposed} "
              f"accept_len_mean={acc:.2f}", flush=True)
    if eng.faults.enabled or eng.aborted or eng.degraded_mode \
            or eng.snapshot_saves:
        print(f"[serve] robustness: aborted={len(eng.aborted)} "
              f"{dict(eng.abort_counts)} faults={eng.faults.total_fired} "
              f"migrated={st.migrated} degraded_mode={eng.degraded_mode} "
              f"snapshots={eng.snapshot_saves}", flush=True)
    if eng.cache is not None:
        cs = eng.cache.stats_dict()
        print(f"[serve] kvcache: hits={cs['hits']}/{cs['lookups']} "
              f"reused_tokens={cs['hit_tokens']} cow={cs['cow_copies']} "
              f"evicted={cs['evicted_pages']} "
              f"swap_out={cs.get('swapped_out_pages', 0)} "
              f"swap_in={cs.get('swapped_in_pages', 0)}", flush=True)
    if tel.enabled:
        print(f"[serve] {tel.stats_line()}", flush=True)
        sm = tel.summary()
        if "ttft_p50_ms" in sm:
            print(f"[serve] latency: ttft p50/p90/p99 = "
                  f"{sm['ttft_p50_ms']:.1f}/{sm['ttft_p90_ms']:.1f}/"
                  f"{sm['ttft_p99_ms']:.1f} ms  tpot p50 = "
                  f"{sm.get('tpot_p50_ms', 0):.2f} ms", flush=True)
        tel.close()
    return st.avg_batch


if __name__ == "__main__":
    enable_compile_cache()
    main()
