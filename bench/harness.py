"""One run of one cell: set-up, the timed window, the correctness check
and the result line.

Set-up builds the served engine (``DecodeEngine``: batched prefill, the
fused decode horizon, the compiled Pallas paged-attention kernel on a
TPU) around weights made on the device from the seed by the
configuration's architecture module (``bench/arch/<name>.py``, which gives
the dims, weights, reference, work and kernel names). The cell's traffic
kind (``bench/traffic/<kind>.py``, named by its mix) makes the requests,
sets them up and warms every program shape the window can reach, then
drives the engine through the window, all through ``Run``, which stamps
submissions, tokens and pages in use on the host clock. After the window
the peak device memory is read, the engine's state is freed, and the
served tokens of a sample of requests are compared with the float32
reference (``bench/reference.py`` over the module's ``hidden``).
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

import cell as cells
import reference
import trace as tracing
import weights


@dataclass
class Cell:
    """What a run needs to know about its cell, found by name."""
    name: str
    config: dict
    traffic: dict
    limits: dict
    run_seconds: float
    chips: int = 1
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)

    @classmethod
    def by_name(cls, name: str) -> "Cell":
        bm = cells.benchmark()
        w = cells.workload(bm, name)
        return cls(name, cells.config_file(w["config"]),
                   cells.traffic_file(w["traffic"]), cells.limits_file(name),
                   float(bm["run_seconds"]), int(w["chips"]),
                   cells.metrics_for(bm, name, "end_to_end"),
                   cells.metrics_for(bm, name, "per_layer"))


class CompileCounter:
    """Counts programs traced, compiled and loaded from the persistent
    cache, through JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.traced = self.compiled = self.loaded = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traced += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def snapshot(self) -> tuple[int, int, int]:
        return self.traced, self.compiled, self.loaded


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_info(chips: int, peaks: dict) -> dict:
    """The devices JAX sees; raises unless they are ``chips`` TPUs whose
    kind is in the peak table."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"bench: needs {chips} TPU chip(s); JAX finds "
                         f"{len(devs)} {devs[0].platform} device(s)")
    if kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


def check_layout(params, cfg) -> None:
    """The benchmark's weights must be the tree the program serves."""
    import jax
    from repro.models import model as MDL
    want = jax.eval_shape(lambda: MDL.init_params(cfg,
                                                  jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise SystemExit(f"bench: weight layout differs from the served "
                         f"one: {got} vs {exp}")


class Run:
    """The engine as a traffic kind drives it (``bench/traffic/<kind>.py``),
    with the benchmark's stamps: once the window is open, the host-clock
    time of each request's submission and of its first and last token seen
    after a tick, and the pool's pages in use after every tick. Times are
    seconds from the window's opening."""

    def __init__(self, eng):
        self.eng = eng
        self.t0 = None
        self.pages: list[int] = []
        self.stamps: dict[int, dict] = {}

    def open(self) -> None:
        self.t0 = time.perf_counter()
        self.base = {rid: len(o) for rid, o in self.eng.outputs.items()}

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def submit(self, r) -> None:
        from repro.serving import Request
        self.eng.submit(Request(r.req_id, r.prompt, r.max_new_tokens))
        if self.t0 is not None:
            self.stamps[r.req_id] = {"submit_s": self.elapsed()}

    def n_out(self, r) -> int:
        return len(self.eng.outputs.get(r.req_id, ()))

    def _seen(self) -> None:
        if self.t0 is None:
            return
        t = self.elapsed()
        for rid, out in self.eng.outputs.items():
            n = len(out) - self.base.get(rid, 0)
            st = self.stamps.setdefault(rid, {})
            if n > st.get("n", 0):
                st.setdefault("first_s", t)
                st["last_s"], st["n"] = t, n

    def tick(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation("bench.tick"):
            self.eng.tick()
        if self.t0 is not None:
            self.pages.append(self.eng.alloc.pages_in_use)
            self._seen()

    def tick_until(self, done, limit: int = 100_000) -> None:
        for _ in range(limit):
            if done():
                return
            self.tick()
        raise SystemExit("bench: set-up did not finish its requests")

    def quiesce(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation("bench.drain"):
            self.eng.quiesce()
        self._seen()

    def warm_decode(self, max_tokens: int) -> None:
        """Compile and run the fused decode horizon at every decode table
        width from the live pages now to those of ``max_tokens`` tokens,
        with no slot allowed to step (nothing is written, no token moves),
        and the device slot-state patch at every padded row count. It
        reaches into the engine (``_fused_jit``, ``_make_fused``,
        ``dev._patch``): the engine has no public warm-up."""
        import jax
        import jax.numpy as jnp
        from repro.serving.prefill import decode_table_bucket
        eng = self.eng
        W = eng.pool_spec.max_pages_per_req
        K = int(eng.ecfg.decode_horizon)
        top = min(-(-(max_tokens + K) // eng.ecfg.page_size), W)
        widths = {decode_table_bucket(p, W)
                  for p in range(eng.batcher.max_live_pages(), top + 1)}
        if eng._fused_jit is None:
            eng._fused_jit = eng._make_fused()
        d = eng.dev
        n = eng.ecfg.n_slots
        allow = jnp.zeros((n,), jnp.int32)
        for w in sorted(widths):
            _, _, _, eng.state, d.tokens, d.ctx, d.rem, d.key = \
                eng._fused_jit(eng.params, eng.state, d.tokens, d.bt, d.ctx,
                               d.rem, allow, d.key, horizon=K, width=int(w))
        rows = 1
        while True:
            z = jnp.zeros((rows,), jnp.int32)
            jax.block_until_ready(d._patch(
                jnp.array(d.bt, copy=True), jnp.array(d.ctx, copy=True),
                jnp.array(d.tokens, copy=True), jnp.array(d.rem, copy=True),
                z, jnp.full((rows, d.bt.shape[1]), -1, jnp.int32), z, z, z))
            if rows >= n:
                break
            rows *= 2
        jax.block_until_ready(eng.state)


def free_engine(eng) -> None:
    """Release the engine's device state (pool, slot state) at once, and
    its hold on the weights: the engine sits in reference cycles, so it
    outlives ``del`` until the next garbage collection."""
    import jax
    for leaf in jax.tree.leaves((eng.state, eng.dev.bt, eng.dev.ctx,
                                 eng.dev.tokens, eng.dev.rem)):
        leaf.delete()
    eng.params = eng.state = eng.dev = None
    gc.collect()


def check_sample(reqs, served: dict, seed: int, k: int) -> list:
    """``k`` of the requests that were served tokens, drawn from the seed,
    the one with the longest prompt and tokens among them."""
    done = [r for r in reqs if len(served.get(r.req_id, ()))]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(served[r.req_id]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) % (2 ** 63), 2]))
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def run(c: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        *, require_tpu: bool = True) -> tuple[dict, dict]:
    """One run; returns (result line, record for the metric readers).
    ``require_tpu=False`` is for tests on the CPU at a tiny size."""
    import jax
    cells.use_program()
    from repro.launch import serve
    from repro.serving import DecodeEngine

    peaks = cells.load_json(cells.BENCH / "peaks.json")
    if require_tpu:
        dev = device_info(c.chips, peaks)
        serve.enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    else:
        d0 = jax.devices()[0]
        dev = {"platform": d0.platform, "kind": d0.device_kind,
               "count": len(jax.devices())}
    peak = peaks.get(dev["kind"], {"flops_per_s": math.nan,
                                   "hbm_bytes_per_s": math.nan})
    counter = CompileCounter()
    cf = c.config
    cfg = cells.model_config(cf)
    ecfg = cells.engine_config(cf)
    arch = cells.arch_of(cf)
    m = arch.dims(cf, cfg.vocab_multiple)
    kind = cells.traffic_kind(c.traffic["kind"])

    # ---- set-up ---------------------------------------------------------
    marks = [("start", t_start), ("device", time.perf_counter())]
    params = arch.make(m, seed, cf["torch_dtype"])
    check_layout(params, cfg)
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    eng = DecodeEngine(cfg, ecfg, params=params)
    reqs = kind.requests(c.traffic, arch, m, cf["engine"], c.run_seconds,
                         seed)
    h = Run(eng)
    marks.append(("engine", time.perf_counter()))
    kind.setup(h, reqs)
    marks.append(("kind set-up", time.perf_counter()))
    st0 = eng.batcher.stats.preempted
    tm0 = (eng.timing.steps, eng.timing.host_s, eng.timing.device_syncs,
           eng.timing.decode_tokens)
    c0 = counter.snapshot()
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    log(f"{c.name}: {len(reqs)} requests, prompts "
        f"{[len(r.prompt) for r in reqs]}, budget "
        f"{[r.max_new_tokens for r in reqs]}, pool {ecfg.n_pages} pages, "
        f"q/k gain {weights.QK_GAIN}, {dev['kind']}; set-up s: " + ", ".join(
            f"{b[0]} {b[1] - a[1]:.3f}" for a, b in zip(marks, marks[1:])))

    # ---- the window -----------------------------------------------------
    if trace:
        jax.profiler.start_trace(logdir)
    h.open()
    with jax.profiler.TraceAnnotation("bench.window"):
        kind.drive(h, reqs, seconds)
    window_s = h.elapsed()
    if trace:
        jax.profiler.stop_trace()
    c1 = counter.snapshot()
    mem = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    tm1 = (eng.timing.steps, eng.timing.host_s, eng.timing.device_syncs,
           eng.timing.decode_tokens)
    preempted = eng.batcher.stats.preempted - st0
    served = {r.req_id: np.asarray(eng.outputs.get(r.req_id, ()), np.int32)
              for r in reqs}
    per_req = []
    for r in reqs:
        st = h.stamps.get(r.req_id, {})
        start = h.base.get(r.req_id, 0)
        per_req.append({
            "req_id": r.req_id, "prompt_len": len(r.prompt),
            "max_new_tokens": r.max_new_tokens, "due_s": r.due_s,
            "submit_s": st.get("submit_s"), "first_s": st.get("first_s"),
            "last_s": st.get("last_s"), "out_start": start,
            "out_end": len(served[r.req_id])})
    tokens = sum(q["out_end"] - q["out_start"] for q in per_req)
    # a request due by the window's end that got no token in it, or that
    # the engine gave up on, failed
    failed = sum(1 for r, q in zip(reqs, per_req)
                 if (r.due_s is None or r.due_s < seconds)
                 and (q["out_end"] == q["out_start"]
                      or r.req_id in eng.aborted))
    log(f"window {window_s:.6f} s, {tokens} tokens, {tm1[0] - tm0[0]} "
        f"ticks, preemptions in window {preempted}, programs traced/"
        f"compiled/loaded in window "
        f"{tuple(b - a for a, b in zip(c0, c1))}")

    rec = {
        "cell": c.name, "arch": cf["bench_arch"], "dims": m, "peaks": peak,
        "seconds": seconds,
        "setup_s": h.t0 - t_start, "window_s": window_s, "tokens": tokens,
        "ticks": tm1[0] - tm0[0], "host_s": tm1[1] - tm0[1],
        "horizons": tm1[2] - tm0[2], "decode_tokens": tm1[3] - tm0[3],
        "horizon": ecfg.decode_horizon, "n_slots": ecfg.n_slots,
        "n_pages": ecfg.n_pages, "pages_in_use": h.pages,
        "memory_peak_bytes": dev["memory_peak_bytes"],
        "requests": per_req, "trace": None,
    }
    if trace:
        try:
            rec["trace"] = tracing.reduce(*tracing.load(logdir),
                                          arch.KERNELS)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        dev["busy_s"] = rec["trace"]["busy_s"]
        dev["window_s"] = rec["trace"]["window_s"]

    # ---- correctness ----------------------------------------------------
    sample = check_sample(reqs, served, seed,
                          int(c.traffic["check_requests"]))
    free_engine(eng)
    del eng, h
    gc.collect()
    t2 = time.perf_counter()
    gaps = [reference.served_gaps(arch, params, m, r.prompt,
                                  served[r.req_id]) for r in sample]
    worst = float(max((g.max() for g in gaps), default=math.inf))
    limit = float(c.limits["max_logit_gap"]["limit"])
    n_checked = int(sum(len(g) for g in gaps))
    log(f"reference over {len(sample)} requests, {n_checked} served "
        f"tokens: {time.perf_counter() - t2:.3f} s")
    rec["served"] = {r.req_id: served[r.req_id].tolist() for r in sample}
    rec["prompts"] = {r.req_id: r.prompt for r in sample}
    check = {"max_logit_gap": {"value": worst, "limit": limit}}
    correct = bool(worst <= limit and tokens >= 1 and failed == 0)

    metrics = {}
    for spec in (c.per_layer if trace else c.end_to_end):
        v = cells.metric_reader(spec["name"])(rec)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    result = {"correct": correct, "attempted": len(reqs), "failed": failed,
              "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                               "idle_gaps": rec["trace"]["idle_gaps"]}
    result["check"] = check
    return result, rec
