"""Where a cell's window goes, by the program's own names: device seconds
by program (``jit_<name>``), the decode program's device
seconds by program scope (``jax.named_scope``), idle gaps named and split
by the engine's phase spans (``engine.<phase>``) nested in the benchmark's
own, and the engine's decode-work counters over the window.

    python3 bench/scopes.py --workload internlm2-1.8b.decode_long \\
        --seed 7 --seconds 10

runs the cell's set-up as ``harness.run`` does, traces its window, and
prints one JSON line: the three readers of ``bench/metrics``
(``decode_device_ms_per_step``, ``decode_movement_frac``,
``paged_grid_live_frac``), the reductions below, and ``out_tok_s`` under
the profiler. The harness does not yet put these keys into its record;
``rec_keys`` shows the keys the readers expect.

The reductions work on plain tuples, as ``bench/trace.py``'s do: ``device``
maps each device to its operation events ``(name, start_ns, end_ns,
module, scope)``; ``host`` lists host spans ``(name, start_ns, end_ns)``,
the benchmark's (``bench.``) and the engine's (``engine.``), on the same
clock. An op's scope is the innermost of ``SCOPES`` on its ``op_name``
path, read from the compiled HLO of its program (``op_scopes``), or None.
"""
from __future__ import annotations

import re

import trace as tracing

DECODE = "jit_decode_horizon"
# the decode program's compute scopes; ``layers`` wraps the layer scan, so
# an op whose innermost scope is ``layers`` (the scan's slices of the KV
# pool and of the stacked weights, the pool's write-back) or that has none
# (XLA's copies) is movement
COMPUTE = ("embed", "attn", "kv_write", "paged_attn", "mlp", "head",
           "sample")
SCOPES = COMPUTE + ("layers",)
UNSCOPED = "unscoped"
SPANS = ("bench.", "engine.")
MODULES_LINE = "XLA Modules"
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                    r'metadata=\{op_name="([^"]*)"')


def op_scopes(hlo_text: str) -> dict[str, str | None]:
    """Instruction name -> innermost scope of its ``op_name``, over a
    compiled program's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = scope_of(m.group(2))
    return out


def scope_of(op_name: str) -> str | None:
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def op_id(name: str) -> str:
    """The instruction name of an op event named by its HLO text."""
    m = tracing._HLO.match(name)
    return m.group("id").lstrip("%") if m else name


def _span_at(host, t) -> str:
    """The innermost span covering time t: the latest start, and of two
    that start together the one that ends first."""
    best = max(((s, -e, name) for name, s, e in host
                if name != tracing.WINDOW and s <= t < e), default=None)
    return best[2] if best else "bench.window (no span)"


def split_gap(host, lo, hi) -> dict[str, float]:
    """Nanoseconds of [lo, hi) under each innermost span."""
    cuts = sorted({lo, hi} | {x for _, s, e in host for x in (s, e)
                              if lo < x < hi})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        name = _span_at(host, (a + b) / 2)
        out[name] = out.get(name, 0) + (b - a)
    return out


def reduce(device: dict, host: list, module: str = DECODE,
           top: int = 10) -> dict:
    """Seconds by program (``module_s``), ``module``'s seconds by scope
    (``scope_s``, ``unscoped`` for ops under none), idle seconds by the
    innermost span over them (``idle_by_span``), the longest idle gaps
    named by the span over their middle, and ``module``'s longest ops with
    their scope, each averaged over the devices."""
    if not device:
        raise RuntimeError("the trace holds no device plane")
    lo, hi = tracing.window_of(host)
    mods, scopes, ops, idle, gaps = {}, {}, {}, {}, []
    for evs in device.values():
        clipped = [(n, max(s, lo), min(e, hi), m, sc)
                   for n, s, e, m, sc in evs
                   if min(e, hi) > max(s, lo)
                   and tracing.opcode(n) not in tracing.CONTAINERS]
        u = tracing.union((s, e) for _, s, e, _, _ in clipped)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
                for k, v in split_gap(host, s, e).items():
                    idle[k] = idle.get(k, 0) + v
        for n, s, e, m, sc in clipped:
            mods[m] = mods.get(m, 0) + (e - s)
            if m == module:
                key = sc or UNSCOPED
                scopes[key] = scopes.get(key, 0) + (e - s)
                op = (tracing.short(n), key)
                ops[op] = ops.get(op, 0) + (e - s)
    d = len(device) * 1e9
    gaps.sort(reverse=True)
    return {
        "module_s": {k: v / d for k, v in
                     sorted(mods.items(), key=lambda kv: -kv[1])},
        "scope_s": {k: v / d for k, v in
                    sorted(scopes.items(), key=lambda kv: -kv[1])},
        "idle_by_span": {k: v / d for k, v in
                         sorted(idle.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[_span_at(host, (s + e) / 2), g / 1e9]
                      for g, s, e in gaps[:top]],
        "decode_ops": [[o, sc, v / d] for (o, sc), v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
    }


def load(logdir: str, scopes: dict[str, dict]) -> tuple[dict, list, dict]:
    """(device events, host spans, ops found in and missing from the HLO
    maps) from the newest trace under ``logdir``. ``scopes`` maps a
    program name to ``op_scopes`` of its compiled HLO, looked up by the
    instruction id in the event's name (HLO text). On a TPU v5e an op
    event carries no program or op-name stat: it takes the program whose
    run on the ``XLA Modules`` line covers its start."""
    import bisect
    import glob
    import os

    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no profiler trace under {logdir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device, host = {}, []
    found = {"mapped": 0, "unmapped": 0}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            runs = sorted((e.start_ns, e.end_ns, e.name.split("(")[0])
                          for line in plane.lines
                          if line.name == MODULES_LINE for e in line.events)
            starts = [r[0] for r in runs]
            for line in plane.lines:
                if line.name != tracing.OPS_LINE:
                    continue
                for e in line.events:
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    mod = runs[i][2] if i >= 0 and \
                        e.start_ns < runs[i][1] else ""
                    hmap = scopes.get(mod)
                    sc = None
                    if hmap is not None:
                        key = op_id(e.name)
                        found["mapped" if key in hmap else "unmapped"] += 1
                        sc = hmap.get(key)
                    evs.append((e.name, e.start_ns, e.end_ns, mod, sc))
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(SPANS))
    return device, host, found


COUNTERS = ("decode_steps", "paged_grid_cells", "paged_live_cells")


def profile_window(c, seed: int, seconds: float, t_start: float,
                   top: int = 10) -> dict:
    """One traced window of cell ``c`` (its set-up as ``harness.run``
    makes it), reduced; the record keys the readers need, and their
    values."""
    import gc
    import shutil
    import tempfile
    import time

    import jax
    import jax.numpy as jnp

    import cell as cells
    import harness
    cells.use_program()
    from repro.launch import serve
    from repro.serving import DecodeEngine

    peaks = cells.load_json(cells.BENCH / "peaks.json")
    harness.device_info(c.chips, peaks)
    serve.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cf = c.config
    cfg, ecfg = cells.model_config(cf), cells.engine_config(cf)
    arch = cells.arch_of(cf)
    m = arch.dims(cf, cfg.vocab_multiple)
    kind = cells.traffic_kind(c.traffic["kind"])
    params = arch.make(m, seed, cf["torch_dtype"])
    eng = DecodeEngine(cfg, ecfg, params=params)
    reqs = kind.requests(c.traffic, arch, m, cf["engine"], c.run_seconds,
                         seed)
    h = harness.Run(eng)
    kind.setup(h, reqs)
    t = eng.timing
    before = {k: getattr(t, k) for k in COUNTERS}
    comp0 = dict(t.compiles)
    logdir = tempfile.mkdtemp(prefix="bench_scopes_")
    # the interpreter's garbage collections in the window, a host stall
    # no engine phase names: (generation, seconds) each
    pauses, t_gc = [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            t_gc[0] = time.perf_counter()
        else:
            pauses.append((info["generation"], time.perf_counter() - t_gc[0]))

    jax.profiler.start_trace(logdir)
    gc.callbacks.append(on_gc)
    h.open()
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        kind.drive(h, reqs, seconds)
    window_s = h.elapsed()
    gc.callbacks.remove(on_gc)
    jax.profiler.stop_trace()
    tokens = sum(len(eng.outputs[r.req_id]) - h.base.get(r.req_id, 0)
                 for r in reqs)
    rec = {k: getattr(t, k) - before[k] for k in COUNTERS}
    # the decode program at the window's table width, compiled again (the
    # persistent cache gives back the same executable) for its HLO text
    from repro.serving.prefill import decode_table_bucket
    W = eng.pool_spec.max_pages_per_req
    width = decode_table_bucket(eng.batcher.max_live_pages(), W)
    d = eng.dev
    text = eng._fused_jit.lower(
        eng.params, eng.state, d.tokens, d.bt, d.ctx, d.rem,
        jnp.zeros((ecfg.n_slots,), jnp.int32), d.key,
        horizon=int(ecfg.decode_horizon), width=int(width)).compile().as_text()
    try:
        device, host, found = load(logdir, {DECODE: op_scopes(text)})
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    rec["trace"] = reduce(device, host, top=top)
    out = {"cell": c.name, "seed": seed, "window_s": window_s,
           "tokens": tokens, "out_tok_s_traced": tokens / window_s,
           "width": width, "one_width": rec["paged_grid_cells"] ==
           rec["decode_steps"] * ecfg.n_slots * width,
           "compiles_in_window": {k: v - comp0.get(k, 0)
                                  for k, v in t.compiles.items()
                                  if v != comp0.get(k, 0)},
           "scopes_found": found,
           "gc_in_window": {"count": len(pauses),
                            "gen2": sum(1 for g, _ in pauses if g == 2),
                            "max_ms": 1e3 * max((d for _, d in pauses),
                                                default=0.0),
                            "total_ms": 1e3 * sum(d for _, d in pauses)},
           "rec_keys": sorted(rec), "metrics": {}}
    for name in ("decode_device_ms_per_step", "decode_movement_frac",
                 "paged_grid_live_frac"):
        out["metrics"][name] = cells.metric_reader(name)(rec)
    out.update(rec)
    out["setup_s"] = h.t0 - t_start
    return out


def main(argv=None) -> None:
    import argparse
    import json
    import time
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--top", type=int, default=10,
                    help="longest idle gaps and decode ops to list")
    args = ap.parse_args(argv)
    import harness
    c = harness.Cell.by_name(args.workload)
    print(json.dumps(profile_window(c, args.seed, args.seconds, t0,
                                    args.top)), flush=True)


if __name__ == "__main__":
    main()
