"""CI observability smoke: one in-process serve run with every telemetry
surface on, then validate each export format end-to-end.

Runs ``repro.launch.serve.main()`` with ``--metrics-port 0`` (ephemeral
Prometheus endpoint), ``--trace-out`` and ``--request-log``, then

* scrapes the live endpoint over real HTTP and runs the scraped text
  through the strict ``parse_exposition`` validator (per-tier PIM pool
  samples must be present);
* reads the captured ``jax.profiler`` trace: the engine's phase spans
  (``engine.overlap`` / ``engine.collect`` / ``engine.dispatch`` / ...)
  and the ops of its named programs (``jit_decode_horizon``) must both be
  there, on the one clock Perfetto and xprof render;
* parses the JSONL request records and cross-checks their token totals and
  finished-count against the scraped counters.

Artifacts (the ``trace/`` profiler directory, ``records.jsonl``,
``metrics.txt``) are left in ``--outdir`` for CI upload so a failing run
can be inspected in Perfetto / by eye. Exit code 0 = all formats valid.

Usage::

    PYTHONPATH=src python benchmarks/telemetry_smoke.py --outdir tel_smoke
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--outdir", default="tel_smoke",
                    help="where trace/ / records.jsonl / metrics.txt land")
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args(argv)
    os.makedirs(args.outdir, exist_ok=True)
    trace_path = os.path.join(args.outdir, "trace")
    log_path = os.path.join(args.outdir, "records.jsonl")
    metrics_path = os.path.join(args.outdir, "metrics.txt")

    from repro.launch import serve
    from repro.telemetry import parse_exposition
    from repro.telemetry import prom
    from repro.telemetry.profile import read_profile

    # small but non-trivial: preemption pressure (few pages), fused horizon
    # (dispatch / collect phases), chunked prefill
    serve.main(["--requests", str(args.requests), "--slots", "3",
                "--pages", "48", "--page", "8", "--max-context", "128",
                "--mean-new", "12", "--prefill-mode", "chunked",
                "--chunk", "16", "--decode-horizon", "4",
                "--metrics-port", "0", "--trace-out", trace_path,
                "--request-log", log_path])

    # ---- Prometheus: scrape over real HTTP, validate strictly ----------
    srv = prom.LAST_SERVER
    assert srv is not None, "serve.main did not start a metrics server"
    text = srv.scrape()
    srv.close()
    with open(metrics_path, "w") as f:
        f.write(text)
    samples = parse_exposition(text)
    for required in ("repro_engine_decode_tokens_total",
                     "repro_engine_device_syncs_total",
                     'repro_kv_pages_total{tier="device"}',
                     "repro_pim_modeled_hbm_bytes_total",
                     "repro_pim_channel_util",
                     "repro_requests_finished_total",
                     "repro_request_ttft_seconds_count",
                     "repro_engine_decode_steps_total",
                     'repro_engine_compiles_total{program="decode_horizon"}',
                     "repro_paged_grid_cells_total",
                     "repro_paged_live_cells_total"):
        assert required in samples, f"missing sample {required}"
    assert samples["repro_requests_finished_total"] == args.requests
    print(f"[smoke] prometheus: {len(samples)} samples valid "
          f"({srv.url})")

    # ---- profiler trace: engine phases and named programs --------------
    spans, ops = read_profile(trace_path)
    names = {n for n, _, _ in spans}
    for phase in ("overlap", "collect", "fold", "schedule", "prefill",
                  "config", "dispatch"):
        assert f"engine.{phase}" in names, f"missing span engine.{phase}"
    modules = {m for _, m, _, _ in ops}
    assert "jit_decode_horizon" in modules, sorted(modules)
    print(f"[smoke] trace: {len(spans)} engine spans, {len(ops)} ops of "
          f"{len(modules)} programs -> {trace_path}")

    # ---- request records: JSONL parses, totals reconcile ---------------
    with open(log_path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    assert len(recs) == args.requests, (len(recs), args.requests)
    assert all(r["finished"] for r in recs)
    toks = sum(r["tokens"] for r in recs)
    assert toks == samples["repro_request_tokens_total"], \
        (toks, samples["repro_request_tokens_total"])
    assert all(r["ttft_s"] is not None and r["ttft_s"] >= 0 for r in recs)
    print(f"[smoke] records: {len(recs)} requests, {toks} tokens "
          f"-> {log_path}")
    print("# telemetry_smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
