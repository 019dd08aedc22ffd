"""Smoke run of the served decode path on one TPU chip.

Builds internlm2-1.8b (arXiv:2403.17297) at its published widths in
bfloat16 through ``repro.launch.serve.build_engine``, as the serving CLI
does, with random weights from a fixed seed. Then:

1. one decode step on a freshly prefilled pool, once on the served runtime
   (the Pallas paged-attention kernel compiled through Mosaic) and once on
   the jnp reference path, same params and same KV: the logits must agree
   within a bf16 tolerance (``LOGIT_TOL``), and the served decode program
   must hold the Mosaic kernel (``tpu_custom_call``);
2. eight requests with LongBench-like prompt lengths served through
   ``DecodeEngine.run`` (batched prefill, fused decode horizon): every
   request must complete with its requested number of tokens, and every
   page must return to the pool.

Run it from the repository root on a machine with one TPU:

    python chip_smoke.py

It exits non-zero, printing no result, where JAX finds no TPU. Every line
before the last is smoke output, not a measurement. The last line is one
JSON object naming the device.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
# the serving CLI's own flags: full width, a 32k-token pool (2048 pages of
# 16), 8k contexts, prompts drawn from the hotpotqa length distribution
SERVE_ARGV = ["--arch", "internlm2-1.8b", "--full", "--task", "hotpotqa",
              "--requests", "8", "--slots", "8", "--page", "16",
              "--pages", "2048", "--max-context", "8192", "--mean-new", "32"]
# decode-comparison prompts: a multi-split table, a page-aligned context, a
# mid-page one and a single-page one
COMPARE_LENS = (3001, 1024, 517, 9)
# Pallas vs jnp logits, both in bf16: max|d| <= max(LOGIT_TOL * max|logit|,
# 2 * the bf16 reference's own max|d| from the f32 reference). Two correct
# bf16 paths through 24 random layers differ by a few per cent of max|logit|
# (bf16 rounding of the residual stream compounds layer by layer), so a
# fixed 2e-2 would fail correct code. A served path no less accurate than
# the jnp one lies, by the triangle inequality, within twice the jnp path's
# distance from f32 of it.
LOGIT_TOL = 2e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def compare_decode(eng, seed: int = 0) -> dict:
    """One decode step on the engine's params and the same prompts through
    three programs: the served runtime (Pallas, compiled through Mosaic) and
    the jnp reference, both in the model's bf16, and the jnp reference with
    f32 activations and KV under full-precision matmuls (the weights stay
    the same bf16 values), as the yardstick for bf16 error."""
    from dataclasses import replace

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.paged_kv import PoolSpec
    from repro.kernels.backend import KernelConfig
    from repro.kernels.ops import write_targets
    from repro.models import model as MDL

    cfg, page = eng.cfg, eng.ecfg.page_size
    B, S = len(COMPARE_LENS), max(COMPARE_LENS)
    lens = np.asarray(COMPARE_LENS, np.int32)
    # pages packed back to back, -1 past each prompt's last page + 1 token
    n_pg = -(-(lens + 1) // page)
    width = int(n_pg.max())
    first = np.concatenate([[0], np.cumsum(n_pg)[:-1]])
    slot = np.arange(width)[None]
    bt = jnp.asarray(np.where(slot < n_pg[:, None], first[:, None] + slot, -1),
                     jnp.int32)
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (B, S)).astype(np.int32)
    ctx = jnp.asarray(lens + 1)
    reference_rt = replace(eng.rt, kernels=KernelConfig(use_pallas=False))

    def decode_logits(params, dtype, rts):
        """Prefill the prompts into a fresh pool of ``dtype`` with the first
        runtime, then one decode step per runtime: compiled programs and
        their f32 logits."""
        spec = PoolSpec(eng.pool_spec.n_layers, int(n_pg.sum()), page,
                        cfg.n_kv_heads, cfg.d_head, width, dtype=dtype)
        logits, state = jax.jit(lambda p, st: MDL.prefill(
            cfg, p, st, toks, bt, last_idx=lens - 1, valid_len=lens,
            rt=rts[0]))(params, MDL.init_decode_state(cfg, spec, B))
        tokens = jnp.argmax(logits, -1).astype(jnp.int32)
        npage, noff = write_targets(bt, ctx, jnp.ones((B,), bool),
                                    page_size=page, n_pages=spec.n_pages)
        out = []
        for rt in rts:
            prog = jax.jit(lambda p, st: MDL.decode_step(
                cfg, p, st, tokens, bt, ctx, npage, noff, rt=rt)[0]
            ).lower(params, state).compile()
            out.append((prog, np.asarray(prog(params, state), np.float32)))
        return out

    (served_prog, served), (_, reference) = decode_logits(
        eng.params, cfg.dtype, (eng.rt, reference_rt))
    check("tpu_custom_call" in served_prog.as_text(),
          "the served decode program holds no Mosaic kernel")
    with jax.default_matmul_precision("highest"):
        f32_params = dict(eng.params,
                          embed=eng.params["embed"].astype(jnp.float32))
        [(_, truth)] = decode_logits(f32_params, "float32", (reference_rt,))
        del f32_params
    check(all(np.isfinite(x).all() for x in (served, reference, truth)),
          "non-finite decode logits")

    def dist(a, b):
        return float(np.abs(a - b).max())

    scale = float(np.abs(truth).max())
    err, floor = dist(served, reference), dist(reference, truth)
    tol = max(LOGIT_TOL * scale, 2 * floor)
    check(err <= tol, f"Pallas vs reference logits: max|d|={err} > {tol} "
          f"(max|logit|={scale}, bf16 reference vs f32: {floor})")
    return {"max_abs_err": err, "tol": tol, "max_abs_logit": scale,
            "reference_vs_f32": floor, "served_vs_f32": dist(served, truth),
            "greedy_agree": float(
                (served.argmax(-1) == reference.argmax(-1)).mean())}


def serve_requests(eng, args) -> dict:
    """Serve the CLI's LongBench-like trace to completion."""
    from repro.launch import serve
    reqs = serve.submit_trace(eng, args)
    t0 = time.perf_counter()
    eng.run(100_000)
    wall = time.perf_counter() - t0
    check(eng.batcher.stats.completed == len(reqs) and not eng.aborted,
          f"{eng.batcher.stats.completed}/{len(reqs)} requests completed, "
          f"aborted: {eng.aborted}")
    for r in reqs:
        # the engine's budget convention: the prefill's token, then
        # max_new_tokens decode steps
        got, want = len(eng.outputs[r.req_id]), r.max_new_tokens + 1
        check(got == want, f"request {r.req_id}: {got}/{want} tokens")
    used = eng.alloc.shard_balance()
    check(int(used.max()) == 0, f"pages still held after serving: {used}")
    return {"requests": len(reqs),
            "prompt_tokens": int(sum(len(r.prompt) for r in reqs)),
            "tokens": int(sum(len(v) for v in eng.outputs.values())),
            "wall_s": wall}


def main() -> None:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform is "
                         f"{dev.platform!r}); this smoke runs only on a TPU")
    sys.path.insert(0, str(SRC))
    from repro.launch import serve
    cache = serve.enable_compile_cache()
    args = serve.parse_args(SERVE_ARGV)

    t0 = time.perf_counter()
    eng = serve.build_engine(args)
    jax.block_until_ready(eng.params)
    cfg = eng.cfg
    kc = eng.rt.kernels.resolve()
    check(cfg.dtype == "bfloat16" and eng.state["pool"]["k"].dtype == "bfloat16",
          f"model dtype {cfg.dtype}, pool {eng.state['pool']['k'].dtype}")
    check(kc.use_pallas and not kc.interpret,
          f"decode kernel resolved to {kc}, not compiled Pallas")
    print(f"[smoke] device={dev.device_kind} count={len(jax.devices())} "
          f"compile_cache={cache}", flush=True)
    print(f"[smoke] {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads}x{cfg.d_head} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype} "
          f"params={sum(x.size for x in jax.tree.leaves(eng.params))} "
          f"pool_tokens={eng.pool_spec.tokens} kernel={kc} "
          f"setup_s={time.perf_counter() - t0:.3f}", flush=True)

    t0 = time.perf_counter()
    cmp = compare_decode(eng)
    print(f"[smoke] decode logits, Pallas vs reference (bf16): "
          f"max|d|={cmp['max_abs_err']} tol={cmp['tol']} "
          f"max|logit|={cmp['max_abs_logit']}; vs f32 reference: "
          f"reference={cmp['reference_vs_f32']} "
          f"Pallas={cmp['served_vs_f32']}; "
          f"greedy_agree={cmp['greedy_agree']} "
          f"(compile+run s={time.perf_counter() - t0:.3f})", flush=True)

    out = serve_requests(eng, args)
    print(f"[smoke] served {out['requests']}/{out['requests']} requests: "
          f"prompt_tokens={out['prompt_tokens']} tokens={out['tokens']} "
          f"page_balance=0 wall_s(compiles included)={out['wall_s']:.3f}",
          flush=True)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[smoke] peak_bytes_in_use={peak}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
