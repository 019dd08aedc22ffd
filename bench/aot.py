"""Ahead-of-time compile of a configuration's served programs for a
described TPU v5e chip, without the chip: the batched prefill at a given
prompt bucket and the fused decode horizon at a given table width, with the
configuration's pool. Prints each program's ``memory_analysis`` as one JSON
line, the numbers its configuration file records to size the pool.

    JAX_PLATFORMS=cpu python bench/aot.py --config internlm2-1.8b \\
        --prefill-len 18176 --width 2048

Nothing runs and nothing is allocated: parameters and state are shapes.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import cell  # noqa: E402

GIB = 2 ** 30


def analyse(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {k: getattr(ma, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}
    out["peak_gib"] = (out["argument_size_in_bytes"]
                       + out["output_size_in_bytes"]
                       - out["alias_size_in_bytes"]
                       + out["temp_size_in_bytes"]) / GIB
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--pages", type=int, default=0,
                    help="pool pages (default: the configuration's)")
    ap.add_argument("--prefill-len", type=int, nargs="*", default=[])
    ap.add_argument("--prefill-batch", type=int, default=1)
    ap.add_argument("--width", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    cf = cell.config_file(args.config)
    cfg = cell.model_config(cf)
    over = {"use_pallas": True, "kernel_interpret": False}
    if args.pages:
        over["n_pages"] = args.pages
    ecfg = cell.engine_config(cf, **over)
    from repro.core.paged_kv import PoolSpec
    from repro.kernels.backend import KernelConfig
    from repro.models import model as MDL
    from repro.serving.prefill import _make_batched_fn
    from repro.serving.sampling import make_scan_sampler

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    rt = MDL.Runtime(kernels=KernelConfig(use_pallas=True, interpret=False))
    maxp = -(-ecfg.max_context // ecfg.page_size) + 1
    spec = PoolSpec(cfg.n_layers, ecfg.n_pages, ecfg.page_size,
                    cfg.n_kv_heads, cfg.d_head, maxp, dtype=cfg.dtype)
    params = shapes(jax.eval_shape(
        lambda: MDL.init_params(cfg, jax.random.PRNGKey(0))))
    state = shapes(jax.eval_shape(
        lambda: MDL.init_decode_state(cfg, spec, ecfg.n_slots)))
    i32 = jnp.int32

    def sds(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    report = {"config": cf["name"], "n_pages": ecfg.n_pages,
              "pool_gib": spec.bytes() / GIB,
              "params_gib": sum(a.size * a.dtype.itemsize for a in
                                jax.tree.leaves(params)) / GIB}
    fn = _make_batched_fn(cfg, rt)
    B = args.prefill_batch
    for S in args.prefill_len:
        c = fn.lower(params, state, sds((B, S)), sds((B, maxp)), sds((B,)),
                     sds((B,))).compile()
        report[f"prefill_{B}x{S}"] = analyse(c)
        print(json.dumps(report), flush=True)

    sample = make_scan_sampler("greedy")

    def fused(params, state, tokens, bt, ctx, rem, allow, key, *,
              horizon, width):
        return MDL.decode_multi(
            cfg, params, state, tokens, bt, ctx, rem, allow, key,
            horizon=horizon, table_width=width, page_size=ecfg.page_size,
            n_pages=ecfg.n_pages, eos_token=-1, sample=sample, rt=rt)

    dec = jax.jit(fused, static_argnames=("horizon", "width"),
                  donate_argnums=(1, 2, 4, 5, 7))
    n = ecfg.n_slots
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    for W in args.width:
        c = dec.lower(params, state, sds((n,)), sds((n, maxp)), sds((n,)),
                      sds((n,)), sds((n,)), key,
                      horizon=ecfg.decode_horizon, width=W).compile()
        r = analyse(c)
        text = c.as_text()
        ops = [line.strip().removeprefix("ROOT ")
               for line in text.splitlines()]
        r["kernels"] = {k: sum(1 for op in ops if re.search(p, op))
                        for k, p in cell.arch_of(cf).KERNELS.items()}
        r["whole_pool_copies"] = sum(
            1 for line in text.splitlines()
            if " copy(" in line and
            f"[{cfg.n_layers},{ecfg.n_pages}," in line.replace(" ", ""))
        report[f"decode_h{ecfg.decode_horizon}_w{W}"] = r
        print(json.dumps(report), flush=True)


if __name__ == "__main__":
    main()
