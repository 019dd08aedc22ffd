"""Ahead-of-time compiles of the served decode kernel, and of the fused
decode horizon around it, for a described TPU v5e chip, at internlm2-1.8b
widths (KVH 8, G 2, D 128, page 16, bf16).

Nothing runs: the TPU compiler, installed with jax, lowers each variant
through Mosaic for a chip that is described but not attached, and refuses
what the chip would refuse (block shapes off the (8, 128) tiling, too much
VMEM). Interpret-mode tests cannot see those faults. The topology is
described inside a fixture, so every pytest worker collects the same tests
and only the worker that runs this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_attention_partials

B, KVH, G, D, PAGE, P, W = 8, 8, 2, 128, 16, 2048, 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("variant,kw,rows", [
    ("decode", dict(n_splits=1), G),
    ("decode_split4", dict(n_splits=4), G),
    ("verify_qpos5", dict(qpos=5), G * 5),
    ("window", dict(window=True, n_splits=4), G),
    ("windowed_slice", dict(window=True, windowed_slice=True), G),
])
def test_paged_attention_compiles_for_v5e(one_chip, variant, kw, rows):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    kw = dict(kw)
    windowed = kw.pop("window", False)
    args = [sds((B, KVH, rows, D), jnp.bfloat16),
            sds((P, PAGE, KVH, D), jnp.bfloat16),
            sds((P, PAGE, KVH, D), jnp.bfloat16),
            sds((B, W), jnp.int32), sds((B,), jnp.int32)]
    if windowed:
        args.append(sds((B,), jnp.int32))

    def f(q, k, v, bt, ctx, window=None):
        return paged_attention_partials(q, k, v, bt, ctx, window=window,
                                        interpret=False, **kw)

    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), variant


def test_paged_attention_kernel_is_named(one_chip):
    """The kernel's custom call carries its own name, so a device trace or
    an HLO dump finds it by name and not as the one Mosaic call there."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((B, KVH, G, D), jnp.bfloat16),
            sds((P, PAGE, KVH, D), jnp.bfloat16),
            sds((P, PAGE, KVH, D), jnp.bfloat16),
            sds((B, W), jnp.int32), sds((B,), jnp.int32)]

    def f(q, k, v, bt, ctx):
        return paged_attention_partials(q, k, v, bt, ctx, interpret=False)

    text = jax.jit(f).lower(*args).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1
    assert "%paged_attention" in calls[0].split(" = ")[0]
    assert "/paged_attention/pallas_call" in calls[0]


# an HLO instruction that produces an array: name, shape, opcode, callee
_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = ([a-z0-9]+\[[0-9,]*\])\S* "
                    r"([\w-]+)\((?:.*calls=%([\w.-]+))?")


def _computations(text):
    """{computation: [(name, shape, opcode, callee), ...]} of an HLO text,
    the root last."""
    comps, cur, root = {}, None, None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", ln)
        if head:
            cur, root = comps.setdefault(head.group(1), []), None
        elif ln.startswith("}") and cur is not None:
            if root is not None:
                cur.append(root)
            cur = None
        elif cur is not None and (m := _INSTR.match(ln)):
            if ln.lstrip().startswith("ROOT "):
                root = m.groups()
            else:
                cur.append(m.groups())
    return comps


def test_decode_horizon_writes_pool_in_place(one_chip):
    """The fused decode horizon (``decode_multi`` under jit, state donated,
    the Pallas kernel) adds the token to the stacked KV pool in place: no
    copy, dynamic-slice, dynamic-update-slice or other fusion produces a
    value of the pool's shape, whole ([L, P, ...]), one layer's ([P, ...],
    [1, P, ...]) or flat ([L*P, ...]). Only parameters, tuple elements,
    bitcasts and the token's scatters do. P is large enough that the pool
    stays in HBM: a pool that fits the chip's VMEM gets memory-space copies
    that a served pool never has."""
    from dataclasses import replace
    from repro.configs import get_config
    from repro.core.paged_kv import PoolSpec
    from repro.models import model as MDL
    from repro.serving.sampling import make_scan_sampler
    from repro.kernels.backend import KernelConfig

    n_layers, pages, slots, width = 2, 1024, 4, 8
    cfg = replace(get_config("internlm2-1.8b"), n_layers=n_layers,
                  d_model=512, d_ff=1024, vocab_size=512)
    assert (cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.d_head) \
        == (KVH, G, D)
    rt = MDL.Runtime(kernels=KernelConfig(use_pallas=True, interpret=False))
    spec = PoolSpec(n_layers, pages, PAGE, KVH, D, width)

    def horizon(params, state, tokens, bt, ctx, rem, allow, key):
        return MDL.decode_multi(
            cfg, params, state, tokens, bt, ctx, rem, allow, key, horizon=2,
            table_width=width, page_size=PAGE, n_pages=pages, eos_token=-1,
            sample=make_scan_sampler("greedy"), rt=rt)

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    args = sds((
        jax.eval_shape(lambda: MDL.init_params(cfg, jax.random.PRNGKey(0),
                                               jnp.bfloat16)),
        jax.eval_shape(lambda: MDL.init_decode_state(cfg, spec, slots)),
        *(jax.ShapeDtypeStruct(s, jnp.int32)
          for s in ((slots,), (slots, width), (slots,), (slots,), (slots,))),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))))
    text = jax.jit(horizon, donate_argnums=(1, 2, 4, 5, 7)).lower(
        *args).compile().as_text()

    tail = f"{PAGE},{KVH},{D}]"
    pool_shapes = {f"bf16[{lead}{tail}" for lead in (
        f"{n_layers},{pages},", f"{pages},", f"1,{pages},",
        f"{n_layers * pages},")}
    comps = _computations(text)

    def in_place(op, callee):
        if op in ("parameter", "get-tuple-element", "bitcast", "scatter"):
            return True
        return op == "fusion" and comps[callee][-1][2] == "scatter"

    moved = [f"{name} {shape} {op}" for instrs in comps.values()
             for name, shape, op, callee in instrs
             if shape in pool_shapes and not in_place(op, callee)]
    assert not moved, moved
    assert any(op == "scatter" and shape in pool_shapes
               for instrs in comps.values() for _, shape, op, _ in instrs)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert calls
    assert all("%paged_attention" in c.split(" = ")[0] for c in calls)
