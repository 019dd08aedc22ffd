"""Ahead-of-time compiles of the served decode kernel for a described TPU
v5e chip, at internlm2-1.8b widths (KVH 8, G 2, D 128, page 16, bf16).

Nothing runs: the TPU compiler, installed with jax, lowers each variant
through Mosaic for a chip that is described but not attached, and refuses
what the chip would refuse (block shapes off the (8, 128) tiling, too much
VMEM). Interpret-mode tests cannot see those faults. The topology is
described inside a fixture, so every pytest worker collects the same tests
and only the worker that runs this file loads the TPU library.
"""
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
