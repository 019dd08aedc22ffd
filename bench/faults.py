"""Faults planted in the served program, each a break the correctness
check has to catch. ``install(name)`` patches the program's module
attributes before an engine is built (its programs are traced after), and
returns a function that undoes the patch.

- ``token_altered``: the sampler puts out a third of its tokens one id off;
- ``decode_kv_dropped``: decode steps leave the KV pool as it was (every
  write of a decoded token's K and V goes out of bounds and is dropped);
- ``prefill_kv_dropped``: the prefill writes none of the prompt's K and V;
- ``attn_zeroed``: decode attention returns zeros;
- ``half_pages``: decode attention reads every even page of the block table
  twice and never the odd page after it;
- ``other_slot``: decode attention reads another slot's pages (the block
  table's rows rolled by one).

``--fault`` of ``bench/control.py`` plants one on the chip; the CPU tests
plant each at a tiny size.
"""
from __future__ import annotations


def _sampler(real):
    import jax.numpy as jnp

    def make(kind="greedy", **kw):
        base = real(kind, **kw)

        def sample(key, logits):
            t = base(key, logits)
            return jnp.where(t % 3 == 0, (t + 1) % logits.shape[-1], t)
        return sample
    return make


def _attention(real, name):
    import jax.numpy as jnp

    def attn(q, k, v, pk, pv, bt, ctx, npage, noff, window=0, **kw):
        if name == "half_pages":
            col = jnp.arange(bt.shape[1])
            bt = bt[:, col - col % 2]
        elif name == "other_slot":
            bt = jnp.roll(bt, 1, axis=0)
        out, pk, pv = real(q, k, v, pk, pv, bt, ctx, npage, noff, window,
                           **kw)
        if name == "attn_zeroed":
            out = jnp.zeros_like(out)
        return out, pk, pv
    return attn


FAULTS = ("token_altered", "decode_kv_dropped", "prefill_kv_dropped",
          "attn_zeroed", "half_pages", "other_slot")


def install(name: str):
    """Plant fault ``name``; returns the function that removes it."""
    import jax.numpy as jnp
    from repro.core import paged_kv
    from repro.kernels import ops
    from repro.models import model
    from repro.serving import engine

    if name == "token_altered":
        mod, attr = engine, "make_scan_sampler"
        new = _sampler(engine.make_scan_sampler)
    elif name == "decode_kv_dropped":
        real = ops.write_targets

        def new(bt, ctx, run, *, page_size, n_pages, ring_width=0):
            npage, noff = real(bt, ctx, run, page_size=page_size,
                               n_pages=n_pages, ring_width=ring_width)
            return jnp.full_like(npage, n_pages), noff
        mod, attr = ops, "write_targets"
    elif name == "prefill_kv_dropped":
        mod, attr = paged_kv, "write_prefill"

        def new(pool_k, pool_v, *a, **kw):
            return pool_k, pool_v
    elif name in ("attn_zeroed", "half_pages", "other_slot"):
        mod, attr = model, "itpp_decode_attention_shard"
        new = _attention(model.itpp_decode_attention_shard, name)
    else:
        raise SystemExit(f"bench: no fault {name!r}; faults: {FAULTS}")
    old = getattr(mod, attr)
    setattr(mod, attr, new)
    return lambda: setattr(mod, attr, old)
