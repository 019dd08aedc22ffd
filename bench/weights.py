"""Random weights of a dense SwiGLU decoder, made on the device from the
seed in one jitted call, in the dtype they are served in.

The tree follows the served layout: ``embed`` [Vp, d], ``final_norm`` [d],
``head`` [d, Vp] and a stack ``layers`` with leading axis L holding
``ln1``/``ln2`` [L, d], ``attn`` (``wq`` [L, d, H*dh], ``wk``/``wv``
[L, d, KVH*dh], ``wo`` [L, H*dh, d]) and ``mlp`` (``w1``/``w3``
[L, d, ff] gate and up, ``w2`` [L, ff, d] down). Norm gains are stored as
offsets from 1. The vocabulary is padded to ``Vp`` rows; the padding rows of
the embedding and columns of the head are zero, as a checkpoint loader
leaves them, so no padding id is ever the largest logit.

The scales are 1/sqrt(fan-in) for the projections, 0.02 for the
embedding and head and 0.1 for the norm offsets, but for q and k: at
``QK_GAIN``/sqrt(d) each, a query's score against a key has a standard
deviation of ``QK_GAIN`` squared, 2.9, so a head puts most of its weight
on a few dozen of the 10k-20k keys of a long context, as trained heads
do, and what attention reads moves the residual stream as much as the MLP
does. At a freshly initialised model's scales (scores of standard
deviation 1, the attention output at 1/sqrt(2 L H dh)) attention spreads
evenly over the context and adds almost nothing to the residual, so a
correctness check could not see the paged KV or the kernel. At a gain of
2 (scores of standard deviation 4), bf16 rounding moved the served
tokens' logit gaps four times as much, for no more reach.

Each KV head's key projection also leans towards the query projection of
the first query head it serves: ``wk`` = ``SELF`` wq + sqrt(1 - ``SELF``^2)
w', so that head scores a token against its own key at about ``SELF``
``QK_GAIN``^2 sqrt(dh), 11, level with the best of 20k other keys (about
13). Decoding then reads the key each step has just written as much as
the far context, as trained heads attend to the current token, and a
decode step whose KV write goes astray shows.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

QK_GAIN = 1.7
SELF = 0.35


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)
    and a stream number, so weights and token ids draw apart."""
    s = np.random.SeedSequence([int(seed) % (2 ** 63), stream])
    return jax.random.PRNGKey(int(s.generate_state(1, np.uint32)[0]))


def layout(m: dict) -> dict:
    """Leaf -> (shape, scale, keep) of the served tree for model dims ``m``
    (see ``dims``). ``scale`` None marks a norm gain; ``keep`` (axis, n)
    zeroes the vocabulary padding past index n along that axis."""
    d, L, ff, V, Vp = m["d"], m["L"], m["ff"], m["V"], m["Vp"]
    qd, kvd = m["H"] * m["dh"], m["KVH"] * m["dh"]
    return {
        "embed": ((Vp, d), 0.02, (0, V)),
        "final_norm": ((d,), None, None),
        "head": ((d, Vp), 0.02, (1, V)),
        "layers": {
            "ln1": ((L, d), None, None),
            "ln2": ((L, d), None, None),
            "attn": {"wq": ((L, d, qd), QK_GAIN / math.sqrt(d), None),
                     "wk": ((L, d, kvd), QK_GAIN / math.sqrt(d), None),
                     "wv": ((L, d, kvd), 1 / math.sqrt(d), None),
                     "wo": ((L, qd, d), 1 / math.sqrt(qd), None)},
            "mlp": {"w1": ((L, d, ff), 1 / math.sqrt(d), None),
                    "w3": ((L, d, ff), 1 / math.sqrt(d), None),
                    "w2": ((L, ff, d), 1 / math.sqrt(ff), None)},
        },
    }


def dims(cf: dict, vocab_multiple: int = 256) -> dict:
    """Model dims from a configuration file's published keys."""
    V = cf["vocab_size"]
    return {"d": cf["hidden_size"], "L": cf["num_hidden_layers"],
            "ff": cf["intermediate_size"], "H": cf["num_attention_heads"],
            "KVH": cf["num_key_value_heads"],
            "dh": cf["hidden_size"] // cf["num_attention_heads"],
            "V": V, "Vp": -(-V // vocab_multiple) * vocab_multiple,
            "eps": float(cf["rms_norm_eps"]),
            "theta": float(cf["rope_theta"])}


def _leaf(key, shape, scale, keep):
    if scale is None:
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        x = scale * jax.random.normal(key, shape, jnp.float32)
    if keep is not None:
        axis, n = keep
        idx = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        x = jnp.where(idx < n, x, 0.0)
    return x


@partial(jax.jit, static_argnums=(0, 2, 3))
def _make(spec, key, dtype, heads):
    leaves, treedef = spec
    keys = jax.random.split(key, len(leaves))
    out = jax.tree.unflatten(treedef, [
        _leaf(k, shape, scale, keep)
        for k, (shape, scale, keep) in zip(keys, leaves)])
    attn = out["layers"]["attn"]
    KVH, G, dh = heads
    wq = attn["wq"]
    first = wq.reshape(wq.shape[:2] + (KVH, G, dh))[:, :, :, 0]
    attn["wk"] = (SELF * first.reshape(attn["wk"].shape)
                  + math.sqrt(1 - SELF ** 2) * attn["wk"])
    return jax.tree.map(lambda x: x.astype(dtype), out)


def make(m: dict, seed: int, dtype: str = "bfloat16"):
    """The served weights for dims ``m`` from ``seed``, on the default
    device."""
    lay = layout(m)
    leaves, treedef = jax.tree.flatten(
        lay, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and isinstance(x[0], tuple))
    return _make((tuple(leaves), treedef), seed_key(seed, 0),
                 jnp.dtype(dtype).name,
                 (m["KVH"], m["H"] // m["KVH"], m["dh"]))
