"""Seeded random weights, shared by the architecture modules
(``bench/arch/<name>.py``): each module describes its served tree as a
layout of leaves and draws it here, on the device from the seed in one
jitted call of its own, in the dtype it is served in.

A layout maps each leaf to ``(shape, scale, keep)``: ``scale`` None marks
a norm gain, stored as an offset from 1 and drawn at 0.1; ``keep`` (axis,
n) zeroes the vocabulary padding past index n along that axis, as a
checkpoint loader leaves it, so no padding id is ever the largest logit.

Attention blocks draw q and k at ``QK_GAIN``/sqrt(d) each: a query's score
against a key then has a standard deviation of ``QK_GAIN`` squared, 2.9,
so a head puts most of its weight on a few dozen of the 10k-20k keys of a
long context, as trained heads do, and what attention reads moves the
residual stream as much as the feed-forward does. At a freshly initialised
model's scales (scores of standard deviation 1, the attention output at
1/sqrt(2 L H dh)) attention spreads evenly over the context and adds
almost nothing to the residual, so a correctness check could not see the
paged KV or the kernel. At a gain of 2 (scores of standard deviation 4),
bf16 rounding moved the served tokens' logit gaps four times as much, for
no more reach.

Each KV head's key projection also leans towards the query projection of
the first query head it serves (``lean_keys``): k = ``SELF`` q +
sqrt(1 - ``SELF``^2) k', so that head scores a token against its own key
at about ``SELF`` ``QK_GAIN``^2 sqrt(dh), 11, level with the best of 20k
other keys (about 13). Decoding then reads the key each step has just
written as much as the far context, as trained heads attend to the current
token, and a decode step whose KV write goes astray shows.
"""
from __future__ import annotations

import math

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


def spec(layout: dict) -> tuple:
    """A layout flattened to a hashable ``(leaves, treedef)``, to pass to a
    jitted draw as a static argument."""
    leaves, treedef = jax.tree.flatten(
        layout, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and isinstance(x[0], tuple))
    return tuple(leaves), treedef


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


def draw(spec: tuple, key) -> dict:
    """The float32 tree of ``spec``, one key per leaf in flattening order;
    called inside an architecture module's jitted ``make``."""
    leaves, treedef = spec
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        _leaf(k, shape, scale, keep)
        for k, (shape, scale, keep) in zip(keys, leaves)])


def lean_keys(q, k, n_kv: int, dh: int):
    """The key projection ``k`` [..., n_kv*dh] leant ``SELF`` towards the
    query projection ``q`` [..., n_kv*G*dh] of the first query head that
    each KV head serves."""
    G = q.shape[-1] // (n_kv * dh)
    first = q.reshape(q.shape[:-1] + (n_kv, G, dh))[..., 0, :]
    return SELF * first.reshape(k.shape) + math.sqrt(1 - SELF ** 2) * k
