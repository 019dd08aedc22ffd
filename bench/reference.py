"""Plain float32 reference, and the same reference computed in float8 as
the control that has to fail: what every architecture shares.

An architecture module (``bench/arch/<name>.py``) gives ``hidden``, its
block's final-norm hidden states over a whole sequence, built from the
pieces here; ``served_gaps`` reads the logits from them. The reference
imports nothing of the program and reads the weights in the served layout
that the module describes: norm gains are offsets from 1, the head's
columns past the published vocabulary are padding and are left out. Every
matmul runs at ``Precision.HIGHEST``, since a float32 matmul on a TPU
otherwise runs in bfloat16.

A module runs a whole sequence a layer at a time, with attention in blocks
of ``Q_BLOCK`` query rows, so that a 30k-token sequence fits beside the
weights; logits are made only at the positions asked for, in blocks of
rows.

The control (``fp8=True``) rounds every matmul input to float8 e4m3 with
a scale per row of activations and per output column of weights: the
weights, the activations, q, k, v and the attention probabilities.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512                 # query rows per attention block
ROW_BLOCK = 256               # positions per block of logits
SEQ_MULTIPLE = 1024           # sequences are padded to a multiple of this
E4M3_MAX = 448.0


def fp8_round(x, axis):
    """x rounded to float8 e4m3 with one scale per slice along ``axis``
    (the reduction axis of the matmul it feeds), back in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, fp8):
    """x [..., k] @ w [k, n] in float32 (float8 inputs for the control)."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = fp8_round(x, -1), fp8_round(w, 0)
    return jnp.dot(x, w, precision=HI)


def norm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))


def rope(x, cos, sin):
    """x [S, heads, dh]; rotation of the two halves of each head."""
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rope_tables(S: int, dh: int, theta: float):
    inv = 1.0 / theta ** (np.arange(0, dh // 2, dtype=np.float64) * 2 / dh)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def padded_ids(seq: np.ndarray) -> np.ndarray:
    """``seq`` padded with id 0 to a multiple of ``SEQ_MULTIPLE`` (and so of
    ``Q_BLOCK``); the padding comes after every real position, so causal
    attention keeps it out of their rows."""
    S = -(-len(seq) // SEQ_MULTIPLE) * SEQ_MULTIPLE
    ids = np.zeros((S,), np.int32)
    ids[:len(seq)] = seq
    return ids


@jax.jit
def embed(table, ids):
    return jnp.take(table, ids, axis=0).astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps",))
def final_norm(x, g, *, eps):
    return norm(x, g, eps)


@partial(jax.jit, static_argnames=("V", "fp8"))
def _logits(h, head, *, V, fp8):
    return mm(h, head[:, :V], fp8)


@jax.jit
def _gaps(ref, chosen):
    """Per row: how far the reference logit of ``chosen`` lies below the
    reference's best (inf where ``chosen`` is outside the vocabulary)."""
    V = ref.shape[-1]
    ok = (chosen >= 0) & (chosen < V)
    got = jnp.take_along_axis(ref, jnp.clip(chosen, 0, V - 1)[:, None],
                              axis=-1)[:, 0]
    return jnp.where(ok, ref.max(-1) - got, jnp.inf)


def served_gaps(arch, params, m: dict, prompt: np.ndarray,
                served: np.ndarray, *, control: bool = False) -> np.ndarray:
    """For each served token j (the first one comes from the prefill), the
    gap by which the reference's logit of it at its position lies below
    the reference's best there, with the hidden states of architecture
    module ``arch``. With ``control``, the gap of the token the float8
    reference puts first at the same positions instead."""
    p, n = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    # positions padded to whole blocks of rows (the pad repeats the last
    # position and is dropped at the end)
    pos = np.arange(p - 1, p - 1 + -(-n // ROW_BLOCK) * ROW_BLOCK)
    pos = jnp.asarray(np.minimum(pos, p + n - 2))
    tok = np.zeros((len(pos),), np.int32)
    tok[:n] = served
    h = arch.hidden(params, m, seq)[pos]
    hc = arch.hidden(params, m, seq, fp8=True)[pos] if control else None
    out = []
    for r in range(0, len(pos), ROW_BLOCK):
        ref = _logits(h[r:r + ROW_BLOCK], params["head"], V=m["V"],
                      fp8=False)
        if control:
            chosen = jnp.argmax(_logits(hc[r:r + ROW_BLOCK], params["head"],
                                        V=m["V"], fp8=True), -1)
        else:
            chosen = jnp.asarray(tok[r:r + ROW_BLOCK])
        out.append(np.asarray(_gaps(ref, chosen)))
    return np.concatenate(out)[:n]
