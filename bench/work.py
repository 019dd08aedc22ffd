"""The work of the algorithm, from a configuration's shapes: model FLOPs
per token processed, and the FLOPs and bytes of paged decode attention.

These count what the computation needs, not what an implementation does:
attention reads each row's K and V once over its exact live context, a
prefill's attention counts its causal half, and no block or page rounding
enters. So the yardstick reads the same work whatever implements it. ``m``
is ``weights.dims(config_file)``.
"""
from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one layer: q, k, v, o and
    the SwiGLU gate, up and down projections."""
    qd, kvd = m["H"] * m["dh"], m["KVH"] * m["dh"]
    return m["d"] * (qd + 2 * kvd) + qd * m["d"] + 3 * m["d"] * m["ff"]


def head_flops(m: dict) -> int:
    """One position's logits over the published vocabulary."""
    return 2 * m["d"] * m["V"]


def decode_flops(m: dict, ctx: int) -> int:
    """One decoded token whose attention spans ``ctx`` keys (its own
    included): every layer's matmuls and attention, and the head."""
    return (m["L"] * (2 * layer_matmul_params(m)
                      + 4 * m["H"] * m["dh"] * ctx) + head_flops(m))


def prefill_flops(m: dict, n: int) -> int:
    """A prompt of ``n`` tokens: matmuls for every token, causal attention
    (position i attends to i + 1 keys) and the last position's logits."""
    attn = 2 * m["H"] * m["dh"] * n * (n + 1)     # QK and PV, causal half
    return m["L"] * (2 * layer_matmul_params(m) * n + attn) + head_flops(m)


def decode_attention(m: dict, ctx: int, bytes_per_el: int = 2
                     ) -> tuple[int, int]:
    """(FLOPs, HBM bytes) of paged decode attention for one row at context
    ``ctx`` summed over the layers: QK and PV over the live context, K and
    V read once, Q read and O written once."""
    qd = m["H"] * m["dh"]
    flops = m["L"] * 4 * qd * ctx
    kv = 2 * ctx * m["KVH"] * m["dh"] * bytes_per_el
    return flops, m["L"] * (kv + 2 * qd * bytes_per_el)


def kv_bytes_per_token(m: dict, bytes_per_el: int = 2) -> int:
    return m["L"] * 2 * m["KVH"] * m["dh"] * bytes_per_el


def step_weight_bytes(m: dict, bytes_per_el: int = 2) -> int:
    """Weights one decode step has to read: every layer's projections and
    the head (the embedding is gathered a row per token)."""
    return bytes_per_el * (m["L"] * layer_matmul_params(m)
                           + m["d"] * m["V"])
