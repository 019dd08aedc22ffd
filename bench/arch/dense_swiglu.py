"""The dense SwiGLU decoder block: pre-norm RMSNorm, rotary embedding by
halves, grouped-query causal softmax attention on every layer, a SwiGLU
MLP, a final norm and an untied head (internlm2, Qwen1.5).

Everything of the benchmark that depends on the block: the check that the
program serves it, the model dims, the served weights, the float32
reference's hidden states and the work of the algorithm. A configuration
file names this module with ``"bench_arch": "dense_swiglu"``;
``cell.arch`` loads it.

The served tree: ``embed`` [Vp, d], ``final_norm`` [d], ``head`` [d, Vp]
and a stack ``layers`` with leading axis L holding ``ln1``/``ln2`` [L, d],
``attn`` (``wq`` [L, d, H*dh], ``wk``/``wv`` [L, d, KVH*dh], ``wo``
[L, H*dh, d]) and ``mlp`` (``w1``/``w3`` [L, d, ff] gate and up, ``w2``
[L, ff, d] down). Scales are 1/sqrt(fan-in), 0.02 for the embedding and
head, and ``weights.QK_GAIN``/sqrt(d) for q and k, with each KV head's key
leant towards its first query head's (``weights.lean_keys``).

The work counts what the computation needs, not what an implementation
does: attention reads each row's K and V once over its exact live
context, a prefill's attention counts its causal half, and no block or
page rounding enters. So the yardstick reads the same work whatever
implements it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import weights

# kernel name -> pattern of its operation's name in the device trace: the
# paged decode kernel's custom call carries the name of its pallas_call.
# Anchored at the start, so that an op whose HLO text names the kernel's
# result among its operands is not credited with it.
KERNELS = {"paged_attn": r"^%?paged_attention(\.\d+)?(\s|$)"}

# published config key -> the program's ModelConfig field it sets
_WIDTHS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size",
}


def _head_dim(cf: dict) -> int:
    return cf.get("head_dim",
                  cf["hidden_size"] // cf["num_attention_heads"])


def check(cf: dict, cfg) -> None:
    """Raises where the program's ModelConfig ``cfg`` is not the published
    dense SwiGLU block of configuration file ``cf`` at its widths."""
    for key, field in _WIDTHS.items():
        if getattr(cfg, field) != cf[key]:
            raise SystemExit(f"bench: {cf['name']}: program {field}="
                             f"{getattr(cfg, field)} but {key}={cf[key]}")
    if cfg.d_head != _head_dim(cf) or cfg.family != "dense" or \
            cfg.act != "swiglu" or cfg.qk_norm:
        raise SystemExit(f"bench: {cf['name']}: the program's "
                         f"{cf['program_arch']} is not the published dense "
                         f"SwiGLU block")


def dims(cf: dict, vocab_multiple: int = 256) -> dict:
    """Model dims from a configuration file's published keys; the head
    size is ``head_dim`` where the file gives one."""
    V = cf["vocab_size"]
    return {"d": cf["hidden_size"], "L": cf["num_hidden_layers"],
            "ff": cf["intermediate_size"], "H": cf["num_attention_heads"],
            "KVH": cf["num_key_value_heads"], "dh": _head_dim(cf),
            "V": V, "Vp": -(-V // vocab_multiple) * vocab_multiple,
            "eps": float(cf["rms_norm_eps"]),
            "theta": float(cf["rope_theta"])}


# ---- weights ---------------------------------------------------------------

def layout(m: dict) -> dict:
    """Leaf -> (shape, scale, keep) of the served tree (see
    ``weights``)."""
    d, L, ff, V, Vp = m["d"], m["L"], m["ff"], m["V"], m["Vp"]
    qd, kvd = m["H"] * m["dh"], m["KVH"] * m["dh"]
    return {
        "embed": ((Vp, d), 0.02, (0, V)),
        "final_norm": ((d,), None, None),
        "head": ((d, Vp), 0.02, (1, V)),
        "layers": {
            "ln1": ((L, d), None, None),
            "ln2": ((L, d), None, None),
            "attn": {"wq": ((L, d, qd), weights.QK_GAIN / math.sqrt(d),
                            None),
                     "wk": ((L, d, kvd), weights.QK_GAIN / math.sqrt(d),
                            None),
                     "wv": ((L, d, kvd), 1 / math.sqrt(d), None),
                     "wo": ((L, qd, d), 1 / math.sqrt(qd), None)},
            "mlp": {"w1": ((L, d, ff), 1 / math.sqrt(d), None),
                    "w3": ((L, d, ff), 1 / math.sqrt(d), None),
                    "w2": ((L, ff, d), 1 / math.sqrt(ff), None)},
        },
    }


@partial(jax.jit, static_argnums=(0, 2, 3))
def _make(spec, key, dtype, heads):
    out = weights.draw(spec, key)
    attn = out["layers"]["attn"]
    attn["wk"] = weights.lean_keys(attn["wq"], attn["wk"], *heads)
    return jax.tree.map(lambda x: x.astype(dtype), out)


def make(m: dict, seed: int, dtype: str = "bfloat16"):
    """The served weights for dims ``m`` from ``seed``, on the default
    device."""
    return _make(weights.spec(layout(m)), weights.seed_key(seed, 0),
                 jnp.dtype(dtype).name, (m["KVH"], m["dh"]))


# ---- reference -------------------------------------------------------------

@partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, layers, i, cos, sin, *, dims, fp8):
    H, KVH, dh, eps = dims
    S = x.shape[0]
    G = H // KVH
    QB = R.Q_BLOCK
    lp = jax.tree.map(lambda a: a[i], layers)
    h = R.norm(x, lp["ln1"], eps)
    q = R.rope(R.mm(h, lp["attn"]["wq"], fp8).reshape(S, H, dh), cos, sin)
    k = R.rope(R.mm(h, lp["attn"]["wk"], fp8).reshape(S, KVH, dh), cos, sin)
    v = R.mm(h, lp["attn"]["wv"], fp8).reshape(S, KVH, dh)
    if fp8:
        q, k, v = R.fp8_round(q, -1), R.fp8_round(k, -1), R.fp8_round(v, 0)
    q = q.reshape(S // QB, QB, KVH, G, dh)
    kpos = jnp.arange(S)

    def block(args):
        qb, b = args
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k,
                       precision=R.HI) / math.sqrt(dh)
        qpos = b * QB + jnp.arange(QB)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = R.fp8_round(p, -1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=R.HI)

    a = jax.lax.map(block, (q, jnp.arange(S // QB)))
    x = x + R.mm(a.reshape(S, H * dh), lp["attn"]["wo"], fp8)
    h = R.norm(x, lp["ln2"], eps)
    gate = jax.nn.silu(R.mm(h, lp["mlp"]["w1"], fp8))
    up = R.mm(h, lp["mlp"]["w3"], fp8)
    return x + R.mm(gate * up, lp["mlp"]["w2"], fp8)


def hidden(params, m: dict, seq: np.ndarray, *, fp8: bool = False):
    """Final-norm hidden states [len(seq), d] in float32."""
    ids = R.padded_ids(seq)
    cos, sin = R.rope_tables(len(ids), m["dh"], m["theta"])
    dims = (m["H"], m["KVH"], m["dh"], m["eps"])
    x = R.embed(params["embed"], jnp.asarray(ids))
    for i in range(m["L"]):
        x = _layer(x, params["layers"], jnp.int32(i), cos, sin, dims=dims,
                   fp8=fp8)
    return R.final_norm(x, params["final_norm"], eps=m["eps"])[:len(seq)]


# ---- work ------------------------------------------------------------------

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


def kernel_work(m: dict, ctx: int) -> dict[str, tuple[int, int]]:
    """(FLOPs, HBM bytes) of each kernel of ``KERNELS`` for one decode row
    at context ``ctx``."""
    return {"paged_attn": decode_attention(m, ctx)}


def kv_bytes_per_token(m: dict, bytes_per_el: int = 2) -> int:
    return m["L"] * 2 * m["KVH"] * m["dh"] * bytes_per_el


def step_weight_bytes(m: dict, bytes_per_el: int = 2) -> int:
    """Weights one decode step has to read: every layer's projections and
    the head (the embedding is gathered a row per token)."""
    return bytes_per_el * (m["L"] * layer_matmul_params(m)
                           + m["d"] * m["V"])


def step_bytes(m: dict, contexts) -> int:
    """The least HBM bytes one decode step of a batch at ``contexts`` reads:
    the step's weights and every row's live KV."""
    return step_weight_bytes(m) + sum(contexts) * kv_bytes_per_token(m)


def session_pages(m: dict, engine: dict, tokens: int) -> int:
    """Pool pages one session of ``tokens`` tokens holds: every layer's KV
    is paged over the whole context."""
    return -(-tokens // engine["page_size"])
