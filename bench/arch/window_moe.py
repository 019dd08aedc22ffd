"""Window and full attention layers with sparse experts (Mellum2): pre-norm
RMSNorm, grouped-query causal softmax attention whose layers either see
the last ``sliding_window`` keys (plain rotary embedding) or the whole
context (YaRN rotary embedding, cos and sin scaled by its attention
factor), and on every layer a top-k softmax-routed mixture of SwiGLU
experts renormalised over the chosen k, with no shared expert; a final
norm and an untied head.

The chip holds one share of an expert-parallel deployment: experts
``[first, first + held)`` of every layer's ``E``, beside all of attention,
the embedding and the head. The router keeps its ``E`` outputs and its
top-k; each layer adds only its held experts' part of a token's output, in
the program and here alike.

Everything of the benchmark that depends on the block: the check that the
program serves it, the model dims, the served weights, the float32
reference's hidden states and the work of the algorithm. A configuration
file names this module with ``"bench_arch": "window_moe"``.

The served tree: ``embed`` [Vp, d], ``final_norm`` [d], ``head`` [d, Vp]
and a stack ``layers`` with leading axis L holding ``ln1``/``ln2`` [L, d],
``attn`` (``wq`` [L, d, H*dh], ``wk``/``wv`` [L, d, KVH*dh], ``wo``
[L, H*dh, d]) and ``moe`` (``router`` [L, d, E], ``w1``/``w3``
[L, held, d, ff] gate and up, ``w2`` [L, held, ff, d] down). Attention is
drawn as in the dense block (``weights``), but for the q and k gains of
each layer kind (``make``); the router at 1/sqrt(d), so a token's logits
over the experts have a standard deviation of about 1.

The work counts what the computation needs: a window layer's attention
reads ``min(ctx, window)`` keys, a full layer's ``ctx``; a token's expert
work is its top-k's expected share held here, ``k * held / E`` experts.
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
# full layers' paged decode call and the window layers' windowed slice of
# it, each named by its pallas_call (anchored, so neither matches the
# other, nor an op that names the kernel's result among its operands)
KERNELS = {"paged_attn": r"^%?paged_attention(\.\d+)?(\s|$)",
           "paged_attn_window": r"^%?paged_attention_window(\.\d+)?(\s|$)"}

Q_BLOCK = 256          # query rows per attention block (32 heads' scores)

# q/k gain of a window layer, in place of ``weights.QK_GAIN`` (see ``make``)
WINDOW_QK_GAIN = 1.2

# published ``layer_types`` -> the program's block kinds
_KINDS = {"sliding_attention": "local", "full_attention": "attn"}
# published config key -> the program's ModelConfig field it sets
_WIDTHS = {
    "hidden_size": "d_model",
    "moe_intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head",
    "vocab_size": "vocab_size",
    "sliding_window": "sliding_window",
    "num_experts_per_tok": "moe_top_k",
    "num_experts": "moe_held",
    "num_experts_published": "n_experts",
    "experts_held_first": "moe_held_first",
}
# YaRN key of ``rope_parameters.full_attention`` -> ModelConfig field
_YARN = {
    "factor": "yarn_factor",
    "original_max_position_embeddings": "yarn_original_max",
    "attention_factor": "yarn_attention_factor",
    "rope_theta": "rope_theta",
}
# YaRN keys the program holds as constants -> their names in
# ``repro.models.layers``
_YARN_CONST = {"beta_fast": "YARN_BETA_FAST", "beta_slow": "YARN_BETA_SLOW"}


def _refuse(cf, what):
    raise SystemExit(f"bench: {cf['name']}: the program's "
                     f"{cf['program_arch']} {what}")


def check(cf: dict, cfg) -> None:
    """Raises where the program's ModelConfig ``cfg`` is not the published
    block of configuration file ``cf``: its layer pattern, window, YaRN
    parameters, experts (held, published, first held, per token) and
    widths."""
    for key, field in _WIDTHS.items():
        if getattr(cfg, field) != cf[key]:
            _refuse(cf, f"has {field}={getattr(cfg, field)} but the file "
                        f"{key}={cf[key]}")
    kinds = [_KINDS[t] for t in cf["layer_types"]]
    if list(cfg.block_kinds()) != kinds[:cfg.n_layers] or \
            len(kinds) != cf["num_hidden_layers"]:
        _refuse(cf, f"has layer kinds {cfg.block_kinds()}, not the file's "
                    f"layer_types")
    rp = cf["rope_parameters"]
    full, win = rp["full_attention"], rp["sliding_attention"]
    if full["rope_type"] != "yarn" or win["rope_type"] != "default" or \
            win["rope_theta"] != cfg.rope_theta:
        _refuse(cf, "does not rotate full layers by YaRN and window layers "
                    "by plain rope at one theta")
    for key, field in _YARN.items():
        if getattr(cfg, field) != full[key]:
            _refuse(cf, f"has {field}={getattr(cfg, field)} but the file's "
                        f"YaRN {key}={full[key]}")
    from repro.models import layers
    for key, name in _YARN_CONST.items():
        if getattr(layers, name) != full[key]:
            _refuse(cf, f"has {name}={getattr(layers, name)} but the file's "
                        f"YaRN {key}={full[key]}")
    if set(cf["mlp_layer_types"]) != {"sparse"} or not cf["norm_topk_prob"] \
            or cfg.family != "moe" or cfg.act != "swiglu" or cfg.qk_norm \
            or cf["hidden_act"] != "silu" or cf["attention_bias"] \
            or cfg.rope_kind != "rope":
        _refuse(cf, "is not the published block: sparse SwiGLU experts "
                    "renormalised over the top k on every layer, no q/k "
                    "norm, no attention bias")


def dims(cf: dict, vocab_multiple: int = 256) -> dict:
    """Model dims from a configuration file's published keys."""
    V = cf["vocab_size"]
    full = cf["rope_parameters"]["full_attention"]
    L = cf["num_hidden_layers"]
    return {"d": cf["hidden_size"], "L": L,
            "ff": cf["moe_intermediate_size"],
            "H": cf["num_attention_heads"],
            "KVH": cf["num_key_value_heads"], "dh": cf["head_dim"],
            "E": cf["num_experts_published"], "held": cf["num_experts"],
            "first": cf["experts_held_first"],
            "k": cf["num_experts_per_tok"],
            "window": cf["sliding_window"],
            "windowed": tuple(t == "sliding_attention"
                              for t in cf["layer_types"][:L]),
            "V": V, "Vp": -(-V // vocab_multiple) * vocab_multiple,
            "eps": float(cf["rms_norm_eps"]),
            "theta": float(cf["rope_theta"]),
            "yarn": tuple(float(full[k]) for k in (
                "factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor"))}


# ---- weights ---------------------------------------------------------------

def layout(m: dict) -> dict:
    """Leaf -> (shape, scale, keep) of the served tree (see
    ``weights``)."""
    d, L, ff, V, Vp = m["d"], m["L"], m["ff"], m["V"], m["Vp"]
    qd, kvd, n = m["H"] * m["dh"], m["KVH"] * m["dh"], m["held"]
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
            "moe": {"router": ((L, d, m["E"]), 1 / math.sqrt(d), None),
                    "w1": ((L, n, d, ff), 1 / math.sqrt(d), None),
                    "w3": ((L, n, d, ff), 1 / math.sqrt(d), None),
                    "w2": ((L, n, ff, d), 1 / math.sqrt(ff), None)},
        },
    }


@partial(jax.jit, static_argnums=(0, 2, 3, 4))
def _make(spec, key, dtype, heads, qk_scale):
    out = weights.draw(spec, key)
    attn = out["layers"]["attn"]
    attn["wk"] = weights.lean_keys(attn["wq"], attn["wk"], *heads)
    s = jnp.asarray(qk_scale, jnp.float32)[:, None, None]
    attn["wq"], attn["wk"] = attn["wq"] * s, attn["wk"] * s
    return jax.tree.map(lambda x: x.astype(dtype), out)


def make(m: dict, seed: int, dtype: str = "bfloat16"):
    """The served weights for dims ``m`` from ``seed``, on the default
    device.

    A full layer's scores spread as the dense block's (``weights.QK_GAIN``,
    a standard deviation of 2.9): YaRN scales its cos and sin by its
    attention factor, so its scores by the factor squared, and its q and k
    are drawn that factor smaller. At the factor's 1.28 the scores spread
    4.7, and the bf16 program's logit gaps read 1.4-2.1, within 1.5x of the
    float8 control's 2.5-3.2 (TPU v5e, seeds 3000016211, 4000016101-4000016103
    and 4000016201-4000016206).

    A window layer draws q and k at ``WINDOW_QK_GAIN``, so its head puts the
    share of its weight on the current token's own key (``weights.SELF``)
    that a full layer's head puts there over the mix's median context, 14k
    keys: 9%. At the dense gain, over a window of 1024 keys, that share is
    58%, and the rest sits on the window's one or two best keys; near-ties
    between them moved the bf16 program's gaps (0.39-1.04 over 21 seeds) to
    within 1.3x of the float8 control's (1.27-1.49)."""
    full = 1 / m["yarn"][4]
    window = WINDOW_QK_GAIN / weights.QK_GAIN
    return _make(weights.spec(layout(m)), weights.seed_key(seed, 0),
                 jnp.dtype(dtype).name, (m["KVH"], m["dh"]),
                 tuple(window if w else full for w in m["windowed"]))


# ---- reference -------------------------------------------------------------

def yarn_inv_freq(dh: int, theta: float, factor: float, original: float,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies in float64, transcribed from HF
    ``_compute_yarn_parameters`` (``truncate`` true): the interpolated
    frequency ``1 / (factor * theta^(2i/dh))`` and the plain one blended
    by a ramp that is 0 below the dimension turning ``beta_fast`` times over
    ``original`` positions and 1 above the one turning ``beta_slow``
    times."""
    def correction_dim(rotations):
        return (dh * math.log(original / (rotations * 2 * math.pi))) / (
            2 * math.log(theta))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dh - 1)
    if low == high:
        high += 0.001
    pos_freqs = theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ramp = np.clip((np.arange(dh // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation = 1 - ramp
    return (1 / (factor * pos_freqs) * (1 - extrapolation)
            + 1 / pos_freqs * extrapolation)


def rope_tables(S: int, m: dict, full: bool):
    """(cos, sin) [S, dh/2] in float32 of a window layer (plain rope) or a
    full layer (YaRN, both scaled by its attention factor)."""
    if not full:
        return R.rope_tables(S, m["dh"], m["theta"])
    factor, original, fast, slow, scale = m["yarn"]
    inv = yarn_inv_freq(m["dh"], m["theta"], factor, original, fast, slow)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def _attention(q, k, v, window, fp8):
    """Causal attention of q [S, KVH, G, dh] over k, v [S, KVH, dh], a
    block of ``Q_BLOCK`` queries at a time; with ``window`` a query sees
    only keys less than ``window`` positions back (HF's sliding window:
    key k masked for query q where q - k >= window), and a block reads only
    the keys its window can reach."""
    S, KVH, G, dh = q.shape
    QB = Q_BLOCK
    span = S if not window else QB + window
    if window:               # keys before position 0 are padding, masked
        k = jnp.concatenate([jnp.zeros((window, KVH, dh), k.dtype), k])
        v = jnp.concatenate([jnp.zeros((window, KVH, dh), v.dtype), v])
    qb = q.reshape(S // QB, QB, KVH, G, dh)

    def block(args):
        qblk, b = args
        start = b * QB if window else 0
        kb = jax.lax.dynamic_slice_in_dim(k, start, span)
        vb = jax.lax.dynamic_slice_in_dim(v, start, span)
        kpos = start - (window or 0) + jnp.arange(span)
        qpos = b * QB + jnp.arange(QB)
        s = jnp.einsum("qkgd,tkd->kgqt", qblk, kb,
                       precision=R.HI) / math.sqrt(dh)
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok = ok & (kpos[None, :] >= 0) & \
                (qpos[:, None] - kpos[None, :] < window)
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        if fp8:
            p = R.fp8_round(p, -1)
        return jnp.einsum("kgqt,tkd->qkgd", p, vb, precision=R.HI)

    return jax.lax.map(block, (qb, jnp.arange(S // QB)))


def _experts(h, mp, dims, fp8):
    """The held experts' part of each row's output, h [S, d] normed: route
    over all E experts in float32, keep the top k, renormalise over them,
    and add each held expert's SwiGLU output times its weight."""
    E, k, first = dims
    logits = R.mm(h, mp["router"], fp8)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    y = jnp.zeros_like(h)
    for j in range(mp["w1"].shape[0]):
        weight = jnp.where(top_e == first + j, top_p, 0.0).sum(-1)
        gate = jax.nn.silu(R.mm(h, mp["w1"][j], fp8))
        up = R.mm(h, mp["w3"][j], fp8)
        y = y + weight[:, None] * R.mm(gate * up, mp["w2"][j], fp8)
    return y


@partial(jax.jit, static_argnames=("dims", "window", "fp8"))
def _layer(x, layers, i, cos, sin, *, dims, window, fp8):
    H, KVH, dh, eps, moe = dims
    S = x.shape[0]
    lp = jax.tree.map(lambda a: a[i], layers)
    h = R.norm(x, lp["ln1"], eps)
    q = R.rope(R.mm(h, lp["attn"]["wq"], fp8).reshape(S, H, dh), cos, sin)
    k = R.rope(R.mm(h, lp["attn"]["wk"], fp8).reshape(S, KVH, dh), cos, sin)
    v = R.mm(h, lp["attn"]["wv"], fp8).reshape(S, KVH, dh)
    if fp8:
        q, k, v = R.fp8_round(q, -1), R.fp8_round(k, -1), R.fp8_round(v, 0)
    a = _attention(q.reshape(S, KVH, H // KVH, dh), k, v, window, fp8)
    x = x + R.mm(a.reshape(S, H * dh), lp["attn"]["wo"], fp8)
    return x + _experts(R.norm(x, lp["ln2"], eps), lp["moe"], moe, fp8)


def hidden(params, m: dict, seq: np.ndarray, *, fp8: bool = False):
    """Final-norm hidden states [len(seq), d] in float32."""
    ids = R.padded_ids(seq)
    tables = {full: rope_tables(len(ids), m, full) for full in (False, True)}
    dims = (m["H"], m["KVH"], m["dh"], m["eps"],
            (m["E"], m["k"], m["first"]))
    x = R.embed(params["embed"], jnp.asarray(ids))
    for i, windowed in enumerate(m["windowed"]):
        cos, sin = tables[not windowed]
        x = _layer(x, params["layers"], jnp.int32(i), cos, sin, dims=dims,
                   window=m["window"] if windowed else 0, fp8=fp8)
    return R.final_norm(x, params["final_norm"], eps=m["eps"])[:len(seq)]


# ---- work ------------------------------------------------------------------

def attn_params(m: dict) -> int:
    """A layer's attention projections: q, k, v and o."""
    qd, kvd = m["H"] * m["dh"], m["KVH"] * m["dh"]
    return m["d"] * (qd + 2 * kvd) + qd * m["d"]


def expert_params(m: dict) -> int:
    """One expert's gate, up and down projections."""
    return 3 * m["d"] * m["ff"]


def n_window(m: dict) -> int:
    return sum(m["windowed"])


def keys_read(m: dict, ctx: int) -> tuple[int, int]:
    """(full-layer keys, window-layer keys) a token at context ``ctx``
    attends to, summed over the layers of each kind."""
    nw = n_window(m)
    return (m["L"] - nw) * ctx, nw * min(ctx, m["window"])


def held_per_token(m: dict) -> float:
    """Experts held here in a token's top k, in expectation under uniform
    routing."""
    return m["k"] * m["held"] / m["E"]


def decode_flops(m: dict, ctx: int) -> int:
    """One decoded token at context ``ctx`` (its own key included): every
    layer's attention projections and router, its held share of the top-k
    experts, attention over the keys each layer reads, and the head."""
    per_layer = 2 * (attn_params(m) + m["d"] * m["E"]
                     + held_per_token(m) * expert_params(m))
    full, win = keys_read(m, ctx)
    return int(m["L"] * per_layer + 4 * m["H"] * m["dh"] * (full + win)
               + 2 * m["d"] * m["V"])


def prefill_flops(m: dict, n: int) -> int:
    """A prompt of ``n`` tokens: every token's matmuls, causal attention
    (position i attends to i + 1 keys on a full layer, to min(i + 1,
    window) on a window layer) and the last position's logits."""
    per_token = 2 * (attn_params(m) + m["d"] * m["E"]
                     + held_per_token(m) * expert_params(m))
    w = m["window"]
    full_pairs = n * (n + 1) // 2
    win_pairs = (full_pairs if n <= w
                 else w * (w + 1) // 2 + (n - w) * w)
    pairs = (m["L"] - n_window(m)) * full_pairs + n_window(m) * win_pairs
    return int(m["L"] * per_token * n + 4 * m["H"] * m["dh"] * pairs
               + 2 * m["d"] * m["V"])


def kernel_work(m: dict, ctx: int, bytes_per_el: int = 2
                ) -> dict[str, tuple[int, int]]:
    """(FLOPs, HBM bytes) of each kernel of ``KERNELS`` for one decode row
    at context ``ctx``: the full layers' calls over ``ctx`` keys and the
    window layers' over ``min(ctx, window)``, each reading K and V once and
    Q and O once."""
    qd = m["H"] * m["dh"]
    kv = 2 * m["KVH"] * m["dh"] * bytes_per_el
    out = {}
    for name, keys, layers in (
            ("paged_attn", ctx, m["L"] - n_window(m)),
            ("paged_attn_window", min(ctx, m["window"]), n_window(m))):
        out[name] = (layers * 4 * qd * keys,
                     layers * (keys * kv + 2 * qd * bytes_per_el))
    return out


def step_bytes(m: dict, contexts, bytes_per_el: int = 2) -> int:
    """The least HBM bytes one decode step of a batch at ``contexts`` reads:
    every layer's attention projections and router, the held experts that
    the batch's rows touch in expectation under uniform routing (each row
    picks a held expert with chance k / E, so ``held * (1 - (1 - k/E)^N)``
    of them for N rows), the head, and the live KV: a full layer's over
    each row's context, a window layer's over its last ``window`` keys."""
    n = len(contexts)
    touched = m["held"] * (1 - (1 - m["k"] / m["E"]) ** n)
    params = m["L"] * (attn_params(m) + m["d"] * m["E"]
                       + touched * expert_params(m)) + m["d"] * m["V"]
    keys = sum(sum(keys_read(m, c)) for c in contexts)
    return int(bytes_per_el * (params
                               + keys * 2 * m["KVH"] * m["dh"]))


def session_pages(m: dict, engine: dict, tokens: int) -> int:
    """Pool pages one session of ``tokens`` tokens holds: the pool has one
    page geometry, so every layer, window layers too, is paged over the
    whole context."""
    return -(-tokens // engine["page_size"])
