"""mellum2-12b-a2.5b — window/full attention with sparse experts
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json].

28 layers at d 2304: three sliding-window layers (1024 keys, plain rope) to
each full layer (YaRN, factor 16 over 8192 original positions); GQA 32/4 at
head_dim 128 (q_dim 4096 != d_model). Every feed-forward is a 64-expert
top-8 MoE of width 896, renormalised over the chosen 8, with no shared
expert (the config's ``intermediate_size`` 7168 is unused).

Served as one chip's share of an 8-chip expert-parallel deployment: this
chip holds experts 0-7 of every layer beside all of attention, the
embedding and the head, and routes over all 64 with no capacity
(``moe_held``). The published MTP head is not served.
"""
from repro.configs.base import ModelConfig, register, set_skips

CONFIG = register(ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    d_head=128,
    d_ff=896,                        # expert width (moe_intermediate_size)
    vocab_size=98304,
    pattern=("local", "local", "local", "attn"),
    sliding_window=1024,
    act="swiglu",
    norm_eps=1e-6,
    rope_theta=500_000.0,
    yarn_factor=16.0,
    yarn_original_max=8192,
    yarn_attention_factor=1.2772588722239782,
    n_experts=64,
    moe_top_k=8,
    moe_held=8,
    moe_held_first=0,
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
))
set_skips(CONFIG.name, {"long_500k"})
