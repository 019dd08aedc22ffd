"""A tiny window/full MoE cell for CPU tests: the program's reduced twin
of mellum2-12b-a2.5b (d 64, 4 heads over 2 KV heads of 16, 4 layers of
which 3 see a 16-token window and 1 the whole context under YaRN over 64
original positions, 8 experts of width 128 routed top-4, experts 2-3
held, vocab 256) under the decode_long mix, scaled to a small pool."""
import cell
import harness
import tiny

KINDS = ["sliding_attention"] * 3 + ["full_attention"]


def config(dtype="float32"):
    return {"name": "tiny-moe", "bench_arch": "window_moe",
            "program_arch": "mellum2-12b-a2.5b", "program_full": False,
            "hidden_size": 64, "moe_intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "num_hidden_layers": 4, "vocab_size": 256,
            "layer_types": KINDS, "mlp_layer_types": ["sparse"] * 4,
            "sliding_window": 16, "num_experts": 2,
            "num_experts_published": 8, "experts_held_first": 2,
            "num_experts_per_tok": 4, "norm_topk_prob": True,
            "hidden_act": "silu", "attention_bias": False,
            "rope_parameters": {
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                    "original_max_position_embeddings": 64, "beta_fast": 32,
                    "beta_slow": 1,
                    "attention_factor": 1.2772588722239782},
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 500000}},
            "rope_theta": 500000, "rms_norm_eps": 1e-6,
            "tie_word_embeddings": False, "torch_dtype": dtype,
            "engine": {"n_slots": 4, "page_size": 8, "n_pages": 96,
                       "max_context": 512, "decode_horizon": 4,
                       "prefill_mode": "batched", "prefix_cache": False}}


def make(limit=1e-3, dtype="float32", per_layer=()):
    bm = cell.benchmark()
    return harness.Cell(
        "tiny-moe.decode_long", config(dtype), tiny.traffic(),
        {"max_logit_gap": {"limit": limit}}, 1.0,
        end_to_end=list(bm["end_to_end"]),
        per_layer=[m for m in bm["per_layer"] if m["name"] in per_layer])
