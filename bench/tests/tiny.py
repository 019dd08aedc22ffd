"""A tiny cell for CPU tests: the program's reduced twin of internlm2
(d 64, 4 heads over 2 KV heads, 2 layers, vocab 256) under the
decode_long mix, scaled to a small pool."""
import copy

import cell
import harness


def config(dtype="float32"):
    return {"name": "tiny", "bench_arch": "dense_swiglu",
            "program_arch": "internlm2-1.8b",
            "program_full": False, "hidden_size": 64,
            "intermediate_size": 128, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_hidden_layers": 2,
            "vocab_size": 256, "rope_theta": 1000000, "rms_norm_eps": 1e-5,
            "tie_word_embeddings": False, "torch_dtype": dtype,
            "engine": {"n_slots": 4, "page_size": 8, "n_pages": 96,
                       "max_context": 512, "decode_horizon": 4,
                       "prefill_mode": "batched", "prefix_cache": False}}


def traffic():
    t = copy.deepcopy(cell.traffic_file("decode_long"))
    t["context_tokens"] = {"mean": 150, "std": 60, "min": 20, "max": 300}
    # a bandwidth that gives the tiny model a budget of tens of tokens
    t["budget"] = {"bandwidth_bytes_per_s": 2e7, "margin_s": 1}
    return t


def make(limit=1e-3, dtype="float32", per_layer=()):
    bm = cell.benchmark()
    return harness.Cell(
        "tiny.decode_long", config(dtype), traffic(),
        {"max_logit_gap": {"limit": limit}}, 1.0,
        end_to_end=list(bm["end_to_end"]),
        per_layer=[m for m in bm["per_layer"] if m["name"] in per_layer])


def run(c, seed=2 ** 31 + 99, seconds=1.0):
    import time
    return harness.run(c, seed, seconds, False, time.perf_counter(),
                       require_tpu=False)
