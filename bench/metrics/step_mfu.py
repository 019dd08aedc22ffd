"""Model step: model FLOPs of every token processed in the window (decode
tokens at their exact context; prompts prefilled in the window with causal
attention), as the cell's architecture module counts them, over the
window's seconds times the chip's peak, in %."""
import cell


def read(rec):
    a, m, flops = cell.arch(rec["arch"]), rec["dims"], 0
    for r in rec["requests"]:
        p = r["prompt_len"]
        if r["out_start"] == 0 and r["out_end"] > 0:
            flops += a.prefill_flops(m, p)
        for j in range(max(1, r["out_start"]), r["out_end"]):
            flops += a.decode_flops(m, p + j)
    peak = rec["peaks"]["flops_per_s"]
    return 100.0 * flops / (rec["window_s"] * peak) if flops else None
