"""Kernel: paged decode attention's share of its roofline, in %. The
least time the chip could take for the algorithm's work (the cell's
architecture module's ``kernel_work(...)["paged_attn"]``: K and V read once
over each row's exact live context, Q read and O written, QK and PV
FLOPs), the larger of FLOPs over peak FLOP/s and bytes over peak
bandwidth, over the summed device time of the kernel's events in the
trace. Decode step j of a request with prompt p attends to p + j keys."""
import cell


def read(rec):
    t = rec["trace"]
    if not t or not t["kernel_s"].get("paged_attn"):
        return None
    a, m, flops, nbytes = cell.arch(rec["arch"]), rec["dims"], 0, 0
    for r in rec["requests"]:
        for j in range(max(1, r["out_start"]), r["out_end"]):
            f, b = a.kernel_work(m, r["prompt_len"] + j)["paged_attn"]
            flops, nbytes = flops + f, nbytes + b
    if not flops:
        return None
    pk = rec["peaks"]
    bound = max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * bound / t["kernel_s"]["paged_attn"]
