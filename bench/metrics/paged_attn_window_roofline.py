"""Kernel: the window layers' paged decode attention's share of its
roofline, in %: the kernel call over the table slots a layer's window
spans (``paged_attention_window``). The least time the chip could take for
the algorithm's work (the cell's architecture module's
``kernel_work(...)["paged_attn_window"]``: K and V read once over the
``min(context, window)`` keys of each row on every window layer, Q read and
O written, QK and PV FLOPs), the larger of FLOPs over peak FLOP/s and
bytes over peak bandwidth, over the summed device time of the kernel's
events in the trace. Decode step j of a request with prompt p attends to
p + j keys."""
import cell

KERNEL = "paged_attn_window"


def read(rec):
    t = rec["trace"]
    if not t or not t["kernel_s"].get(KERNEL):
        return None
    a, m, flops, nbytes = cell.arch(rec["arch"]), rec["dims"], 0, 0
    for r in rec["requests"]:
        for j in range(max(1, r["out_start"]), r["out_end"]):
            f, b = a.kernel_work(m, r["prompt_len"] + j)[KERNEL]
            flops, nbytes = flops + f, nbytes + b
    if not flops:
        return None
    pk = rec["peaks"]
    bound = max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * bound / t["kernel_s"][KERNEL]
