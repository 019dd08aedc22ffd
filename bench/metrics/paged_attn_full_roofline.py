"""Kernel: the full layers' paged decode attention's share of its roofline,
in %, on a block whose window layers' decode is a kernel call of its own
(``paged_attn_window``): ``paged_attn_roofline``'s reading, the full
layers' call ``paged_attn`` against the architecture module's
``kernel_work(...)["paged_attn"]``, for the cells that reading does not
list."""
import cell

read = cell.metric_reader("paged_attn_roofline")
