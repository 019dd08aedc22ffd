"""Kernels: share of the paged kernel's (slot, table slot) grid that
holds live KV over the window: ``EngineTiming.paged_live_cells`` (a
running slot's live pages at each of its steps) over
``paged_grid_cells`` (slots x table width per decode step)."""


def read(rec):
    g = rec.get("paged_grid_cells")
    return rec["paged_live_cells"] / g if g else None
