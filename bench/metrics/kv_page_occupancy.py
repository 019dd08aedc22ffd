"""DPA allocator: pages in use over pool pages, sampled after every tick
of the window, mean."""


def read(rec):
    s = rec["pages_in_use"]
    return sum(s) / len(s) / rec["n_pages"] if s else None
