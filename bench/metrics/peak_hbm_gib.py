"""Device: peak_bytes_in_use after the window, in GiB."""


def read(rec):
    b = rec["memory_peak_bytes"]
    return b / 2 ** 30 if b else None
