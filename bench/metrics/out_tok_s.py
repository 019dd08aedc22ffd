"""Output tokens delivered to requests in the window over the window's
seconds; the window ends once the last dispatched work is collected."""


def read(rec):
    return rec["tokens"] / rec["window_s"]
