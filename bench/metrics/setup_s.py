"""Set-up time: process start to the first timed request (weights, engine,
the sessions' contexts, warm-up and, in a cold run, compilation)."""


def read(rec):
    return rec["setup_s"]
