"""Engine tick: host bookkeeping time per tick (EngineTiming.host_s over
ticks) in the window."""


def read(rec):
    return 1e3 * rec["host_s"] / rec["ticks"] if rec["ticks"] else None
