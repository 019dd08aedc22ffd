"""Scheduler: decode tokens emitted over the decode slots the horizons
offered (horizons x horizon length x slots), from EngineTiming counters."""


def read(rec):
    slots = rec["horizons"] * rec["horizon"] * rec["n_slots"]
    return rec["decode_tokens"] / slots if slots else None
