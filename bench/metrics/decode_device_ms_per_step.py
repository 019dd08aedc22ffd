"""Model step: device time of the decode program's ops in the window
(``jit_decode_horizon``, by the ``hlo_module`` of each op in the trace)
over the decode steps dispatched in it (``EngineTiming.decode_steps``),
in ms."""


def read(rec):
    t = rec.get("trace") or {}
    s = t.get("module_s", {}).get("jit_decode_horizon")
    n = rec.get("decode_steps")
    return 1e3 * s / n if s and n else None
