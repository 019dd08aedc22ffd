"""Reading a ``jax.profiler`` trace of the serving loop.

The engine's phases are profiler spans named ``engine.<phase>``
(``DecodeEngine._phase``) and its jitted programs carry their own names,
so one trace holds the host's phases and the device ops they wait for or
dispatch, on one clock. ``read_profile`` pulls both out of the newest
``.xplane.pb`` under a profiler log directory.
"""
from __future__ import annotations

import glob
import os

SPAN_PREFIX = "engine."


def read_profile(logdir: str) -> tuple[list, list]:
    """``(spans, ops)`` of the newest trace under ``logdir``: the engine's
    phase spans ``(name, start_ns, end_ns)``, and every op event that
    names its program, ``(hlo_op, hlo_module, start_ns, end_ns)`` (the
    device's op lines on a TPU, the XLA client's lines on the CPU)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {logdir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    spans, ops = [], []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, e.start_ns, e.end_ns))
                    continue
                st = dict(e.stats)
                if "hlo_module" in st:
                    ops.append((st.get("hlo_op", e.name), st["hlo_module"],
                                e.start_ns, e.end_ns))
    return spans, ops
