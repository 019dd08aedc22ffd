"""Reduction of a profiler trace to device busy time, idle gaps and the
time of named operations.

A trace is reduced from plain tuples so that it can be checked on small
recorded or synthetic event lists: ``device`` maps each device to its
operation events ``(name, start_ns, end_ns)``; ``host`` lists the
benchmark's own host spans ``(name, start_ns, end_ns)`` (its
``jax.profiler.TraceAnnotation`` names, all starting ``bench.``), on the
same clock. The traced window is the host span ``bench.window``.

- busy: the union of a device's operation intervals inside the window,
  averaged over the devices;
- idle gaps: the stretches of the window where no operation runs on the
  device, each named by the innermost benchmark span that covers its
  middle;
- op time: the summed device time of the operations, by name.

On a TPU an operation's event name is its HLO text. Control-flow
operations (``while``, ``conditional``, ``call``) span the operations
they run and are left out; a name is shortened to the operation's id,
opcode and result type, with a custom call's target.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
_HLO = re.compile(r"^%?(?P<id>[^ =]+) = (?P<type>.*?)\s(?P<op>[a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def opcode(name: str) -> str | None:
    """The HLO opcode of an operation event's name, None where the name is
    not HLO text."""
    m = _HLO.match(name)
    return m.group("op") if m else None


def short(name: str) -> str:
    m = _HLO.match(name)
    if not m:
        return name
    t = m.group("type")
    out = f"{m.group('id')} {m.group('op')}"
    if not t.startswith("("):
        out += " " + t.split("{")[0]
    tgt = _TARGET.search(name)
    return out + (f" {tgt.group(1)}" if tgt else "")


def load(logdir: str) -> tuple[dict, list]:
    """(device events, host spans) from the newest ``.xplane.pb`` under
    ``logdir``: the ``XLA Ops`` line of every TPU plane, and the
    ``bench.`` spans of the host plane."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no profiler trace under {logdir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return device, host


def union(intervals) -> list[tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def window_of(host) -> tuple[float, float]:
    spans = [(s, e) for n, s, e in host if n == WINDOW]
    if not spans:
        raise RuntimeError(f"the trace holds no {WINDOW} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _clip(events, lo, hi):
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def _span_at(host, t) -> str:
    """The innermost benchmark span (latest start) covering time t."""
    best = None
    for name, s, e in host:
        if name != WINDOW and s <= t < e and (best is None or s > best[1]):
            best = (name, s)
    return best[0] if best else "bench.window (no span)"


def reduce(device: dict, host: list, kernels: dict[str, str],
           top: int = 10) -> dict:
    """Busy and window seconds, the top device ops, the longest idle gaps,
    and the summed seconds of each kernel: ``kernels`` maps a kernel's name
    to a regular expression its operation names match."""
    if not device:
        raise RuntimeError("the trace holds no TPU device plane")
    lo, hi = window_of(host)
    busy, gaps, ops = [], [], {}
    kern = {k: 0.0 for k in kernels}
    pats = {k: re.compile(p) for k, p in kernels.items()}
    for evs in device.values():
        clipped = [ev for ev in _clip(evs, lo, hi)
                   if opcode(ev[0]) not in CONTAINERS]
        u = union((s, e) for _, s, e in clipped)
        busy.append(sum(e - s for s, e in u))
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
        for name, s, e in clipped:
            key = short(name)
            ops[key] = ops.get(key, 0.0) + (e - s)
            for k, p in pats.items():
                if p.search(name):
                    kern[k] += e - s
    n = len(device)
    gaps.sort(reverse=True)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_span_at(host, (s + e) / 2), g / 1e9]
                      for g, s, e in gaps[:top]],
        "kernel_s": {k: v / n / 1e9 for k, v in kern.items()},
    }
