"""Reduce a profiler trace (``*.xplane.pb``) to the numbers the metrics read.

The harness wraps its window in a host annotation ``window`` and each of
its host steps in one of ``HOST_STEPS``'s annotations.  From the trace:

- ``window_s``: the length of the ``window`` annotation;
- ``busy_s``: the union of the intervals in which an operation ran on a
  device (its ``XLA Ops`` line) inside the window, averaged over devices;
- ``programs``: device seconds of each jitted program (``XLA Modules``
  events, matched by the jitted function's name) inside the window;
- ``device_ops``: device seconds of each operation (its HLO name, such
  as ``%while.628``), largest first; an operation's time includes the
  operations nested in it (a ``while`` holds its body's);
- ``idle_gaps``: device idle seconds inside the window, split by the host
  step whose annotation overlaps them (``other`` where none does).
"""
from __future__ import annotations

import pathlib
import re
from collections import defaultdict

HOST_STEPS = ("traffic", "dispatch:insert_many", "wait:insert_many",
              "dispatch:search_many", "wait:search_many", "record")
WINDOW = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_trace(log_dir: pathlib.Path) -> pathlib.Path:
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return files[-1]


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(op_event: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``%fusion.12``."""
    return op_event.split(" = ", 1)[0]


def program_name(module_event: str) -> str:
    """``jit__search_many(12)`` / ``jit__search_many`` -> ``_search_many``."""
    name = re.sub(r"\(\d+\)$", "", module_event)
    return name[4:] if name.startswith("jit_") else name


def reduce_planes(planes) -> dict:
    """The reduction over ``(name, [(line name, [(event, start_ns,
    duration_ns)])])`` planes, as ``ProfileData`` gives them."""
    windows, host = [], []
    devices = []
    for pname, lines in planes:
        if pname.startswith("/device:") and "TPU" in pname:
            devices.append(dict(lines))
            continue
        if not pname.startswith("/host:"):
            continue
        for _, events in lines:
            for name, start, dur in events:
                if name == WINDOW:
                    windows.append((start, start + dur))
                elif name in HOST_STEPS:
                    host.append((start, start + dur, name))
    if not windows:
        raise ValueError(f"no '{WINDOW}' annotation in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)

    busy_ns, programs, ops = 0.0, defaultdict(float), defaultdict(float)
    gaps = defaultdict(float)
    for dev in devices:
        op_events = dev.get(OPS_LINE, [])
        iv = _clip([(s, s + d) for _, s, d in op_events], lo, hi)
        busy = _union(iv)
        busy_ns += sum(e - s for s, e in busy)
        for name, s, d in op_events:
            ov = min(s + d, hi) - max(s, lo)
            if ov > 0:
                ops[op_name(name)] += ov
        for name, s, d in dev.get(MODULES_LINE, []):
            ov = min(s + d, hi) - max(s, lo)
            if ov > 0:
                programs[program_name(name)] += ov
        edges = [lo] + [x for b in busy for x in b] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            covered = 0.0
            for s, e, name in host:
                ov = min(e, g1) - max(s, g0)
                if ov > 0:
                    gaps[name] += ov
                    covered += ov
            if g1 - g0 > covered:
                gaps["other"] += g1 - g0 - covered
    n = len(devices)
    top = lambda d: [[k, v / n / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / n / 1e9,
            "devices": n,
            "programs": {k: v / n / 1e9 for k, v in programs.items()},
            "device_ops": top(ops)[:10], "idle_gaps": top(gaps)[:10]}


def read_planes(path: pathlib.Path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return [(p.name, [(l.name, [(e.name, e.start_ns, e.duration_ns)
                                for e in l.events]) for l in p.lines])
            for p in data.planes]


def reduce_trace(path: pathlib.Path) -> dict:
    return reduce_planes(read_planes(path))
