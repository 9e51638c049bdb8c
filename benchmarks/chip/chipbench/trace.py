"""Reduce a profiler trace (`.xplane.pb`) to device busy time, per-op time
and idle gaps.

Device planes are named `/device:TPU:<n>`; on each, the line `XLA Ops`
holds one event per operation that ran, and `XLA Modules` one per program.
Times are nanoseconds on the profile's own clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Plane:
    name: str
    ops: list = field(default_factory=list)      # (op, start_ns, dur_ns)
    modules: list = field(default_factory=list)  # (name, start_ns, dur_ns)
    hlo: dict = field(default_factory=dict)      # op -> its HLO text


def op_name(text: str) -> str:
    """`%fusion.12 = f32[8]{0} fusion(...)` -> `fusion.12`.  A TPU trace
    names each op event by its whole HLO instruction; names without one
    (other backends, host spans) stay as they are."""
    head, eq, _ = text.partition(" = ")
    return head.lstrip("%") if eq else text


def opcode(text: str) -> str:
    """The HLO opcode of an instruction's text (`fusion`, `custom-call`,
    `while`, ...), or "" when the text is not an instruction."""
    _, eq, rest = text.partition(" = ")
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest) if eq else None
    return m.group(1) if m else ""


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {paths}")
    return paths[0]


def read_planes(path: str, prefix: str = DEVICE_PREFIX,
                ops_line: str = OPS_LINE,
                modules_line: str = MODULES_LINE) -> list[Plane]:
    """Events of the planes whose name starts with `prefix`.  Op events keep
    their short name; each distinct op's full text is kept once."""
    from jax.profiler import ProfileData

    out = []
    for pl in ProfileData.from_file(path).planes:
        if not pl.name.startswith(prefix):
            continue
        plane = Plane(pl.name)
        for line in pl.lines:
            if line.name == ops_line:
                for e in line.events:
                    text = e.name
                    op = op_name(text)
                    if op not in plane.hlo:
                        plane.hlo[op] = text
                    plane.ops.append((op, float(e.start_ns),
                                      float(e.duration_ns)))
            elif line.name == modules_line:
                plane.modules.extend((e.name, float(e.start_ns),
                                      float(e.duration_ns))
                                     for e in line.events)
        out.append(plane)
    return out


def intervals(events, t0: float | None = None, t1: float | None = None):
    """Union of the events' [start, end) intervals, clipped to [t0, t1]."""
    spans = sorted((s, s + d) for _, s, d in events)
    merged = []
    for s, e in spans:
        if t0 is not None:
            s = max(s, t0)
        if t1 is not None:
            e = min(e, t1)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(events, t0=None, t1=None) -> float:
    return sum(e - s for s, e in intervals(events, t0, t1))


def idle_stretches(events, t0: float, t1: float) -> list[tuple[float, float]]:
    """(start, length) of each stretch of [t0, t1] with no event running,
    longest first."""
    gaps, cur = [], t0
    for s, e in intervals(events, t0, t1):
        if s > cur:
            gaps.append((cur, s - cur))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1 - cur))
    return sorted(gaps, key=lambda g: -g[1])


def idle_gaps(events, t0: float, t1: float) -> list[float]:
    """Lengths of the idle stretches of [t0, t1], longest first."""
    return [n for _, n in idle_stretches(events, t0, t1)]


def time_by_name(events, key=lambda name: name) -> dict[str, float]:
    """Summed duration (ns) per key(event name)."""
    out: dict[str, float] = {}
    for name, _, d in events:
        k = key(name)
        out[k] = out.get(k, 0.0) + d
    return out


def span(planes: list[Plane]) -> tuple[float, float]:
    """First start and last end over every event of the planes."""
    evs = [e for p in planes for e in p.ops + p.modules]
    return (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))


def window(path: str, planes: list[Plane], open_ns: int | None = None,
           close_ns: int | None = None) -> tuple[float, float]:
    """The measured window on the events' clock.  Events are timed from the
    profile's start, which the `Task Environment` plane records in
    nanoseconds since the epoch; given the window's edges on that clock
    (`time.time_ns()`), the window is exactly them, else the whole profile.
    Without that plane, the span of the events."""
    from jax.profiler import ProfileData

    for pl in ProfileData.from_file(path).planes:
        if pl.name == "Task Environment":
            st = dict(pl.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                t_start = float(st["profile_start_time"])
                t1 = float(st["profile_stop_time"]) - t_start
                if open_ns is not None and close_ns is not None:
                    return max(open_ns - t_start, 0.0), min(close_ns - t_start, t1)
                return 0.0, t1
    return span(planes)
