"""The program's own names in a profile (`.xplane.pb`): host spans and the
scopes of device ops.

Host spans are the `jax.profiler.TraceAnnotation`s the serve engine opens
(`serve.*`, `python.gc`), on the host plane's thread lines, with their
integer metadata as stats.  A device op's scope path is the `op_name` of
its HLO instruction (`jit(traced_decode)/layer_scan/while/body/closed_call/
kv_flush/...`), which the profiler keeps as a stat of the op's event
metadata; `jax.profiler.ProfileData` does not expose those stats, so
`device_ops` reads them from the protobuf's wire format.

An op's scope is the innermost of `SCOPES` on its path.  The profiler
gives control flow (`conditional`, `while`, `call`) no path, and the ops
the compiler adds (layout copies) have none: a container takes the scopes
that what ran inside it agrees on, and an op without a path the scope of
the innermost container that ran around it.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field

from chipbench import trace as trace_lib
from chipbench.kernels import CONTAINERS

HOST_PLANE = "/host:CPU"
HOST_PREFIXES = ("serve.", "python.")
# the named scopes of the serve engine's programs
SCOPES = ("layer_scan", "qkv", "kv_flush", "attend", "mlp", "unembed",
          "sample", "kv_bulk_compress")
NO_SCOPE = "(no scope)"
NO_SPAN = "host (no span)"
WINDOW_EDGE = "window edge (no span recorded)"
# the event-metadata stat that holds an op's scope path
PATH_STAT = "tf_op"
DECODE_PROGRAM = "traced_decode"


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

def host_spans(path: str, plane: str = HOST_PLANE) -> list[tuple]:
    """(name, start_ns, dur_ns, stats, line) of every host event whose name
    starts with one of HOST_PREFIXES, on every thread line of the host
    plane; `line` numbers the thread lines.  Sorted by start."""
    from jax.profiler import ProfileData

    out = []
    for pl in ProfileData.from_file(path).planes:
        if pl.name != plane:
            continue
        for i, line in enumerate(pl.lines):
            for e in line.events:
                if e.name.startswith(HOST_PREFIXES):
                    out.append((e.name, float(e.start_ns),
                                float(e.duration_ns), dict(e.stats), i))
    return sorted(out, key=lambda s: s[1])


def _covering(spans, t: float) -> list[tuple]:
    return [s for s in spans if s[1] <= t < s[1] + s[2]]


def gap_labels(gaps, spans, t0: float | None = None,
               t1: float | None = None) -> list[str]:
    """A name for each device idle gap (start_ns, length_ns): the innermost
    span covering its midpoint on the serve thread (the line that opens
    `serve.dispatch`), or a `python.gc` span on any line (a collection
    stops every thread); the innermost span of another line where the
    serve thread has none; else WINDOW_EDGE for a gap cut by the traced
    window's edge [t0, t1] (a span still open when the profiler started or
    stopped is not recorded), else NO_SPAN."""
    lines: dict[int, int] = {}
    for name, _, _, _, line in spans:
        if name == "serve.dispatch":
            lines[line] = lines.get(line, 0) + 1
    serve_line = max(lines, key=lines.get) if lines else None
    out = []
    for start, length in gaps:
        cover = _covering(spans, start + length / 2)
        pick = [s for s in cover if s[0] == "python.gc"] \
            or [s for s in cover if s[4] == serve_line] or cover
        if pick:
            out.append(min(pick, key=lambda s: s[2])[0])
        elif start == t0 or start + length == t1:
            out.append(WINDOW_EDGE)
        else:
            out.append(NO_SPAN)
    return out


# ---------------------------------------------------------------------------
# Op scope paths (protobuf wire format)
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: int | None = None):
    """(field number, wire type, value) of a message's fields; a length-
    delimited value is its (start, stop) span in `buf`."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield num, wt, v


def _str(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _event_metadata(buf, span):
    """XEventMetadata -> (id, name, [(stat id, str value or ("ref", id))])."""
    mid, name, stats = None, "", []
    for num, _, v in _fields(buf, *span):
        if num == 1:
            mid = v
        elif num == 2:
            name = _str(buf, v)
        elif num == 5:
            sid = val = None
            for n2, _, v2 in _fields(buf, *v):
                if n2 == 1:
                    sid = v2
                elif n2 == 5:
                    val = _str(buf, v2)
                elif n2 == 7:
                    val = ("ref", v2)
            if sid is not None and val is not None:
                stats.append((sid, val))
    return mid, name, stats


@dataclass
class DeviceOps:
    """One device plane's op events, each with its own HLO text and path
    (the same short op name can stand for different instructions in
    different programs, so events keep their metadata id)."""
    name: str
    ops: list = field(default_factory=list)    # (metadata id, start, dur)
    text: dict = field(default_factory=dict)   # metadata id -> HLO text
    path: dict = field(default_factory=dict)   # metadata id -> op_name


def device_ops(path: str, prefix: str = trace_lib.DEVICE_PREFIX,
               ops_line: str = trace_lib.OPS_LINE) -> list[DeviceOps]:
    """The op events of the planes whose name starts with `prefix`, with
    each event metadata's `PATH_STAT`, read from the protobuf's wire
    format.  Times are ns on the events' clock, as `trace.read_planes`
    gives them."""
    with open(path, "rb") as f:
        buf = f.read()
    out = []
    for num, _, plane in _fields(buf):
        if num != 1:
            continue
        dev, metas, names = None, [], {}
        for n, _, v in _fields(buf, *plane):
            if n == 2:
                if not _str(buf, v).startswith(prefix):
                    break
                dev = DeviceOps(_str(buf, v))
            elif n == 3:
                line, t_ns, events = "", 0, []
                for n2, _, v2 in _fields(buf, *v):
                    if n2 == 2:
                        line = _str(buf, v2)
                    elif n2 == 3:
                        t_ns = v2
                    elif n2 == 4 and line == ops_line:
                        events.append(v2)
                for ev in events if line == ops_line else ():
                    mid = off = dur = 0
                    for n3, _, v3 in _fields(buf, *ev):
                        if n3 == 1:
                            mid = v3
                        elif n3 == 2:
                            off = v3
                        elif n3 == 3:
                            dur = v3
                    dev.ops.append((mid, float(t_ns + off // 1000),
                                    float(dur // 1000)))
            elif n == 4:
                for n2, _, v2 in _fields(buf, *v):
                    if n2 == 2:
                        metas.append(_event_metadata(buf, v2))
            elif n == 5:
                for n2, _, v2 in _fields(buf, *v):
                    if n2 == 2:
                        fs = {n3: v3 for n3, _, v3 in _fields(buf, *v2)}
                        names[fs.get(1)] = _str(buf, fs[2]) if 2 in fs else ""
        if dev is None:
            continue
        for mid, text, stats in metas:
            dev.text[mid] = text
            for sid, val in stats:
                if names.get(sid) == PATH_STAT:
                    dev.path[mid] = names.get(val[1], "") \
                        if isinstance(val, tuple) else val
        out.append(dev)
    return out


def scope_chain(path: str | None) -> tuple:
    """The SCOPES on an op_name path, outermost first."""
    return tuple(p for p in (path or "").split("/") if p in SCOPES)


# ---------------------------------------------------------------------------
# Device time by scope
# ---------------------------------------------------------------------------

def _nesting(dev: DeviceOps) -> list[int | None]:
    """For each of `dev.ops` (same order), the index of the innermost
    container op (`conditional`, `while`, `call`) running around it, or
    None: one sweep in start order, a container before what it holds."""
    box = {m: trace_lib.opcode(t) in CONTAINERS for m, t in dev.text.items()}
    order = sorted(range(len(dev.ops)),
                   key=lambda i: (dev.ops[i][1], -dev.ops[i][2]))
    parent: list[int | None] = [None] * len(dev.ops)
    stack: list[int] = []
    end = lambda i: dev.ops[i][1] + dev.ops[i][2]
    for i in order:
        mid, s, d = dev.ops[i]
        while stack and end(stack[-1]) <= s:
            stack.pop()
        if stack and end(stack[-1]) >= s + d:
            parent[i] = stack[-1]
        if box.get(mid):
            stack.append(i)
    return parent


def op_scopes(dev: DeviceOps) -> list[str]:
    """The scope of each of `dev.ops` (same order), else NO_SCOPE.

    An op's scope is the innermost of SCOPES on its own path.  The
    profiler gives container ops no path: a container takes the deepest
    chain of scopes that the chains of all the ops run inside it lie on
    (each a prefix of it), else their common prefix.  An op without a
    path (a copy the compiler added) takes the scope of the innermost
    container that ran around it."""
    parent = _nesting(dev)
    seen: dict[int, list] = {}   # container -> [longest, on one line, lcp]
    for i, (mid, _, _) in enumerate(dev.ops):
        c = scope_chain(dev.path.get(mid))
        j = parent[i]
        while c and j is not None:
            box = dev.ops[j][0]
            if box not in seen:
                seen[box] = [c, True, c]
            else:
                agg = seen[box]
                if agg[1] and agg[0][:len(c)] != c:
                    if c[:len(agg[0])] == agg[0]:
                        agg[0] = c
                    else:
                        agg[1] = False
                n = 0
                while n < min(len(c), len(agg[2])) and c[n] == agg[2][n]:
                    n += 1
                agg[2] = agg[2][:n]
            j = parent[j]
    scope = {m: scope_chain(dev.path.get(m)) for m in dev.text}
    for m, (longest, line, lcp) in seen.items():
        scope[m] = scope[m] or (longest if line else lcp)
    out = []
    for i, (mid, _, _) in enumerate(dev.ops):
        sc, j = scope.get(mid), parent[i]
        while not sc and j is not None:
            sc, j = scope.get(dev.ops[j][0]), parent[j]
        out.append(sc[-1] if sc else NO_SCOPE)
    return out


def device_scopes(devs: list[DeviceOps], t0: float, t1: float
                  ) -> dict[str, float]:
    """Device seconds in [t0, t1) per scope, outer control flow left out,
    averaged over the planes; sorted, largest first."""
    total: dict[str, float] = {}
    for dev in devs:
        by: dict[str, list] = {}
        for ev, sc in zip(dev.ops, op_scopes(dev)):
            if trace_lib.opcode(dev.text.get(ev[0], "")) not in CONTAINERS:
                by.setdefault(sc, []).append(ev)
        for sc, evs in by.items():
            total[sc] = total.get(sc, 0.0) \
                + trace_lib.busy_ns(evs, t0, t1) * 1e-9 / len(devs)
    return dict(sorted(total.items(), key=lambda kv: -kv[1]))


# ---------------------------------------------------------------------------
# Host and device clocks
# ---------------------------------------------------------------------------

def readback_lags(modules, spans) -> list[tuple[float, bool]]:
    """(ns from the end of a `traced_decode` program on the device to the
    end of the `serve.readback` span that waits for it, whether the
    readback began before the program ended) for each such program.  A
    program's step is that of the last `serve.dispatch` to start before
    the program did; a readback inside a `serve.admit` waits on an
    admission, not a step."""
    dispatch = sorted((s, m.get("step")) for n, s, _, m, _ in spans
                      if n == "serve.dispatch")
    admits = [(s, s + d) for n, s, d, _, _ in spans if n == "serve.admit"]
    reads = {}
    for n, s, d, m, _ in spans:
        if n == "serve.readback" and not any(a <= s < b for a, b in admits):
            reads[m.get("step")] = (s, s + d)
    starts = [s for s, _ in dispatch]
    out = []
    for name, s, d in sorted(modules, key=lambda m: m[1]):
        if DECODE_PROGRAM not in name:
            continue
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and dispatch[k][1] in reads:
            r0, r1 = reads[dispatch[k][1]]
            out.append((r1 - (s + d), r0 < s + d))
    return out


# ---------------------------------------------------------------------------
# The reading of one traced window
# ---------------------------------------------------------------------------

def report(path: str, tr: dict) -> dict:
    """What the program's names add to the reading of a traced window `tr`
    (run.py's trace context: planes, t0, t1, window_s) of the profile at
    `path`: device seconds by scope, the scope `kv_flush`'s share of the
    window, the ten longest idle gaps of the first chip by the span that
    covers them, the host spans' counts and seconds, and the lag from each
    decode program's end to the end of its readback (all, and those whose
    readback began before the program ended)."""
    t0, t1 = tr["t0"], tr["t1"]
    spans = host_spans(path)
    by_scope = device_scopes(device_ops(path), t0, t1)
    busy = sum(by_scope.values())
    gaps = trace_lib.idle_stretches(tr["planes"][0].ops, t0, t1)[:10]
    labels = gap_labels(gaps, spans, t0, t1)
    totals: dict[str, list] = {}
    for name, s, d, _, _ in spans:
        if t0 <= s < t1:
            c = totals.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += d * 1e-9
    mods = [m for m in tr["planes"][0].modules if t0 <= m[1] < t1]
    lags = readback_lags(mods, spans)
    ms = lambda xs: sorted(x * 1e-6 for x in xs)
    summary = lambda xs: {"n": len(xs), "min": xs[0] if xs else None,
                          "median": xs[len(xs) // 2] if xs else None,
                          "max": xs[-1] if xs else None}
    return {
        "device_scopes": [[k, v] for k, v in by_scope.items()],
        "no_scope_share": 100.0 * by_scope.get(NO_SCOPE, 0.0) / busy
        if busy else None,
        "flush_share": 100.0 * by_scope.get("kv_flush", 0.0)
        / tr["window_s"],
        "idle_gaps": [[lab, n * 1e-9] for lab, (_, n) in zip(labels, gaps)],
        "host_spans": totals,
        "readback_lag_ms": summary(ms(x for x, _ in lags)),
        "readback_lag_ms_waiting": summary(ms(x for x, w in lags if w)),
    }
