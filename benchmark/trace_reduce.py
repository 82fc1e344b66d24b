"""From a profiler trace to busy time, idle gaps and per-operation time.

The arithmetic works on a plain structure, so that it can be checked on a
small recorded trace (`tests/data/trace_small.json`):

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

`load_xplane` makes that structure from the `.xplane.pb` the JAX profiler
writes, with nothing but `jax.profiler.ProfileData`.

What is what in a trace of this chip (looked at by hand, PR 24): a device is
a plane `/device:TPU:<n>`; its line `XLA Ops` holds one event per executed
HLO operation (fusions, custom calls, copies), `XLA Modules` one per
executable run, `Steps` one per step.  The host is the plane `/host:CPU`;
`jax.profiler.TraceAnnotation` spans of the runner are events named
`bench/<span>` on the line of the thread that opened them.  All planes share
one clock.
"""
from __future__ import annotations

import glob
import math
import os
import re
import typing

Event = typing.Tuple[str, float, float]  # name, start_ns, duration_ns

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"


def load_xplane(trace_dir: str) -> dict:
    """The newest `.xplane.pb` under `trace_dir` as the plain structure.
    Device planes keep their operations; host planes only `bench/` spans."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_ops(trace: dict) -> typing.Dict[str, typing.List[Event]]:
    """Per device plane, its operation events sorted by start."""
    out = {}
    for plane in trace["planes"]:
        if not plane["name"].startswith(DEVICE_PLANE):
            continue
        events = [tuple(e) for line in plane["lines"]
                  if line["name"] == OPS_LINE for e in line["events"]]
        out[plane["name"]] = sorted(events, key=lambda e: e[1])
    return out


def host_spans(trace: dict) -> typing.List[Event]:
    """The runner's `bench/` spans, prefix dropped, sorted by start."""
    events = [(e[0][len(SPAN_PREFIX):], e[1], e[2])
              for plane in trace["planes"] if plane["name"] == HOST_PLANE
              for line in plane["lines"] for e in line["events"]
              if e[0].startswith(SPAN_PREFIX)]
    return sorted(events, key=lambda e: e[1])


def window_of(spans: typing.Sequence[Event], name: str = "window"
              ) -> typing.Tuple[float, float]:
    """Start and end (ns) of the runner's window span."""
    found = [s for s in spans if s[0] == name]
    if not found:
        raise ValueError(f"the trace holds no {SPAN_PREFIX}{name} span")
    start = min(s[1] for s in found)
    return start, max(s[1] + s[2] for s in found)


def clip(events: typing.Sequence[Event], start: float, end: float
         ) -> typing.List[Event]:
    """Events cut to [start, end]; those wholly outside go."""
    out = []
    for name, s, d in events:
        lo, hi = max(s, start), min(s + d, end)
        if hi > lo:
            out.append((name, lo, hi - lo))
    return out


def union(events: typing.Sequence[Event]
          ) -> typing.List[typing.Tuple[float, float]]:
    """Merged [start, end] intervals of events sorted by start."""
    merged: typing.List[typing.List[float]] = []
    for _, s, d in events:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s + d)
        else:
            merged.append([s, s + d])
    return [(a, b) for a, b in merged]


def busy_ns(events: typing.Sequence[Event]) -> float:
    return sum(b - a for a, b in union(events))


_LAYOUT = re.compile(r"\{[^}]*\}")
_HLO = re.compile(r"^%?([\w\-]+?)(?:\.\d+)? = (\(?[^()]*\)?)")
_RESULT = re.compile(r"\w+\[([\d,]*)\]")


def instruction(name: str) -> str:
    """An event's own instruction, numbering dropped: `_fwd_pallas` of
    `%_fwd_pallas.64 = bf16[...] custom-call(... %copy.5885, ...)`.  The
    operands, which name other instructions, are no part of it."""
    found = _HLO.match(name)
    return found.group(1) if found else name


def op_kind(name: str) -> str:
    """An event's name is the whole HLO instruction, one per depth and block.
    Fold it to `<instruction> -> <largest result>` with the numbering and
    the layouts dropped, so that the 32 copies of one operation add up."""
    found = _HLO.match(_LAYOUT.sub("", name))
    results = list(_RESULT.finditer(found.group(2))) if found else []
    if not results:
        return name[:80]

    def elements(m):
        return math.prod(int(d) for d in m.group(1).split(",") if d)

    return f"{found.group(1)} -> {max(results, key=elements).group(0)}"


def top_ops(events: typing.Sequence[Event], n: int = 10
            ) -> typing.List[typing.Tuple[str, float]]:
    """The `n` kinds of operation with most summed time, in seconds."""
    total: typing.Dict[str, float] = {}
    for name, _, d in events:
        name = op_kind(name)
        total[name] = total.get(name, 0.0) + d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / 1e9) for name, ns in ranked]


def idle_gaps(events: typing.Sequence[Event], spans: typing.Sequence[Event],
              start: float, end: float, n: int = 10
              ) -> typing.List[typing.Tuple[str, float]]:
    """Idle time inside [start, end] by what the host was doing: every gap
    of the device's busy union is given to the `bench/` span that covers
    most of it (`(no span)` where none does), and the seconds are summed by
    that name.  The `window` span itself is not a candidate."""
    busy = union(events)
    edges = [start] + [t for ab in busy for t in ab] + [end]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    inner = [s for s in spans if s[0] != "window"]
    total: typing.Dict[str, float] = {}
    for a, b in gaps:
        cover: typing.Dict[str, float] = {}
        for name, s, d in inner:
            overlap = min(b, s + d) - max(a, s)
            if overlap > 0:
                cover[name] = cover.get(name, 0.0) + overlap
        owner = max(cover, key=cover.get) if cover else "(no span)"
        total[owner] = total.get(owner, 0.0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / 1e9) for name, ns in ranked]


def matching(events: typing.Sequence[Event], names: typing.Sequence[str]
             ) -> typing.List[Event]:
    """Events whose own instruction is one of `names`.  An event that only
    takes such an instruction's result as an operand is not one."""
    return [e for e in events if instruction(e[0]) in names]
