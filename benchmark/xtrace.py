"""From a profiler trace to numbers.  `read` turns jax's .xplane.pb into
plain lists; everything after works on those lists, so the tests run the
reduction on a small recorded trace (tests/trace_small.json) without jax.

A trace here is {"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}.  On a TPU the device planes are
named "/device:TPU:<n>"; their line "XLA Ops" holds one event per
executed HLO op and "XLA Modules" one per launched program, named
"<jit name>(<fingerprint>)".  Host planes hold the threads, and on them
the marks: the harness's own "bench:*" annotations on the client's
thread, and the program's phase seam's "vm:*" (one a served phase, nested
as the phases nest) on the threads that serve.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARKS = ("bench:", "vm:")


def read(logdir: str) -> dict:
    """The newest .xplane.pb under logdir, as plain lists."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(MARKS)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_lines(trace: dict, line_name: str) -> list:
    """[events] per device plane, for the line of that name."""
    out = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            events = [e for line in plane["lines"]
                      if line["name"] == line_name for e in line["events"]]
            out.append(events)
    return out


def union(events: list) -> list:
    """Merged [start, end) intervals of the events, sorted."""
    out = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], start + dur)
        else:
            out.append([start, start + dur])
    return out


def busy_s(trace: dict):
    """Seconds in which an op ran on the device, averaged over the device
    planes; None where the trace has no device op."""
    per_device = [sum(e - s for s, e in union(ev))
                  for ev in device_lines(trace, OPS_LINE) if ev]
    if not per_device:
        return None
    return sum(per_device) / len(per_device) / 1e9


def programs(trace: dict, pattern: str) -> list:
    """Launches whose program name (fingerprint stripped) matches."""
    rx = re.compile(pattern)
    return [e for ev in device_lines(trace, MODULES_LINE) for e in ev
            if rx.search(e[0].split("(")[0])]


def op_name(text: str) -> str:
    """"%fusion.2 = s32[...] fusion(...)" -> "fusion.2": the HLO op's own
    name, without its operands and layout."""
    return text.split(" = ")[0].lstrip("%")[:80]


def top_ops(trace: dict, n: int = 10) -> list:
    """[[name, seconds]]: the device ops that took most time."""
    total = {}
    for ev in device_lines(trace, OPS_LINE):
        for text, _, dur in ev:
            name = op_name(text)
            total[name] = total.get(name, 0) + dur
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, dur / 1e9] for name, dur in top]


def innermost(marks: list) -> list:
    """[(start, end, name)], sorted and disjoint: each stretch of time
    that some mark covers, under the INNERMOST mark covering it - of
    those open, the one that started last (the shorter of two that
    started together).  On one thread that is the mark's self time; over
    threads, the server's phase inside the client's call."""
    marks = sorted(marks, key=lambda e: (e[1], -e[2]))
    edges = sorted({e[1] for e in marks} | {e[1] + e[2] for e in marks})
    out, open_, i = [], [], 0
    for t0, t1 in zip(edges, edges[1:]):
        while i < len(marks) and marks[i][1] <= t0:
            name, start, dur = marks[i]
            # a heap by the latest start, then the nearest end
            heapq.heappush(open_, (-start, start + dur, i, name))
            i += 1
        while open_ and open_[0][1] <= t0:
            heapq.heappop(open_)
        if open_:
            name = open_[0][3]
            if out and out[-1][2] == name and out[-1][1] == t0:
                out[-1][1] = t1
            else:
                out.append([t0, t1, name])
    return out


def idle_gaps(trace: dict, n: int = 10) -> list:
    """[[what the host was doing, seconds]]: the time the first device sat
    idle between ops, each stretch of it charged to the innermost mark
    that covers it ("unmarked" where none does), longest first.  What is
    left under "bench:query_range" is the client's own: the request on
    its way and the body read."""
    lines = device_lines(trace, OPS_LINE)
    if not lines or not lines[0]:
        return []
    spans = innermost([e for plane in trace["planes"]
                       if not DEVICE_PLANE.match(plane["name"])
                       for line in plane["lines"] for e in line["events"]
                       if e[0].startswith(MARKS)])
    ends = [t1 for _, t1, _ in spans]
    busy = union(lines[0])
    total = {}
    for (_, g0), (g1, _) in zip(busy, busy[1:]):
        left = g1 - g0
        for t0, t1, name in spans[bisect.bisect_right(ends, g0):]:
            if t0 >= g1:
                break
            cover = min(g1, t1) - max(g0, t0)
            total[name] = total.get(name, 0) + cover
            left -= cover
        total["unmarked"] = total.get("unmarked", 0) + left
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, dur / 1e9] for name, dur in top]
