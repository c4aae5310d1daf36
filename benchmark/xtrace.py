"""From a profiler trace to numbers.  `read` turns jax's .xplane.pb into
plain lists; everything after works on those lists, so the tests run the
reduction on a small recorded trace (tests/trace_small.json) without jax.

A trace here is {"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}.  On a TPU the device planes are
named "/device:TPU:<n>"; their line "XLA Ops" holds one event per
executed HLO op and "XLA Modules" one per launched program, named
"<jit name>(<fingerprint>)".  Host planes hold the threads, and on them
the harness's own "bench:*" annotations.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "bench:"


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
                      if device or e.name.startswith(MARK)]
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


def idle_gaps(trace: dict, n: int = 10) -> list:
    """[[what the host was doing, seconds]]: the time the first device sat
    idle between ops, shared out over the harness annotations that cover
    it ("unmarked" where none does), longest first."""
    lines = device_lines(trace, OPS_LINE)
    if not lines or not lines[0]:
        return []
    marks = sorted((e for plane in trace["planes"]
                    if not DEVICE_PLANE.match(plane["name"])
                    for line in plane["lines"] for e in line["events"]
                    if e[0].startswith(MARK)), key=lambda e: e[1])
    starts = [e[1] for e in marks]
    busy = union(lines[0])
    total = {}
    for (_, g0), (g1, _) in zip(busy, busy[1:]):
        left = g1 - g0
        # one client: its marks do not overlap, so only the mark that
        # holds g0 and those that start inside the gap can cover it
        for name, start, dur in marks[max(bisect.bisect_right(starts, g0)
                                          - 1, 0):]:
            if start >= g1:
                break
            cover = max(min(g1, start + dur) - max(g0, start), 0)
            total[name] = total.get(name, 0) + cover
            left -= cover
        total["unmarked"] = total.get("unmarked", 0) + left
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, dur / 1e9] for name, dur in top]
