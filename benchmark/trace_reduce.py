"""From a profiler trace to numbers: device busy and idle, per-module time,
the operations that took most time, the longest idle gaps.

The reduction works on a plain structure, so that it can be checked against a
small recorded trace kept beside it (`trace_fixture.json`) without a chip:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

`load_xplane` turns the profiler's `.xplane.pb` into that structure with
nothing but JAX (`jax.profiler.ProfileData`). On a TPU the device planes are
named `/device:TPU:<n>`; their line `XLA Ops` holds one event per executed HLO
operation (nested for `while` bodies and fusions) and `XLA Modules` one event
per executed program. Host threads are lines of the plane `/host:CPU`.

Definitions (on-chip-measurement guide, section 4):
- busy    = union of the intervals in which an operation ran on the device,
            averaged over the device planes used;
- window  = the trace's own extent: from the first device event's start to
            the last one's end, on the device's clock. No host clock enters
            (a host-timed window read 3.70 s where the capture held 2.91 s
            of events and printed 21.7% idle for 0.43%: my chip run, PR 22),
            so idle before the first and after the last device event of a
            capture is not counted: on a device that is mostly idle the
            share reads low by at most one gap;
- idle %  = 100 * (1 - busy / window);
- module time = sum of the durations of that module's events;
- an operation's time = its SELF time (its interval minus what its nested
            children cover), so a `while` does not count its body twice.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest `.xplane.pb` under a `jax.profiler` log directory."""
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime,
    )
    return found[-1] if found else None


def load_xplane(path: str, keep_lines: Optional[Iterable[str]] = None) -> dict:
    """Read an `.xplane.pb` into the plain structure (module docstring)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            if keep_lines is not None and DEVICE_PLANE.match(plane.name) \
                    and line.name not in keep_lines:
                continue
            lines.append({
                "name": line.name,
                "events": [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                ],
            })
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def describe(trace: dict) -> list[str]:
    """One line per plane and line: what a trace holds, for a first look."""
    out = []
    for plane in trace["planes"]:
        out.append(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            evs = line["events"]
            head = ", ".join(str(e[0])[:40] for e in evs[:3])
            out.append(f"  LINE {line['name']!r}: {len(evs)} events ({head})")
    return out


def merged(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """The intervals' union as a sorted list of disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events: list[list]) -> dict[str, float]:
    """Self time per operation name on one line (nanoseconds): each event's
    duration minus what the events nested inside it cover."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    totals: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(self_ns, 0.0)

    for name, start, dur in order:
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return totals


def _line(plane: dict, name: str) -> Optional[dict]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def _busy_events(plane: dict) -> list[list]:
    """The events that mean "an operation ran": the `XLA Ops` line where the
    trace has one, else every event of the plane except step markers."""
    ops = _line(plane, OPS_LINE)
    if ops is not None:
        return ops["events"]
    return [
        e for line in plane["lines"] if line["name"] != "Steps"
        for e in line["events"]
    ]


def host_events(trace: dict) -> list[list]:
    """Host-side events (TraceMe / TraceAnnotation) with a duration."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            out.extend(e for e in line["events"] if e[2] > 0)
    return out


def label_gap(gap: tuple[float, float], host: list[list],
              prefixes: tuple[str, ...] = ("bench:",)) -> str:
    """What the host was doing in an idle gap. A gap is attributed only to
    one of the harness's own `TraceAnnotation`s (names starting with one of
    `prefixes`) and only where that annotation covers at least half of it.
    Anything else is `unattributed` (the `tracing` issue puts names inside
    the program); where one of the profiler's own host events covers half
    the gap, its name follows as a hint (`unattributed;host=<event>`)."""
    gs, ge = gap
    best = {True: ("", 0.0), False: ("", 0.0)}
    for name, start, dur in host:
        cover = min(ge, start + dur) - max(gs, start)
        ours = str(name).startswith(prefixes)
        if cover > best[ours][1]:
            best[ours] = (str(name), cover)
    half = 0.5 * (ge - gs)
    if best[True][1] >= half:
        return best[True][0]
    if best[False][1] >= half:
        return f"unattributed;host={best[False][0][:60]}"
    return "unattributed"


def reduce(trace: dict, top: int = 10, gaps: int = 5) -> Optional[dict]:
    """The numbers of one trace, or None when no operation ran on a device.

    Returns busy_s / window_s / idle_pct (busy averaged over device planes),
    `modules` {name: {"count", "total_s", "median_s"}}, `top_ops`
    [[name, self_seconds], ...] and `idle_gaps` [[label, seconds], ...]."""
    planes = device_planes(trace)
    per_plane = []
    for plane in planes:
        evs = [e for e in _busy_events(plane) if e[2] > 0]
        if evs:
            per_plane.append((plane, evs))
    if not per_plane:
        return None
    host = host_events(trace)
    busy = []
    extents = []
    ops: dict[str, float] = {}
    modules: dict[str, list[float]] = {}
    gap_list: list[tuple[float, float, float]] = []  # (length, start, end)
    for plane, evs in per_plane:
        ivs = merged((e[1], e[1] + e[2]) for e in evs)
        busy.append(sum(e - s for s, e in ivs))
        extents.append(ivs[-1][1] - ivs[0][0])
        for name, ns in self_times(evs).items():
            ops[name] = ops.get(name, 0.0) + ns
        mod_line = _line(plane, MODULES_LINE)
        if mod_line is not None:
            for name, _, dur in mod_line["events"]:
                modules.setdefault(module_key(name), []).append(dur)
        gap_list.extend((s1 - e0, e0, s1) for (_, e0), (s1, _) in zip(ivs, ivs[1:]))
    n = len(per_plane)
    busy_s = sum(busy) / n / 1e9
    window = max(extents) / 1e9
    # Only the longest gaps are labelled: a small-batch program leaves a gap
    # after nearly every operation, and labelling walks the host's events.
    gap_list.sort(reverse=True)
    labelled = [(ns, label_gap((s, e), host)) for ns, s, e in gap_list[:gaps]]
    return {
        "busy_s": busy_s,
        "window_s": window,
        "idle_pct": 100.0 * (1.0 - busy_s / window),
        "device_planes": n,
        "modules": {
            k: {
                "count": len(v),
                "total_s": sum(v) / 1e9,
                "median_s": sorted(v)[len(v) // 2] / 1e9,
            }
            for k, v in modules.items()
        },
        "top_ops": [
            [short_name(k), v / n / 1e9]
            for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [[label, ns / 1e9] for ns, label in labelled],
    }


def short_name(hlo: str, limit: int = 140) -> str:
    """An HLO operation's text without layouts and cut to `limit`: the
    trace names an operation by its whole instruction."""
    return re.sub(r"\{[^{}]*\}", "", str(hlo))[:limit]


def module_key(event_name: str) -> str:
    """`jit_train_step(1234567)` -> `jit_train_step`: the program's name
    without the fingerprint the profiler appends."""
    return re.sub(r"\(\d+\)$", "", str(event_name)).strip()
