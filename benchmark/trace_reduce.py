"""From a profiler trace to numbers: device busy and idle, per-module time,
the operations that took most time, the longest idle gaps.

The reduction works on a plain structure, so that it can be checked against a
small recorded trace kept beside it (`trace_fixture.json`) without a chip:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

An event may carry a fourth element, `{stat: value}`: those of the profiler's
stats the caller asked `load_xplane` for (`stats=`) and the event has. The
one this file reads itself is `NAME_STACK_STAT`, the operation's name stack
(where `jax.named_scope` shows); everything here works on events with and
without it.

`load_xplane` turns the profiler's `.xplane.pb` into that structure with
nothing but JAX (`jax.profiler.ProfileData`) for planes, lines, events and an
event's own stats. What names an operation is not among those: the v5e's
profiler keeps the name stack, the category, the operations and bytes of an
HLO instruction once, as stats of the event's METADATA entry, which
`ProfileData` does not show. `metadata_stats` reads those from the file's
protobuf wire format directly (a few dozen lines, no further package) and
`load_xplane` joins them to the events by plane and name. On a TPU the
device planes are named `/device:TPU:<n>`; their line `XLA Ops` holds one
event per executed HLO operation (nested for `while` bodies and fusions) and
`XLA Modules` one event per executed program. Host threads are lines of the
plane `/host:CPU`.

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
import struct
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# The stat that holds an operation's name stack as the v5e's profiler writes
# it: `jit(train_step)/while/body/closed_call/.../conv_general_dilated:`, on
# the METADATA entry of an `XLA Ops` event (PERF.md section 5, PR 25). A
# Mosaic kernel is a `custom-call` event whose stack ends in `pallas_call:`.
NAME_STACK_STAT = "tf_op"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest `.xplane.pb` under a `jax.profiler` log directory."""
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime,
    )
    return found[-1] if found else None


def _wire(buf):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a view of its bytes, not parsed further."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        value = shift = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return value

    while i < n:
        key = varint()
        number, kind = key >> 3, key & 7
        if kind == 0:
            yield number, kind, varint()
        elif kind == 2:
            size = varint()
            yield number, kind, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            yield number, kind, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {kind} in an xplane file")


def _keeps(stats: Iterable[str]):
    """The test `name -> bool` for a `stats=` argument; `"*"` keeps all."""
    wanted = frozenset(stats)
    return (lambda name: True) if "*" in wanted else wanted.__contains__


def _map_entry(buf) -> tuple:
    entry = {number: value for number, _, value in _wire(buf)}
    return entry.get(1), entry.get(2)


def metadata_stats(path: str, stats: Iterable[str]) -> dict[str, dict[str, dict]]:
    """{plane name: {event name: {stat: value}}} from the stats of each
    plane's event METADATA (`tf_op`, `hlo_category`, `flops`,
    `bytes_accessed`, `source`, ... on a TPU's `XLA Ops`), for the names in
    `stats` (`"*"`: all but raw bytes). Field numbers are those of
    `xplane.proto` (tsl/profiler): XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5 (maps: key 1, value 2);
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
    XStat.metadata_id = 1, .double_value = 2, .uint64_value = 3,
    .int64_value = 4, .str_value = 5, .ref_value = 7."""
    keep = _keeps(stats)
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: dict[str, dict[str, dict]] = {}
    for number, _, plane in _wire(space):
        if number != 1:
            continue
        plane_name, events, stat_names = "", [], {}
        for field, _, value in _wire(plane):
            if field == 2:
                plane_name = bytes(value).decode()
            elif field == 4:
                events.append(_map_entry(value)[1])
            elif field == 5:
                key, meta = _map_entry(value)
                names = [bytes(v).decode() for f, _, v in _wire(meta) if f == 2]
                stat_names[key] = names[0] if names else ""
        found: dict[str, dict] = {}
        for meta in events:
            name, kept = None, {}
            for field, _, value in _wire(meta):
                if field == 2:
                    name = bytes(value).decode()
                elif field == 5:
                    stat = {f: v for f, _, v in _wire(value)}
                    key = stat_names.get(stat.get(1), "")
                    if not keep(key):
                        continue
                    if 5 in stat:
                        kept[key] = bytes(stat[5]).decode(errors="replace")
                    elif 7 in stat:
                        kept[key] = stat_names.get(stat[7], "")
                    elif 2 in stat:
                        kept[key] = struct.unpack("<d", stat[2])[0]
                    elif 3 in stat or 4 in stat:
                        kept[key] = stat.get(3, stat.get(4))
            if name is not None and kept:
                found[name] = kept
        if found:
            out[plane_name] = found
    return out


def load_xplane(path: str, keep_lines: Optional[Iterable[str]] = None,
                stats: Iterable[str] = ()) -> dict:
    """Read an `.xplane.pb` into the plain structure (module docstring).
    Where `stats` names some of the profiler's stats, an event that has any
    of them, of its own or through its metadata entry, is
    `[name, start_ns, duration_ns, {stat: value}]`; `"*"` keeps every stat
    (for `describe`, a first look)."""
    import jax

    stats = tuple(stats)
    keep = _keeps(stats)
    by_plane = metadata_stats(path, stats) if stats else {}

    def plain(e, of_name: dict) -> list:
        out = [e.name, float(e.start_ns), float(e.duration_ns)]
        if stats:
            found = {**of_name.get(e.name, {}),
                     **{k: v for k, v in e.stats if keep(k)}}
            if found:
                out.append(found)
        return out

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        of_name = by_plane.get(plane.name, {})
        lines = []
        for line in plane.lines:
            if keep_lines is not None and DEVICE_PLANE.match(plane.name) \
                    and line.name not in keep_lines:
                continue
            lines.append({"name": line.name,
                          "events": [plain(e, of_name) for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def describe(trace: dict) -> list[str]:
    """One line per plane and line: what a trace holds, for a first look,
    with the names of the stats the line's first event carries (of those the
    trace was loaded with: `stats=("*",)` shows all)."""
    out = []
    for plane in trace["planes"]:
        out.append(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            evs = line["events"]
            head = ", ".join(str(e[0])[:40] for e in evs[:3])
            out.append(f"  LINE {line['name']!r}: {len(evs)} events ({head})")
            if evs and len(evs[0]) > 3:
                out.append(f"    stats of the first: {sorted(evs[0][3])}")
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

    for name, start, dur, *_ in order:
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


GAP_PREFIXES = ("bench:", "ac:")


def label_gap(gap: tuple[float, float], host: list[list],
              prefixes: tuple[str, ...] = GAP_PREFIXES) -> str:
    """What the host was doing in an idle gap. A gap is attributed only to a
    `TraceAnnotation` whose name starts with one of `prefixes` and only where
    that annotation covers at least half of it: `bench:<what>` is the
    harness's own, `ac:<span name>` the name under which the program mirrors
    its `telemetry` spans (`ac:update`, `ac:log`, `ac:checkpoint`, ...) into
    the profiler's trace. The benchmark fixes that name, the program meets
    it. Anything else is `unattributed`; where one of the profiler's own
    host events covers half the gap, its name follows as a hint
    (`unattributed;host=<event>`)."""
    gs, ge = gap
    best = {True: ("", 0.0), False: ("", 0.0)}
    for name, start, dur, *_ in host:
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


_WRAPPER = re.compile(r"^p?jit(\(.*\))?$")


def scope_of(event: list, limit: int = 48) -> Optional[str]:
    """The event's name stack (`NAME_STACK_STAT`) cut to what a person
    reads, or None where the event carries no stack. The `jit(...)` and
    `pjit` wrappers and the closing colon go; of what is left the FIRST
    component stays, because it is the phase (`while` is the rollout's scan,
    `jvp(...)` the update's forward, `transpose(jvp(...))` its backward; a
    `jax.named_scope` round a phase lands there too), and the LAST two, the
    layer and the primitive (`conv_0/conv_general_dilated`), with `~` for
    what was between. At most `limit` characters, cut at the end."""
    stack = event[3].get(NAME_STACK_STAT) if len(event) > 3 else None
    if not stack:
        return None
    parts = [p for p in str(stack).rstrip(":").split("/")
             if p and not _WRAPPER.match(p)]
    if not parts:
        return None
    if len(parts) > 3:
        parts = [parts[0], "~", *parts[-2:]]
    return "/".join(parts)[:limit]


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
    first: dict[str, list] = {}  # an instruction runs under one name stack
    modules: dict[str, list[float]] = {}
    gap_list: list[tuple[float, float, float]] = []  # (length, start, end)
    for plane, evs in per_plane:
        ivs = merged((e[1], e[1] + e[2]) for e in evs)
        busy.append(sum(e - s for s, e in ivs))
        extents.append(ivs[-1][1] - ivs[0][0])
        for name, ns in self_times(evs).items():
            ops[name] = ops.get(name, 0.0) + ns
        for e in evs:
            first.setdefault(e[0], e)
        mod_line = _line(plane, MODULES_LINE)
        if mod_line is not None:
            for name, _, dur, *_ in mod_line["events"]:
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
            [short_name(k, scope_of(first[k])), v / n / 1e9]
            for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [[label, ns / 1e9] for ns, label in labelled],
    }


def short_name(hlo: str, scope: Optional[str] = None, limit: int = 140) -> str:
    """An HLO operation's text without layouts and cut to `limit`: the trace
    names an operation by its whole instruction. With a scope, what tells
    most comes first (the ledger keeps 64 characters of a name):
    `<scope>|<op name> <output shape>`, then the rest of the text."""
    text = re.sub(r"\{[^{}]*\}", "", str(hlo))
    if scope is not None:
        m = re.match(r"^%?(\S+) = (.*)$", text)
        text = f"{scope}|{m.group(1)} {m.group(2)}" if m else f"{scope}|{text}"
    return text[:limit]


def module_key(event_name: str) -> str:
    """`jit_train_step(1234567)` -> `jit_train_step`: the program's name
    without the fingerprint the profiler appends."""
    return re.sub(r"\(\d+\)$", "", str(event_name)).strip()
