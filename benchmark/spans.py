"""From the program's `spans.jsonl` (Chrome-trace events, microseconds on the
session's clock) to durations inside the measured window.

The window's edges on that clock are the ends of the spans that carry the
iteration number of the window's first and last row (`log` in the fused loop,
`iteration` on the host path), so no clock has to be converted; a window the
harness timed itself (serving) is converted through the tracer's `clock_sync`.
"""

from __future__ import annotations

from typing import Optional

MARKERS = ("log", "iteration")


def window_us(run: dict) -> Optional[tuple[float, float]]:
    if "window_epoch" in run:
        # A window the harness timed itself (serving): onto the session's
        # clock through the `clock_sync` event the tracer writes first.
        for e in run.get("spans", []):
            if e.get("name") == "clock_sync":
                t0 = e["args"]["unix_epoch_at_ts0"]
                return tuple((t - t0) * 1e6 for t in run["window_epoch"])
        return None
    first, last = run["window_iters"]
    ends = {}
    for e in run.get("spans", []):
        if e.get("ph") == "X" and e.get("name") in MARKERS:
            it = (e.get("args") or {}).get("it")
            if it in (first, last):
                ends[it] = max(ends.get(it, 0.0), e["ts"] + e["dur"])
    if first not in ends or last not in ends or ends[last] <= ends[first]:
        return None
    return ends[first], ends[last]


def window_ms(run: dict) -> Optional[float]:
    w = window_us(run)
    return None if w is None else (w[1] - w[0]) / 1e3


def durations_ms(run: dict, name: str) -> list[float]:
    """Durations of the spans called `name` that start inside the window."""
    w = window_us(run)
    if w is None:
        return []
    return [
        e["dur"] / 1e3 for e in run.get("spans", [])
        if e.get("ph") == "X" and e.get("name") == name
        and w[0] <= e["ts"] < w[1]
    ]
