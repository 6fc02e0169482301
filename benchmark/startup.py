"""From the program's `spans.jsonl` to where `train.main`'s wait before its
first step goes (the readers `startup_ms`, `init_state_ms`, `retrace_ms`,
`cache_load_ms`, `gc_pause_ms`).

The training thread is the `tid` of the first `update` span (the lowest
`it`). Start-up runs from the start of the `startup` span on that thread,
which `train.main` opens once its arguments are parsed and which may start
before the tracer's ts 0, to the end of that first `update`, which holds the
step's re-trace, lowering and compile or cache load. A run whose spans hold
no `startup` (one made before the program had it) gives None everywhere.
"""

from __future__ import annotations

from typing import Optional

# Rounding of `ts` and `dur` to 0.1 us may put a child a hair past its parent.
_SLACK_US = 1.0


def _complete(run: dict, name: str) -> list[dict]:
    return [e for e in run.get("spans", [])
            if e.get("ph") == "X" and e.get("name") == name]


def first_update(run: dict) -> Optional[dict]:
    updates = [e for e in _complete(run, "update")
               if (e.get("args") or {}).get("it") is not None]
    return min(updates, key=lambda e: e["args"]["it"]) if updates else None


def window(run: dict) -> Optional[tuple[float, float, int]]:
    """(start of `startup`, end of the first `update`, training tid), in
    microseconds on the session's clock, or None."""
    update = first_update(run)
    if update is None:
        return None
    end = update["ts"] + update["dur"]
    starts = [e["ts"] for e in _complete(run, "startup")
              if e["tid"] == update["tid"] and e["ts"] <= update["ts"]]
    if not starts:
        return None
    return max(starts), end, update["tid"]


def _union_ms(intervals: list[tuple[float, float]]) -> float:
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total / 1e3


def span_ms(run: dict, name: str) -> Optional[float]:
    """The duration of the span `name` on the training thread inside
    start-up (the last, should there be more)."""
    w = window(run)
    if w is None:
        return None
    found = [e for e in _complete(run, name)
             if e["tid"] == w[2] and w[0] - _SLACK_US <= e["ts"]
             and e["ts"] + e["dur"] <= w[1] + _SLACK_US]
    return found[-1]["dur"] / 1e3 if found else None


def in_first_update_ms(run: dict, names: tuple[str, ...]) -> Optional[float]:
    """Time covered by the spans called `names` on the training thread that
    lie inside the first `update` (overlaps counted once)."""
    update = first_update(run)
    if window(run) is None:
        return None
    lo, hi = update["ts"] - _SLACK_US, update["ts"] + update["dur"] + _SLACK_US
    return _union_ms([
        (e["ts"], e["ts"] + e["dur"])
        for name in names for e in _complete(run, name)
        if e["tid"] == update["tid"] and e["ts"] >= lo
        and e["ts"] + e["dur"] <= hi])


def startup_gc_ms(run: dict) -> Optional[float]:
    """Full collections on the training thread that start inside start-up;
    0 where none ran."""
    w = window(run)
    if w is None:
        return None
    return sum(e["dur"] for e in _complete(run, "gc")
               if e["tid"] == w[2] and w[0] <= e["ts"] < w[1]) / 1e3
