#!/usr/bin/env python
"""Round-over-round bench trend: the multi-metric view of BENCH_r*.json.

The round driver's artifact (`BENCH_rNN.json`, one JSON line per round)
used to be read headline-only — a round whose headline could not run
looked like "0.0" even though PR 6 started attaching a CPU-measured
`cpu_metrics` block to EVERY record. This script trends the WHOLE block
across rounds, so
regressions in host_pool_scaling / startup_to_first_step /
async_decoupling / update_wall / fused_update_wall /
replay_sample_throughput / multihost_scaling are visible even across
rounds whose TPU headline never ran. The multihost record additionally expands into
per-process-count sub-rows (its sync scaling curve) and the straggler
gossip-over-sync ratio.

Usage:
    python scripts/bench_trend.py            # repo-root BENCH_r*.json
    python scripts/bench_trend.py --root DIR # a fixture/scratch tree
    python scripts/bench_trend.py --json     # machine-readable rows

Output: one markdown table, rounds as columns — headline first (a round
whose headline did not run shows `dead`), then one row per cpu_metrics
entry ever seen (`-`
before a metric existed, `err` where a round's subprocess failed).
Tolerant of malformed files: a round that cannot be parsed shows as a
column of `?` rather than taking the report down.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys


def round_files(root: str) -> list[tuple[int, str]]:
    """(round number, path) sorted by round, from BENCH_r*.json names."""
    out = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def load_record(path: str) -> dict | None:
    """The bench record inside one round file, else None.

    Two shapes exist: the driver's wrapper object ({"n", "cmd", "rc",
    "tail", "parsed": <record>} — pretty-printed, multi-line; `parsed`
    holds the bench.py JSON line, with `tail` as the raw fallback) and
    bench.py's own one-record-per-line output."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return None
    try:
        rec = json.loads(text)
    except json.JSONDecodeError:
        rec = None
    if isinstance(rec, dict):
        if isinstance(rec.get("parsed"), dict):
            return rec["parsed"]
        if "metric" in rec:
            return rec
        # Wrapper without a parsed record (e.g. a crashed child): the
        # tail may still carry bench.py's JSON line.
        tail = rec.get("tail")
        if isinstance(tail, str):
            for ln in reversed(tail.strip().splitlines()):
                try:
                    inner = json.loads(ln)
                except json.JSONDecodeError:
                    continue
                if isinstance(inner, dict) and "metric" in inner:
                    return inner
        return None
    # Line-oriented fallback (bench.py's direct output).
    for ln in reversed([l for l in text.splitlines() if l.strip()]):
        try:
            inner = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if isinstance(inner, dict):
            return inner
    return None


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, bool):
        return str(v)
    if isinstance(v, (int, float)):
        if v == 0:
            return "0"
        if abs(v) >= 10000:
            return f"{v:.3g}"
        return f"{v:g}"
    return str(v)[:12]


def headline_cell(rec: dict | None) -> str:
    if rec is None:
        return "?"
    value = rec.get("value")
    if rec.get("error") or not value:
        return "dead"
    return _fmt(value)


def cpu_cell(rec: dict | None, name: str) -> str:
    if rec is None:
        return "?"
    block = rec.get("cpu_metrics")
    if not isinstance(block, dict):
        return "-"
    entry = block.get(name)
    if entry is None:
        return "-"
    if not isinstance(entry, dict):
        return _fmt(entry)
    if "error" in entry:
        return "err"
    return _fmt(entry.get("value"))


def _metric_entry(rec: dict | None, name: str):
    """(entry, None) when the round carries a well-formed cpu_metrics
    dict for `name`, else (None, sentinel cell) — the shared
    presence/malformed ladder of every sub-row: `?` for an unparseable
    round, `-` before the metric existed, `err` for a failed
    subprocess, `?` for a present-but-malformed entry."""
    if rec is None:
        return None, "?"
    block = rec.get("cpu_metrics")
    if not isinstance(block, dict) or name not in block:
        return None, "-"
    entry = block[name]
    if not isinstance(entry, dict):
        return None, "?"
    if "error" in entry:
        return None, "err"
    return entry, None


def _multihost_entry(rec: dict | None):
    return _metric_entry(rec, "multihost_scaling")


def _numeric_cell(value) -> str:
    return _fmt(value) if isinstance(value, (int, float)) else "?"


def multihost_proc_counts(recs: list[dict | None]) -> list[int]:
    """Union of sync-curve process counts across rounds (the ISSUE 9
    record nests per-process-count runs under `sync`)."""
    counts: set[int] = set()
    for rec in recs:
        entry, _ = _multihost_entry(rec)
        sync = entry.get("sync") if entry else None
        if isinstance(sync, dict):
            for k in sync:
                if str(k).isdigit():
                    counts.add(int(k))
    return sorted(counts)


def multihost_proc_cell(rec: dict | None, n: int) -> str:
    """Aggregate consumed env-steps/s of the n-process sync run."""
    entry, cell = _multihost_entry(rec)
    if entry is None:
        return cell
    sync = entry.get("sync")
    if not isinstance(sync, dict):
        return "?"
    sub = sync.get(str(n))
    if sub is None:
        return "-"
    if not isinstance(sub, dict):
        return "?"
    return _numeric_cell(sub.get("aggregate_steps_per_s"))


def serving_cell(rec: dict | None, field: str) -> str:
    """One micro-batched sub-metric of the serving SLO record (ISSUE 10
    satellite: the p50/p99/actions-per-s curve trends per round)."""
    entry, cell = _metric_entry(rec, "serving_latency")
    if entry is None:
        return cell
    sub = entry.get("micro_batched")
    if not isinstance(sub, dict):
        return "?"
    return _numeric_cell(sub.get(field))


def pad_overhead_cell(rec: dict | None, group: str, key: str) -> str:
    """One padded-vs-exact shape pair of the pad-overhead record
    (ISSUE 20 satellite: the shape-stabilization tax — Pallas ragged
    lanes, serving bucket backfill — trends per round)."""
    entry, cell = _metric_entry(rec, "pad_overhead")
    if entry is None:
        return cell
    sub = entry.get(group)
    if not isinstance(sub, dict):
        return "?"
    pair = sub.get(key)
    if not isinstance(pair, dict):
        return "?"
    return _numeric_cell(pair.get("overhead_x"))


def fleet_replica_counts(recs: list[dict | None]) -> list[int]:
    """Union of fleet-curve replica counts across rounds (the ISSUE 17
    record nests per-count runs under `points`, keyed by `replicas`)."""
    counts: set[int] = set()
    for rec in recs:
        entry, _ = _metric_entry(rec, "serving_fleet_scaling")
        points = entry.get("points") if entry else None
        if isinstance(points, list):
            for p in points:
                if isinstance(p, dict) and isinstance(
                    p.get("replicas"), int
                ):
                    counts.add(p["replicas"])
    return sorted(counts)


def fleet_point_cell(rec: dict | None, n: int, field: str) -> str:
    """One field of the n-replica fleet point (ISSUE 17: actions/s and
    p99 per replica count trend per round)."""
    entry, cell = _metric_entry(rec, "serving_fleet_scaling")
    if entry is None:
        return cell
    points = entry.get("points")
    if not isinstance(points, list):
        return "?"
    for p in points:
        if isinstance(p, dict) and p.get("replicas") == n:
            return _numeric_cell(p.get(field))
    return "-"


def scenario_mixture_types(recs: list[dict | None]) -> list[str]:
    """Union of mixture member names across rounds (the ISSUE 11 record
    nests per-type steps/s under `mixture.per_type_steps_per_s`)."""
    names: list[str] = []
    for rec in recs:
        entry, _ = _metric_entry(rec, "scenario_fleet")
        mix = entry.get("mixture") if entry else None
        per_type = mix.get("per_type_steps_per_s") if isinstance(mix, dict) else None
        if isinstance(per_type, dict):
            for k in per_type:
                if k not in names:
                    names.append(k)
    return names


def scenario_type_cell(rec: dict | None, name: str) -> str:
    """One member type's homogeneous-fleet steps/s (`-` before the
    mixture block existed, `?` where it is present but malformed)."""
    entry, cell = _metric_entry(rec, "scenario_fleet")
    if entry is None:
        return cell
    mix = entry.get("mixture")
    if mix is None:
        return "-"
    if not isinstance(mix, dict):
        return "?"
    per_type = mix.get("per_type_steps_per_s")
    if not isinstance(per_type, dict):
        return "?"
    if name not in per_type:
        return "-"
    return _numeric_cell(per_type[name])


def scenario_mixture_cell(rec: dict | None, field: str) -> str:
    """A scalar field of the heterogeneous-mixture block."""
    entry, cell = _metric_entry(rec, "scenario_fleet")
    if entry is None:
        return cell
    mix = entry.get("mixture")
    if mix is None:
        return "-"
    if not isinstance(mix, dict):
        return "?"
    return _numeric_cell(mix.get(field))


def scenario_sweep_cell(rec: dict | None) -> str:
    """Peak steps/s of the instance-count sweep (the rollover curve's
    summit; the full curve lives in the round record)."""
    entry, cell = _metric_entry(rec, "scenario_fleet")
    if entry is None:
        return cell
    sweep = entry.get("instance_sweep")
    if sweep is None:
        return "-"
    if not isinstance(sweep, dict):
        return "?"
    return _numeric_cell(sweep.get("peak_steps_per_s"))


def update_wall_guarded_cell(rec: dict | None) -> str:
    """The ISSUE 14 finite-gate overhead wall (`guarded_ms`) of the
    update-wall record (`-` before the field existed, `?` malformed)."""
    entry, cell = _metric_entry(rec, "update_wall")
    if entry is None:
        return cell
    if "guarded_ms" not in entry:
        return "-"
    return _numeric_cell(entry.get("guarded_ms"))


def update_wall_field_cell(rec: dict | None, field: str) -> str:
    """A budget-counter actual of the update-wall record (ISSUE 15:
    `dispatches_per_block` / `device_transferred_bytes_per_block`, the
    same meters perfsan gates tier-1 with; `-` before the field
    existed, `?` malformed)."""
    entry, cell = _metric_entry(rec, "update_wall")
    if entry is None:
        return cell
    if field not in entry:
        return "-"
    return _numeric_cell(entry.get(field))


def fused_update_wall_cell(rec: dict | None, field: str) -> str:
    """A field of the ISSUE 19 fused-consume record (`fused_ms` /
    `bf16_ms` / `speedup_x`; `-` before the metric existed, `?`
    malformed)."""
    entry, cell = _metric_entry(rec, "fused_update_wall")
    if entry is None:
        return cell
    if field not in entry:
        return "-"
    return _numeric_cell(entry.get(field))


def data_plane_measured_cell(rec: dict | None, field: str) -> str:
    """A METERED transfer actual from the data-plane record's
    `per_block_transfer_bytes` row (ISSUE 15: `host_measured` /
    `enqueue_measured`, counted at perfsan's device_put/jnp.array
    seams rather than computed; `-` before the field existed, `?`
    malformed)."""
    entry, cell = _metric_entry(rec, "consumed_env_steps_per_s")
    if entry is None:
        return cell
    bytes_row = entry.get("per_block_transfer_bytes")
    if bytes_row is None:
        return "-"
    if not isinstance(bytes_row, dict):
        return "?"
    if field not in bytes_row:
        return "-"
    return _numeric_cell(bytes_row.get(field))


def data_plane_cell(rec: dict | None, plane: str) -> str:
    """One plane's consumed env-steps/s from the ISSUE 13 data-plane
    A/B record (`-` before the metric existed, `?` malformed)."""
    entry, cell = _metric_entry(rec, "consumed_env_steps_per_s")
    if entry is None:
        return cell
    sub = entry.get(plane)
    if sub is None:
        return "-"
    if not isinstance(sub, dict):
        return "?"
    return _numeric_cell(sub.get("consumed_steps_per_s"))


def data_plane_bytes_cell(rec: dict | None) -> str:
    """Per-consumed-block enqueue bytes of the device plane (the host
    plane's per-block figure rides the same record; consume-side
    transfer is 0 by construction)."""
    entry, cell = _metric_entry(rec, "consumed_env_steps_per_s")
    if entry is None:
        return cell
    bytes_row = entry.get("per_block_transfer_bytes")
    if bytes_row is None:
        return "-"
    if not isinstance(bytes_row, dict):
        return "?"
    return _numeric_cell(bytes_row.get("device_enqueue_per_block"))


def multihost_straggler_cell(rec: dict | None) -> str:
    """The straggler A/B ratio (gossip over sync fleet throughput)."""
    entry, cell = _multihost_entry(rec)
    if entry is None:
        return cell
    straggler = entry.get("straggler")
    if not isinstance(straggler, dict):
        return "?"
    return _numeric_cell(straggler.get("gossip_over_sync"))


def multihost_recover_cell(rec: dict | None) -> str:
    """Wall time-to-recover after an injected host kill (ISSUE 12's
    fault-injection block; `-` before the block existed, `?`/`err`
    where it is malformed or the chaos run failed)."""
    entry, cell = _multihost_entry(rec)
    if entry is None:
        return cell
    fault = entry.get("fault_injection")
    if fault is None:
        return "-"
    if not isinstance(fault, dict):
        return "?"
    if "error" in fault:
        return "err"
    return _numeric_cell(fault.get("time_to_recover_s"))


def trend_rows(root: str) -> tuple[list[int], list[tuple[str, list[str]]]]:
    """(round numbers, [(row label, cells per round)]) — the table body.

    The row set is the UNION of cpu_metrics names across all rounds, so
    a metric added in round N trends as `-` before N instead of
    silently starting the table late."""
    files = round_files(root)
    rounds = [n for n, _ in files]
    recs = [load_record(p) for _, p in files]
    names: list[str] = []
    for rec in recs:
        if rec and isinstance(rec.get("cpu_metrics"), dict):
            for k in rec["cpu_metrics"]:
                if k != "error" and k not in names:
                    names.append(k)
    rows = [("tpu_headline", [headline_cell(r) for r in recs])]
    for name in names:
        rows.append((name, [cpu_cell(r, name) for r in recs]))
        if name == "multihost_scaling":
            # Per-process-count sub-rows (ISSUE 9): the sync scaling
            # curve, one row per process count ever benchmarked, plus
            # the straggler A/B ratio — so a scaling regression at one
            # fleet size is visible even when the headline ratio holds.
            for n in multihost_proc_counts(recs):
                rows.append((
                    f"multihost_scaling.p{n}",
                    [multihost_proc_cell(r, n) for r in recs],
                ))
            rows.append((
                "multihost_scaling.straggler_gossip_x",
                [multihost_straggler_cell(r) for r in recs],
            ))
            rows.append((
                "multihost_scaling.recover_s",
                [multihost_recover_cell(r) for r in recs],
            ))
        if name == "update_wall":
            # Numerics-guard sub-row (ISSUE 14): the update wall with
            # the per-update finite-gate on, so the guard overhead
            # trends as a measured number next to the wall it taxes.
            rows.append((
                "update_wall.guarded_ms",
                [update_wall_guarded_cell(r) for r in recs],
            ))
            # Budget-counter sub-rows (ISSUE 15): dispatches and the
            # device-gather transfer bytes per steady-state block —
            # the same counters perfsan gates, trended so a program
            # quietly splitting into two dispatches (or the slot
            # scalar growing into a block re-upload) is visible next
            # to the wall it would tax.
            for field in (
                "dispatches_per_block",
                "device_transferred_bytes_per_block",
            ):
                rows.append((
                    f"update_wall.{field}",
                    [update_wall_field_cell(r, field) for r in recs],
                ))
        if name == "fused_update_wall":
            # Fused-consume sub-rows (ISSUE 19): the one-program
            # gather+decode+advantages+update wall, the bf16 update
            # wall behind --update-dtype, and the fused-vs-unfused
            # speedup — so the fusion silently splitting back into two
            # dispatches (speedup collapsing) or the bf16 path
            # regressing trends next to the walls they tax.
            for field in ("fused_ms", "bf16_ms", "speedup_x"):
                rows.append((
                    f"fused_update_wall.{field}",
                    [fused_update_wall_cell(r, field) for r in recs],
                ))
        if name == "scenario_fleet":
            # Scenario-universe sub-rows (ISSUE 11): the heterogeneous
            # mixture fleet's steps/s, each member type's homogeneous
            # steps/s at the same shape, and the instance-sweep peak —
            # so a per-type regression (one member's step got slow) is
            # visible even when the homogeneous headline holds.
            rows.append((
                "scenario_fleet.mixture",
                [scenario_mixture_cell(r, "steps_per_s") for r in recs],
            ))
            for t in scenario_mixture_types(recs):
                rows.append((
                    f"scenario_fleet.{t}",
                    [scenario_type_cell(r, t) for r in recs],
                ))
            rows.append((
                "scenario_fleet.sweep_peak",
                [scenario_sweep_cell(r) for r in recs],
            ))
        if name == "serving_latency":
            # Micro-batched gateway sub-rows (ISSUE 10): the SLO curve
            # (p50/p99 at saturating closed-loop concurrency) and the
            # absolute actions/s, so a latency regression is visible
            # even when the headline speedup ratio holds. The hist_*
            # quantiles + burn rate (ISSUE 16) are the server-side
            # histogram-derived view — the mergeable fleet metric —
            # trending next to the loadgen's client-side point
            # percentiles; rounds predating them render `?`.
            for field in ("actions_per_s", "p50_ms", "p99_ms",
                          "slo_burn", "hist_p50_ms", "hist_p99_ms"):
                rows.append((
                    f"serving_latency.{field}",
                    [serving_cell(r, field) for r in recs],
                ))
        if name == "serving_fleet_scaling":
            # Fleet scale-out sub-rows (ISSUE 17): absolute actions/s
            # and p99 at every replica count ever benchmarked, so a
            # flat curve (replicas stopped helping) or a tail-latency
            # regression at one fleet size is visible even when the
            # headline 3-vs-1 ratio holds.
            for n in fleet_replica_counts(recs):
                rows.append((
                    f"serving_fleet_scaling.r{n}",
                    [fleet_point_cell(r, n, "actions_per_s")
                     for r in recs],
                ))
                rows.append((
                    f"serving_fleet_scaling.r{n}.p99_ms",
                    [fleet_point_cell(r, n, "p99_ms") for r in recs],
                ))
        if name == "consumed_env_steps_per_s":
            # Data-plane A/B sub-rows (ISSUE 13): each plane's absolute
            # consumed env-steps/s and the device plane's per-block
            # enqueue bytes, so a regression in either plane (or a
            # codec silently fattening the enqueue) is visible even
            # when the headline device figure holds.
            for plane in ("host", "device"):
                rows.append((
                    f"consumed_env_steps_per_s.{plane}",
                    [data_plane_cell(r, plane) for r in recs],
                ))
            rows.append((
                "consumed_env_steps_per_s.enqueue_bytes",
                [data_plane_bytes_cell(r) for r in recs],
            ))
            # Metered actuals (ISSUE 15): the host plane's per-block
            # upload and the device enqueue as perfsan's counters saw
            # them — drift between these and the computed rows above
            # means the accounting lied.
            for field in ("host_measured", "enqueue_measured"):
                rows.append((
                    f"consumed_env_steps_per_s.{field}",
                    [data_plane_measured_cell(r, field) for r in recs],
                ))
        if name == "pad_overhead":
            # Pad-tax sub-rows (ISSUE 20): the padded-vs-exact dispatch
            # overhead at every guarded shape — the Pallas ragged env
            # batches and the serving backfill sizes — so one pad seam
            # quietly growing a copy is attributable even when the
            # worst-case headline is carried by a different seam.
            for key in ("E7", "E96", "E200"):
                rows.append((
                    f"pad_overhead.pallas_{key}",
                    [pad_overhead_cell(r, "pallas", key) for r in recs],
                ))
            for key in ("n3", "n5"):
                rows.append((
                    f"pad_overhead.serving_{key}",
                    [pad_overhead_cell(r, "serving", key) for r in recs],
                ))
    return rounds, rows


def render(rounds: list[int], rows: list[tuple[str, list[str]]]) -> str:
    if not rounds:
        return "(no BENCH_r*.json rounds found)"
    head = ["metric"] + [f"r{n:02d}" for n in rounds]
    widths = [
        max(len(head[i]), *(len(r[1][i - 1]) if i else len(r[0]) for r in rows))
        for i in range(len(head))
    ]
    lines = [
        " | ".join(h.ljust(w) for h, w in zip(head, widths)),
        "-|-".join("-" * w for w in widths),
    ]
    for label, cells in rows:
        lines.append(
            " | ".join(
                c.ljust(w) for c, w in zip([label, *cells], widths)
            )
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--root",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        help="directory holding BENCH_r*.json (default: repo root)",
    )
    p.add_argument(
        "--json", action="store_true",
        help="emit {rounds, rows} as JSON instead of the table",
    )
    args = p.parse_args(argv)
    rounds, rows = trend_rows(args.root)
    if args.json:
        print(json.dumps({
            "rounds": rounds,
            "rows": {label: cells for label, cells in rows},
        }))
    else:
        print(render(rounds, rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
