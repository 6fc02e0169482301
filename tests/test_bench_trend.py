"""scripts/bench_trend.py: the round driver's multi-metric trend view
(ROADMAP "Bench resilience", ISSUE 8 satellite) — wrapper and raw round
formats parse, the cpu_metrics block trends as rows (union across
rounds), a headline that did not run shows `dead`, malformed files
degrade to `?` columns instead of crashing."""

import importlib.util
import json
from pathlib import Path

REPO = Path(__file__).parent.parent


def _load():
    spec = importlib.util.spec_from_file_location(
        "bench_trend", REPO / "scripts" / "bench_trend.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_rounds(root: Path):
    # r01: driver wrapper, headline did not run, cpu_metrics present.
    rec1 = {
        "metric": "a2c", "value": 0.0, "error": "no chip",
        "cpu_metrics": {
            "host_pool_scaling": {"value": 3.0},
            "update_wall": {"error": "rc=1: boom"},
        },
    }
    (root / "BENCH_r01.json").write_text(
        json.dumps({"n": 1, "rc": 1, "parsed": rec1}, indent=2)
    )
    # r02: raw bench.py line format, green, adds a NEW metric.
    rec2 = {
        "metric": "a2c", "value": 123456.0,
        "cpu_metrics": {
            "host_pool_scaling": {"value": 2.9},
            "update_wall": {"value": 10.4},
            "replay_sample_throughput": {"value": 2.07e6},
        },
    }
    (root / "BENCH_r02.json").write_text(json.dumps(rec2) + "\n")
    # r03: malformed.
    (root / "BENCH_r03.json").write_text("{not json")


def test_trend_rows_union_and_cells(tmp_path):
    mod = _load()
    _write_rounds(tmp_path)
    rounds, rows = mod.trend_rows(str(tmp_path))
    assert rounds == [1, 2, 3]
    table = dict(rows)
    # Headline: did not run, green value, unparseable.
    assert table["tpu_headline"][0] == "dead"
    assert table["tpu_headline"][1] != "dead"
    assert table["tpu_headline"][2] == "?"
    # Union of metric names across rounds; '-' before a metric existed,
    # 'err' where a round's subprocess failed.
    assert table["host_pool_scaling"] == ["3", "2.9", "?"]
    assert table["update_wall"][0] == "err"
    assert table["replay_sample_throughput"][0] == "-"
    assert table["replay_sample_throughput"][1] != "-"


def _write_guarded_rounds(root: Path):
    """r01 before guarded_ms existed, r02 carrying it, r03 malformed
    (guarded_ms a string), r04 the whole entry a failed subprocess."""
    (root / "BENCH_r01.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"update_wall": {"value": 8.0}},
    }) + "\n")
    (root / "BENCH_r02.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"update_wall": {
            "value": 8.1, "guarded_ms": 8.9, "guard_overhead_x": 1.1,
        }},
    }) + "\n")
    (root / "BENCH_r03.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"update_wall": {
            "value": 8.2, "guarded_ms": "oops",
        }},
    }) + "\n")
    (root / "BENCH_r04.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"update_wall": {"error": "rc=1: boom"}},
    }) + "\n")


def test_update_wall_guarded_sub_row(tmp_path):
    """ISSUE 14 satellite: guarded_ms trends as an update_wall sub-row
    — '-' before the field existed, '?' where it is malformed, 'err'
    when the whole metric subprocess failed."""
    mod = _load()
    _write_guarded_rounds(tmp_path)
    _rounds, rows = mod.trend_rows(str(tmp_path))
    table = dict(rows)
    assert table["update_wall.guarded_ms"] == ["-", "8.9", "?", "err"]


def _write_budget_counter_rounds(root: Path):
    """r01 before the counters existed, r02 carrying them, r03
    malformed (a counter is a string), r04 a failed subprocess."""
    (root / "BENCH_r01.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"update_wall": {"value": 8.0}},
    }) + "\n")
    (root / "BENCH_r02.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"update_wall": {
            "value": 8.1, "dispatches_per_block": 1,
            "device_transferred_bytes_per_block": 4,
        }},
    }) + "\n")
    (root / "BENCH_r03.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"update_wall": {
            "value": 8.2, "dispatches_per_block": "oops",
            "device_transferred_bytes_per_block": None,
        }},
    }) + "\n")
    (root / "BENCH_r04.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"update_wall": {"error": "rc=1: boom"}},
    }) + "\n")


def test_update_wall_budget_counter_sub_rows(tmp_path):
    """ISSUE 15 satellite: the perfsan dispatch/transfer actuals trend
    as update_wall sub-rows — '-' before the fields existed, '?' where
    malformed, 'err' when the whole metric subprocess failed."""
    mod = _load()
    _write_budget_counter_rounds(tmp_path)
    _rounds, rows = mod.trend_rows(str(tmp_path))
    table = dict(rows)
    assert table["update_wall.dispatches_per_block"] == [
        "-", "1", "?", "err",
    ]
    assert table["update_wall.device_transferred_bytes_per_block"] == [
        "-", "4", "?", "err",
    ]


def _write_fused_update_rounds(root: Path):
    """r01 before the metric existed, r02 a full fused-consume record,
    r03 malformed (walls are strings / None), r04 a failed subprocess."""
    (root / "BENCH_r01.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"update_wall": {"value": 8.0}},
    }) + "\n")
    (root / "BENCH_r02.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"fused_update_wall": {
            "value": 4.2, "fused_ms": 4.2, "unfused_ms": 4.9,
            "speedup_x": 1.17, "bf16_ms": 3.6, "fp32_ms": 4.1,
        }},
    }) + "\n")
    (root / "BENCH_r03.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"fused_update_wall": {
            "value": 4.3, "fused_ms": "oops", "speedup_x": None,
            "bf16_ms": {"nested": True},
        }},
    }) + "\n")
    (root / "BENCH_r04.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"fused_update_wall": {"error": "rc=1: boom"}},
    }) + "\n")


def test_fused_update_wall_sub_rows(tmp_path):
    """ISSUE 19 satellite: the fused-consume record expands into
    fused_ms / bf16_ms / speedup_x sub-rows — '-' before the metric
    existed, '?' where malformed, 'err' when the subprocess failed."""
    mod = _load()
    _write_fused_update_rounds(tmp_path)
    _rounds, rows = mod.trend_rows(str(tmp_path))
    table = dict(rows)
    assert table["fused_update_wall"] == ["-", "4.2", "4.3", "err"]
    assert table["fused_update_wall.fused_ms"] == ["-", "4.2", "?", "err"]
    assert table["fused_update_wall.bf16_ms"] == ["-", "3.6", "?", "err"]
    assert table["fused_update_wall.speedup_x"] == [
        "-", "1.17", "?", "err",
    ]
    # sub-rows sit directly under their parent row
    labels = [name for name, _ in rows]
    i = labels.index("fused_update_wall")
    assert labels[i + 1:i + 4] == [
        "fused_update_wall.fused_ms",
        "fused_update_wall.bf16_ms",
        "fused_update_wall.speedup_x",
    ]


def _write_multihost_rounds(root: Path):
    """r01 without the metric, r02 a full multihost record, r03 a
    malformed one (sync curve not a dict), r04 an unparseable file."""
    (root / "BENCH_r01.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"host_pool_scaling": {"value": 3.0}},
    }) + "\n")
    (root / "BENCH_r02.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "multihost_scaling": {
                "value": 1.95,
                "sync": {
                    "1": {"aggregate_steps_per_s": 94.2},
                    "2": {"aggregate_steps_per_s": 162.6},
                    "4": {"aggregate_steps_per_s": 184.0},
                },
                "straggler": {"gossip_over_sync": 2.01},
                "fault_injection": {"time_to_recover_s": 8.41},
            },
        },
    }) + "\n")
    (root / "BENCH_r03.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "multihost_scaling": {
                "value": 0.5, "sync": "oops",
                "straggler": {"gossip_over_sync": None},
                "fault_injection": {"error": "FleetSanError: rejoin"},
            },
        },
    }) + "\n")
    (root / "BENCH_r04.json").write_text("{not json")


def test_multihost_per_process_rows(tmp_path):
    """ISSUE 9 satellite: the multihost_scaling record expands into one
    sub-row per sync process count plus the straggler ratio; '-' before
    the metric existed, '?' for malformed sub-records."""
    mod = _load()
    _write_multihost_rounds(tmp_path)
    rounds, rows = mod.trend_rows(str(tmp_path))
    assert rounds == [1, 2, 3, 4]
    table = dict(rows)
    assert table["multihost_scaling"] == ["-", "1.95", "0.5", "?"]
    assert table["multihost_scaling.p1"] == ["-", "94.2", "?", "?"]
    assert table["multihost_scaling.p2"] == ["-", "162.6", "?", "?"]
    assert table["multihost_scaling.p4"] == ["-", "184", "?", "?"]
    assert table["multihost_scaling.straggler_gossip_x"] == [
        "-", "2.01", "?", "?",
    ]
    # ISSUE 12 satellite: wall time-to-recover after an injected host
    # kill; '-' before the fault-injection block existed, 'err' where
    # the chaos run itself failed.
    assert table["multihost_scaling.recover_s"] == [
        "-", "8.41", "err", "?",
    ]
    # Sub-rows sit directly under the main multihost row.
    labels = [label for label, _ in rows]
    main = labels.index("multihost_scaling")
    assert labels[main + 1 : main + 4] == [
        "multihost_scaling.p1", "multihost_scaling.p2",
        "multihost_scaling.p4",
    ]


def test_render_and_cli(tmp_path, capsys):
    mod = _load()
    _write_rounds(tmp_path)
    assert mod.main(["--root", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "r01" in out and "r03" in out
    assert "replay_sample_throughput" in out
    assert mod.main(["--root", str(tmp_path), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["rounds"] == [1, 2, 3]
    assert "host_pool_scaling" in payload["rows"]


def test_empty_root(tmp_path, capsys):
    mod = _load()
    assert mod.main(["--root", str(tmp_path)]) == 0
    assert "no BENCH_r" in capsys.readouterr().out


def test_serving_latency_sub_rows(tmp_path):
    """ISSUE 10 satellite: serving_latency expands into micro-batched
    actions/s + p50/p99 sub-rows; '-' before the metric existed, '?'
    for malformed sub-records, 'err' for failed subprocesses."""
    mod = _load()
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"host_pool_scaling": {"value": 3.0}},
    }) + "\n")
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "serving_latency": {
                "value": 6.6,
                "micro_batched": {
                    "actions_per_s": 445.6, "p50_ms": 66.2,
                    "p99_ms": 182.4,
                },
            },
        },
    }) + "\n")
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "serving_latency": {"value": 1.0, "micro_batched": "oops"},
        },
    }) + "\n")
    (tmp_path / "BENCH_r04.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"serving_latency": {"error": "rc=1"}},
    }) + "\n")
    # r05: carries the ISSUE 16 histogram-derived fields — one of them
    # malformed (a string where a number belongs).
    (tmp_path / "BENCH_r05.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "serving_latency": {
                "value": 5.1,
                "micro_batched": {
                    "actions_per_s": 400.0, "p50_ms": 70.0,
                    "p99_ms": 190.0, "slo_burn": 0.25,
                    "hist_p50_ms": 68.4, "hist_p99_ms": "garbage",
                },
            },
        },
    }) + "\n")
    rounds, rows = mod.trend_rows(str(tmp_path))
    assert rounds == [1, 2, 3, 4, 5]
    table = dict(rows)
    assert table["serving_latency"] == ["-", "6.6", "1", "err", "5.1"]
    assert table["serving_latency.actions_per_s"] == [
        "-", "445.6", "?", "err", "400",
    ]
    assert table["serving_latency.p50_ms"] == [
        "-", "66.2", "?", "err", "70",
    ]
    assert table["serving_latency.p99_ms"] == [
        "-", "182.4", "?", "err", "190",
    ]
    # ISSUE 16 sub-rows: rounds predating the fields render '?', the
    # malformed hist_p99_ms cell degrades to '?' instead of crashing.
    assert table["serving_latency.slo_burn"] == [
        "-", "?", "?", "err", "0.25",
    ]
    assert table["serving_latency.hist_p50_ms"] == [
        "-", "?", "?", "err", "68.4",
    ]
    assert table["serving_latency.hist_p99_ms"] == [
        "-", "?", "?", "err", "?",
    ]
    labels = [label for label, _ in rows]
    i = labels.index("serving_latency")
    assert labels[i + 1:i + 7] == [
        "serving_latency.actions_per_s",
        "serving_latency.p50_ms",
        "serving_latency.p99_ms",
        "serving_latency.slo_burn",
        "serving_latency.hist_p50_ms",
        "serving_latency.hist_p99_ms",
    ]


def test_scenario_fleet_sub_rows(tmp_path):
    """ISSUE 11 satellite: scenario_fleet expands into the mixture
    steps/s, one homogeneous-fleet sub-row per member type (union
    across rounds), and the instance-sweep peak; '-' before the mixture
    block existed (the PR 8 homogeneous-only record), '?' for malformed
    sub-records, 'err' for failed subprocesses."""
    mod = _load()
    # r01: the PR 8 record — scenario_fleet without a mixture block.
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"scenario_fleet": {"value": 280000.0}},
    }) + "\n")
    # r02: the full ISSUE 11 record.
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "scenario_fleet": {
                "value": 275000.0,
                "mixture": {
                    "steps_per_s": 61000.0,
                    "per_type_steps_per_s": {
                        "cartpole": 240000.0, "pendulum": 250000.0,
                        "acrobot": 90000.0, "maze": 120000.0,
                    },
                    "overhead_vs_series_x": 0.7,
                },
                "instance_sweep": {
                    "curve": {"256": 20000.0, "512": 40000.0},
                    "peak_instances": 512,
                    "peak_steps_per_s": 40000.0,
                },
            },
        },
    }) + "\n")
    # r03: malformed mixture/sweep blocks degrade to '?'.
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "scenario_fleet": {
                "value": 1.0, "mixture": "oops", "instance_sweep": 3,
            },
        },
    }) + "\n")
    # r04: the whole metric's subprocess failed.
    (tmp_path / "BENCH_r04.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"scenario_fleet": {"error": "rc=1"}},
    }) + "\n")
    rounds, rows = mod.trend_rows(str(tmp_path))
    assert rounds == [1, 2, 3, 4]
    table = dict(rows)
    assert table["scenario_fleet"] == ["2.8e+05", "2.75e+05", "1", "err"]
    assert table["scenario_fleet.mixture"] == ["-", "6.1e+04", "?", "err"]
    assert table["scenario_fleet.cartpole"] == ["-", "2.4e+05", "?", "err"]
    assert table["scenario_fleet.maze"] == ["-", "1.2e+05", "?", "err"]
    assert table["scenario_fleet.sweep_peak"] == ["-", "4e+04", "?", "err"]
    labels = [label for label, _ in rows]
    i = labels.index("scenario_fleet")
    assert labels[i + 1] == "scenario_fleet.mixture"
    assert "scenario_fleet.acrobot" in labels


def test_serving_fleet_scaling_sub_rows(tmp_path):
    """ISSUE 17 satellite: serving_fleet_scaling expands into per-
    replica-count actions/s + p99 sub-rows (union across rounds); '-'
    before the metric existed or a count was dropped, '?' for malformed
    sub-records, 'err' for failed subprocesses."""
    mod = _load()
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"host_pool_scaling": {"value": 3.0}},
    }) + "\n")
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "serving_fleet_scaling": {
                "value": 1.96,
                "points": [
                    {"replicas": 1, "actions_per_s": 610.0,
                     "p99_ms": 61.0},
                    {"replicas": 2, "actions_per_s": 1001.4,
                     "p99_ms": 55.3},
                    {"replicas": 3, "actions_per_s": 1195.2,
                     "p99_ms": 51.2},
                ],
            },
        },
    }) + "\n")
    # r03: points block malformed; r04: a point carries a non-numeric
    # field and a count (r2) is absent from the curve.
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "serving_fleet_scaling": {"value": 0.9, "points": "oops"},
        },
    }) + "\n")
    (tmp_path / "BENCH_r04.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "serving_fleet_scaling": {
                "value": 1.5,
                "points": [
                    {"replicas": 1, "actions_per_s": 600.0,
                     "p99_ms": 62.0},
                    {"replicas": 3, "actions_per_s": "garbage"},
                ],
            },
        },
    }) + "\n")
    (tmp_path / "BENCH_r05.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"serving_fleet_scaling": {"error": "rc=1"}},
    }) + "\n")
    rounds, rows = mod.trend_rows(str(tmp_path))
    assert rounds == [1, 2, 3, 4, 5]
    table = dict(rows)
    assert table["serving_fleet_scaling"] == [
        "-", "1.96", "0.9", "1.5", "err",
    ]
    assert table["serving_fleet_scaling.r1"] == [
        "-", "610", "?", "600", "err",
    ]
    assert table["serving_fleet_scaling.r2"] == [
        "-", "1001.4", "?", "-", "err",
    ]
    assert table["serving_fleet_scaling.r3"] == [
        "-", "1195.2", "?", "?", "err",
    ]
    # p99 of the r3 point is absent in r04 — malformed, not missing.
    assert table["serving_fleet_scaling.r3.p99_ms"] == [
        "-", "51.2", "?", "?", "err",
    ]
    labels = [label for label, _ in rows]
    i = labels.index("serving_fleet_scaling")
    assert labels[i + 1:i + 3] == [
        "serving_fleet_scaling.r1", "serving_fleet_scaling.r1.p99_ms",
    ]


def _write_data_plane_rounds(root: Path):
    """r01 without the metric, r02 a full data-plane A/B record, r03 a
    malformed one, r04 unparseable."""
    (root / "BENCH_r01.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"host_pool_scaling": {"value": 3.0}},
    }) + "\n")
    (root / "BENCH_r02.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "consumed_env_steps_per_s": {
                "value": 1210.6,
                "host": {"consumed_steps_per_s": 808.3},
                "device": {"consumed_steps_per_s": 1210.6},
                "per_block_transfer_bytes": {
                    "host_per_consumed_block": 7232,
                    "device_per_consumed_block": 0,
                    "device_enqueue_per_block": 2960,
                    "host_measured": 7232,
                    "enqueue_measured": "oops",
                },
            },
        },
    }) + "\n")
    (root / "BENCH_r03.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "consumed_env_steps_per_s": {
                "value": 0.5, "host": "oops", "device": {},
                "per_block_transfer_bytes": [],
            },
        },
    }) + "\n")
    (root / "BENCH_r04.json").write_text("{not json")


def test_data_plane_sub_rows(tmp_path):
    """ISSUE 13 satellite: the consumed_env_steps_per_s record expands
    into per-plane steps/s sub-rows plus the device enqueue bytes; '-'
    before the metric existed, '?' for malformed sub-records."""
    mod = _load()
    _write_data_plane_rounds(tmp_path)
    rounds, rows = mod.trend_rows(str(tmp_path))
    assert rounds == [1, 2, 3, 4]
    table = dict(rows)
    assert table["consumed_env_steps_per_s"] == ["-", "1210.6", "0.5", "?"]
    assert table["consumed_env_steps_per_s.host"] == ["-", "808.3", "?", "?"]
    assert table["consumed_env_steps_per_s.device"] == [
        "-", "1210.6", "?", "?",
    ]
    assert table["consumed_env_steps_per_s.enqueue_bytes"] == [
        "-", "2960", "?", "?",
    ]
    # ISSUE 15: the METERED actuals trend too — '-' before the fields
    # existed, '?' where a counter is malformed.
    assert table["consumed_env_steps_per_s.host_measured"] == [
        "-", "7232", "?", "?",
    ]
    assert table["consumed_env_steps_per_s.enqueue_measured"] == [
        "-", "?", "?", "?",
    ]
    labels = [label for label, _ in rows]
    main = labels.index("consumed_env_steps_per_s")
    assert labels[main + 1 : main + 6] == [
        "consumed_env_steps_per_s.host",
        "consumed_env_steps_per_s.device",
        "consumed_env_steps_per_s.enqueue_bytes",
        "consumed_env_steps_per_s.host_measured",
        "consumed_env_steps_per_s.enqueue_measured",
    ]


def test_pad_overhead_sub_rows(tmp_path):
    """ISSUE 20 satellite: pad_overhead expands into per-shape
    overhead_x sub-rows (Pallas ragged lanes + serving backfill sizes);
    '-' before the metric existed, '?' for malformed sub-records, 'err'
    for failed subprocesses."""
    mod = _load()
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"host_pool_scaling": {"value": 3.0}},
    }) + "\n")
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "pad_overhead": {
                "value": 1.31,
                "pallas": {
                    "E7": {"overhead_x": 1.02},
                    "E96": {"overhead_x": 1.05},
                    "E200": {"overhead_x": 1.31},
                },
                "serving": {
                    "n3": {"overhead_x": 1.11},
                    "n5": {"overhead_x": 1.08},
                },
            },
        },
    }) + "\n")
    # r03: present but malformed — the pallas group is a string, one
    # serving pair lost its overhead_x, the other pair isn't a dict.
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {
            "pad_overhead": {
                "value": 1.0,
                "pallas": "oops",
                "serving": {
                    "n3": {"padded_us": 9.0},
                    "n5": "oops",
                },
            },
        },
    }) + "\n")
    (tmp_path / "BENCH_r04.json").write_text(json.dumps({
        "metric": "a2c", "value": 1.0,
        "cpu_metrics": {"pad_overhead": {"error": "rc=1"}},
    }) + "\n")
    rounds, rows = mod.trend_rows(str(tmp_path))
    assert rounds == [1, 2, 3, 4]
    table = dict(rows)
    assert table["pad_overhead"] == ["-", "1.31", "1", "err"]
    assert table["pad_overhead.pallas_E7"] == ["-", "1.02", "?", "err"]
    assert table["pad_overhead.pallas_E96"] == ["-", "1.05", "?", "err"]
    assert table["pad_overhead.pallas_E200"] == [
        "-", "1.31", "?", "err",
    ]
    assert table["pad_overhead.serving_n3"] == ["-", "1.11", "?", "err"]
    assert table["pad_overhead.serving_n5"] == ["-", "1.08", "?", "err"]
    labels = [label for label, _ in rows]
    i = labels.index("pad_overhead")
    assert labels[i + 1:i + 6] == [
        "pad_overhead.pallas_E7",
        "pad_overhead.pallas_E96",
        "pad_overhead.pallas_E200",
        "pad_overhead.serving_n3",
        "pad_overhead.serving_n5",
    ]
