"""CPU tests of the benchmark's harness (tier-1; no chip, no speed).

They hold the yardstick itself: the manifest agrees with the files it names,
cells and layer readers are found by name from files alone, a run without a
TPU prints no result, the rehearsal walks a whole run, and the arithmetic
(rate from rows, percentiles, closed-loop counts, trace reduction, operation
counts, the references' bite) gives known answers.
"""

import gzip
import http.server
import json
import os
import shutil
import subprocess
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, harness, spans, trace_reduce  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _cpu_env():
    return {**os.environ, "JAX_PLATFORMS": "cpu", "TPU_LOG_DIR": "disabled"}


# -- the manifest and the files it names ----------------------------------

def test_manifest_keys_and_limits():
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    assert any(e["name"] == "setup_s" and e["bound"] <= 0.1
               for e in m["end_to_end"])
    assert all(0.01 <= e["bound"] <= 0.1 for e in m["end_to_end"])
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
    assert all(len(w["why"]) <= 200 for w in m["workloads"] + m["configs"])
    assert len(json.dumps(m)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in _manifest()["workloads"]])
def test_manifest_cell_agrees_with_its_files(cell):
    m = _manifest()
    entry = next(w for w in m["workloads"] if w["name"] == cell)
    w = harness.load_json("workloads", f"{cell}.json")
    assert (w["config"], w["traffic"], w["chips"]) == (
        entry["config"], entry["traffic"], entry["chips"])
    cfg = next(c for c in m["configs"] if c["name"] == w["config"])
    on_disk = harness.load_json("configs", f"{w['config']}.json")
    assert cfg["file"] == f"benchmark/configs/{w['config']}.json"
    assert (cfg["source"], cfg["reduced"]) == (on_disk["source"], on_disk["reduced"])
    harness.load_json("traffic", f"{w['traffic']}.json")
    applies = lambda e: cell in e.get("workloads", [cell])  # noqa: E731
    assert set(w["end_to_end"]) == {e["name"] for e in m["end_to_end"] if applies(e)}
    for e in m["end_to_end"]:
        if applies(e):
            assert w["units"][e["name"]] == e["unit"]
    # Which per-layer metric an accepted cell reads is said once, by the
    # manifest: every entry lists manifest cells, the cell's file has no list
    # of its own to keep equal, and each reader named is a file.
    cells = {x["name"] for x in m["workloads"]}
    assert "per_layer" not in w
    for e in m["per_layer"]:
        assert e["workloads"] and set(e["workloads"]) <= cells
        assert set(e["workloads"]) <= {
            x["name"] for x in m["workloads"]
            if e["moves"] in harness.load_json(
                "workloads", f"{x['name']}.json")["end_to_end"]}
    names = harness.per_layer_names(w)
    assert names == [e["name"] for e in m["per_layer"] if cell in e["workloads"]]
    assert names, "every cell reports at least one per-layer metric"
    for name in names:
        assert os.path.isfile(os.path.join(BENCH, "layers", f"{name}.py"))


@pytest.mark.parametrize(
    "reader", sorted(f[:-3] for f in os.listdir(os.path.join(BENCH, "layers"))
                     if f.endswith(".py")))
def test_layer_reader_declares_itself(reader):
    mod = harness.load_module("layers", reader)
    assert mod.SOURCE in ("device_trace", "program_span", "program_counter",
                          "host_clock")
    assert mod.LAYER and mod.UNIT and mod.MOVES and " " not in mod.UNIT
    for e in _manifest()["per_layer"]:
        if e["name"] == reader:
            assert (e["unit"], e["source"], e["layer"].lower()) == (
                mod.UNIT, mod.SOURCE, mod.LAYER.lower())
    # A reader that finds nothing to read returns nothing.
    empty = {"device": {"kind": "cpu", "count": 1}, "end_to_end": {},
             "window_iters": (0, 0)}
    ctx = harness.Ctx({"rate_metric": "x", "name": "t"}, {}, {}, 0, 1.0, True,
                      True, "")
    assert mod.read(empty, ctx) is None


@pytest.mark.parametrize(
    "cell", sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "workloads"))))
def test_every_workload_file_names_files_that_exist(cell):
    w = harness.load_json("workloads", f"{cell}.json")
    assert w["name"] == cell and len(w["why"]) <= 200
    cfg = harness.load_json("configs", f"{w['config']}.json")
    harness.load_json("traffic", f"{w['traffic']}.json")
    for kind, name in (("drivers", w["driver"]), ("reference", cfg["reference"]),
                       ("seams", cfg["seam"])):
        assert os.path.isfile(os.path.join(BENCH, kind, f"{name}.py"))
    for name in harness.per_layer_names(w):
        assert os.path.isfile(os.path.join(BENCH, "layers", f"{name}.py"))
    # Only a cell that no manifest entry can name lists its readers itself.
    listed = cell in {x["name"] for x in _manifest()["workloads"]}
    assert ("per_layer" in w) is (not listed)
    assert set(w["units"]) == set(w["end_to_end"])


# -- runs ------------------------------------------------------------------

def test_without_a_tpu_exits_nonzero_and_prints_no_result():
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "impala_pong.fleet",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_cpu_env(), capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no accelerator" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark")
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "impala_pong.fleet",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_cpu_env(), capture_output=True, text=True, timeout=60)
    assert r.returncode != 0 and "not here" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_rehearsal_of_a_cell_and_a_reader_added_as_files_only(tmp_path):
    """A fifth cell and a new per-layer reader are new files, nothing more:
    the harness finds both by name and the last line it would print has
    exactly the contract's keys."""
    cell = "impala_pong.added_by_test"
    reader = "rows_in_window_test"
    paths = [
        os.path.join(BENCH, "workloads", f"{cell}.json"),
        os.path.join(BENCH, "layers", f"{reader}.py"),
    ]
    w = harness.load_json("workloads", "impala_pong.fleet.json")
    assert "per_layer" not in w  # an accepted cell's file; the new cell lists its own
    w.update(name=cell, per_layer=["compiles_in_window", reader])
    try:
        with open(paths[0], "w") as fh:
            json.dump(w, fh)
        with open(paths[1], "w") as fh:
            fh.write('LAYER, UNIT, SOURCE, MOVES = "test", "count", '
                     '"program_counter", "none"\n\n\n'
                     'def read(run, ctx):\n    return len(run["rows"])\n')
        r = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
             "5", "--seconds", "1", "--trace", "1", "--rehearsal"],
            cwd=ROOT, env=_cpu_env(), capture_output=True, text=True, timeout=300)
    finally:
        for p in paths:
            if os.path.exists(p):
                os.remove(p)
        shutil.rmtree(os.path.join(ROOT, ".bench_scratch", cell), ignore_errors=True)
        pace = os.path.join(ROOT, ".bench_scratch", "pace", f"{cell}.rehearsal.json")
        if os.path.exists(pace):
            os.remove(pace)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("REHEARSAL")]
    assert lines, r.stdout[-2000:]
    # Every line of the harness is tagged, and no result line is printed.
    assert not any(ln.startswith("{\"correct") for ln in r.stdout.splitlines())
    would = json.loads(lines[-1].split("would print: ", 1)[1])
    assert set(would) == RESULT_KEYS
    assert would["correct"] is True and would["failed"] == 0
    assert set(would["compared"]) == {"adv_err", "loss_err", "narrow_ops",
                                      "compiles_in_window", "nonfinite_rows"}
    # `attempted` counts iterations, the reader rows: the first iteration,
    # every tenth, the last.
    rows = would["metrics"][reader]["value"]
    assert rows >= 3 and 10 * (rows - 3) < would["attempted"] <= 10 * (rows - 1)
    assert would["metrics"]["compiles_in_window"] == {"value": 0.0, "unit": "count"}
    assert set(would["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}


READER_FOR_AN_ACCEPTED_CELL = (
    'LAYER, UNIT, SOURCE, MOVES = "test", "count", "program_counter", '
    '"fused_steps_per_s"\n\n\n'
    'def read(run, ctx):\n'
    '    assert "trace_path" in run\n'
    '    return len(run["rows"])\n')


def _entry_for(reader: str, cells: list) -> dict:
    return {"name": reader, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "test",
            "moves": "fused_steps_per_s", "workloads": cells}


def _files_under(top: str) -> dict:
    out = {}
    for folder, _, names in os.walk(top):
        if "__pycache__" in folder:
            continue
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = fh.read()
    return out


def test_rehearsal_of_a_reader_added_to_an_accepted_cell_as_files_only(tmp_path):
    """A per-layer metric for a cell the manifest already lists = one new
    `benchmark/layers/<name>.py` + one new entry in `BENCHMARK.json`, and no
    edit to any file that is there (PR 24 was refused for two such edits).
    On a copy of the checkout (the benchmark copied, the program linked):
    the reader is written, the manifest gets one more entry that lists
    `impala_pong.fleet`, the cell is rehearsed with `--trace 1`, the metric
    is in the line it would print, and every file that was under
    `benchmark/` is byte for byte what it was."""
    reader = "rows_seen_by_a_later_pr"
    before = _files_under(BENCH)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("train.py", "scripts", "actor_critic_tpu"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    manifest = _manifest()
    manifest["per_layer"].append(_entry_for(reader, ["impala_pong.fleet"]))
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(manifest, fh)
    (tmp_path / "benchmark" / "layers" / f"{reader}.py").write_text(
        READER_FOR_AN_ACCEPTED_CELL)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "impala_pong.fleet",
         "--seed", "7", "--seconds", "1", "--trace", "1", "--rehearsal"],
        cwd=tmp_path, env=_cpu_env(), capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("REHEARSAL")]
    would = json.loads(lines[-1].split("would print: ", 1)[1])
    assert set(would) == RESULT_KEYS and would["correct"] is True
    assert would["metrics"][reader]["value"] >= 3
    assert would["metrics"][reader]["unit"] == "count"
    # The readers the cell had are still read, in the manifest's order, the
    # new one last (those that find nothing on the CPU are left out).
    accepted = [e["name"] for e in manifest["per_layer"]]
    assert [n for n in accepted if n in would["metrics"]] == list(would["metrics"])
    assert {"compiles_in_window", "cache_miss_count", "adv_kernel_calls",
            "enqueue_ms"} <= set(would["metrics"])
    after = _files_under(str(tmp_path / "benchmark"))
    assert set(after) - set(before) == {os.path.join("layers", f"{reader}.py")}
    assert {k: after[k] for k in before} == before
    assert _files_under(BENCH) == before


def test_per_layer_names_come_from_the_manifest_for_an_accepted_cell(monkeypatch):
    manifest = _manifest()
    names = [e["name"] for e in manifest["per_layer"]]
    fleet = harness.load_json("workloads", "impala_pong.fleet.json")
    half = harness.load_json("workloads", "impala_pong.fleet2048.json")
    assert harness.per_layer_names(fleet) == names == harness.per_layer_names(half)
    later = json.loads(json.dumps(manifest))
    later["per_layer"].append(_entry_for("later", ["impala_pong.fleet"]))
    # An entry without the key is read in every cell that reports what it moves.
    everywhere = _entry_for("everywhere", [])
    del everywhere["workloads"]
    later["per_layer"].append(everywhere)
    nowhere = {**everywhere, "name": "nowhere", "moves": "act_p99_ms"}
    later["per_layer"].append(nowhere)
    monkeypatch.setattr(harness, "load_manifest", lambda: later)
    assert harness.per_layer_names(fleet) == names + ["later", "everywhere"]
    assert harness.per_layer_names(half) == names + ["everywhere"]
    # A cell that is only a file keeps its own list, whatever the manifest says.
    held_out = harness.load_json("workloads", "impala_pong.preset.json")
    assert harness.per_layer_names(held_out) == held_out["per_layer"]
    assert "mfu_pct" in held_out["per_layer"]
    assert harness.per_layer_names({"name": "t", "per_layer": ["x"]}) == ["x"]


# -- arithmetic --------------------------------------------------------------

CANNED = [
    {"iter": 10, "wall_s": 2.5, "env_steps": 12800, "loss": 0.1},
    {"iter": 20, "wall_s": 2.6, "env_steps": 25600, "loss": 0.1},
    {"iter": 30, "wall_s": 2.7, "env_steps": 38400, "loss": None},
    {"iter": 40, "wall_s": 2.8, "env_steps": 51200, "loss": 0.2},
]


def test_rate_from_canned_rows():
    times = [100.0, 100.1, 100.2, 100.3]
    assert harness.steps_per_s(CANNED, times, 1280) == pytest.approx(128000.0)
    start = harness.window_start(times, 0.15)
    assert start == 2
    assert harness.steps_per_s(CANNED[start:], times[start:], 1280) == \
        pytest.approx(128000.0)
    # The program's own step count must agree with the cell's files.
    assert harness.steps_per_s(CANNED, times, 1000) is None
    assert harness.steps_per_s(CANNED[:1], times[:1], 1280) is None
    assert [harness.row_failed(r) for r in CANNED] == [False, False, True, False]
    pace = harness.pace_of(CANNED)
    assert pace == {"first_iter": 10, "iters_per_s": pytest.approx(100.0)}
    assert harness.iterations_for(pace, 1.0, 10) == 10 + 100
    assert harness.iterations_for(pace, 0.001, 10) == 10 + 10


def test_spans_inside_the_window():
    run = {"window_iters": (10, 30), "spans": [
        {"ph": "X", "name": "log", "ts": 90.0, "dur": 10.0, "args": {"it": 10}},
        {"ph": "X", "name": "update", "ts": 50.0, "dur": 7.0},
        {"ph": "X", "name": "update", "ts": 150.0, "dur": 3000.0},
        {"ph": "X", "name": "update", "ts": 3500.0, "dur": 1000.0},
        {"ph": "X", "name": "log", "ts": 5000.0, "dur": 100.0, "args": {"it": 30}},
        {"ph": "X", "name": "update", "ts": 6000.0, "dur": 5.0},
    ]}
    assert spans.window_us(run) == (100.0, 5100.0)
    assert spans.durations_ms(run, "update") == [3.0, 1.0]
    assert harness.median([3.0, 1.0]) == 2.0
    served = {"window_epoch": [1000.5, 1001.5], "spans": [
        {"name": "clock_sync", "ph": "M", "args": {"unix_epoch_at_ts0": 1000.0}},
        {"ph": "X", "name": "serve_dispatch", "ts": 600000.0, "dur": 400.0},
        {"ph": "X", "name": "serve_dispatch", "ts": 100.0, "dur": 900.0},
    ]}
    assert spans.durations_ms(served, "serve_dispatch") == [0.4]


def test_flops_of_the_nature_torso_against_a_hand_count():
    net = harness.load_json("configs", "impala_pong.json")["network"]
    # 84 -> 20 -> 9 -> 7 under VALID 8/4, 4/2, 3/1; multiply-accumulates:
    conv0 = 20 * 20 * 32 * (8 * 8 * 2)    # 1,638,400
    conv1 = 9 * 9 * 64 * (4 * 4 * 32)     # 2,654,208
    conv2 = 7 * 7 * 64 * (3 * 3 * 64)     # 1,806,336
    dense = 7 * 7 * 64 * 512              # 1,605,632
    heads = 512 * (3 + 1)
    assert (conv0, conv1, conv2, dense) == (1638400, 2654208, 1806336, 1605632)
    fwd = 2 * (conv0 + conv1 + conv2 + dense + heads)
    assert flops.forward_flops(net) == fwd == 15413248
    assert flops.flops_per_decision(net, 20) == pytest.approx(fwd * 4.05)
    mlp = harness.load_json("configs", "ppo_halfcheetah.json")["network"]
    assert flops.forward_flops(mlp) == 2 * (
        17 * 64 + 64 * 64 + 64 * 6 + 17 * 64 + 64 * 64 + 64 * 1)
    assert flops.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        flops.peak_flops("TPU v9")


def test_trace_reduce_on_a_hand_made_trace():
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_train_step(123)", 0, 100], ["jit_train_step(123)", 150, 100]]},
            {"name": "XLA Ops", "events": [
                ["while.1", 0, 90], ["fusion.2", 10, 30], ["conv.3", 50, 40],
                ["copy.4", 95, 5], ["while.1", 150, 100], ["fusion.2", 160, 50]]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench:scrape", 100, 45], ["PjitFunction(f)", 0, 10]]}]},
    ]}
    r = trace_reduce.reduce(trace)
    assert r["busy_s"] == pytest.approx(195e-9)
    # The window is the device events' own extent (0..250), no host clock.
    assert r["window_s"] == pytest.approx(250e-9)
    assert r["idle_pct"] == pytest.approx(22.0)
    assert r["modules"]["jit_train_step"]["count"] == 2
    assert r["modules"]["jit_train_step"]["total_s"] == pytest.approx(200e-9)
    ops = dict(r["top_ops"])
    # Self times: the while's own time excludes its nested body.
    assert ops["fusion.2"] == pytest.approx(80e-9)
    assert ops["while.1"] == pytest.approx(70e-9)
    assert ops["conv.3"] == pytest.approx(40e-9)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    gaps = r["idle_gaps"]
    assert gaps == [["bench:scrape", pytest.approx(50e-9)],
                    ["unattributed", pytest.approx(5e-9)]]
    # No device plane, or one on which nothing ran: nothing to report.
    assert trace_reduce.reduce({"planes": trace["planes"][1:]}) is None


STACK = "jit(train_step)/while/body/closed_call/ActorCriticDiscrete/torso/conv_0/conv_general_dilated:"


@pytest.mark.parametrize("stack, scope", [
    (STACK, "while/~/conv_0/conv_general_dilated"),
    ("jit(train_step)/jvp(ActorCriticDiscrete)/torso/div:",
     "jvp(ActorCriticDiscrete)/torso/div"),
    ("jit(train_step)/transpose(jvp(ActorCriticDiscrete))/torso/conv_0/conv_general_dilated:",
     "transpose(jvp(ActorCriticDiscrete))/~/conv_0/con"),
    ("jit(train_step)/jit(main)/pjit/rollout/while/body/add", "rollout/~/body/add"),
    ("jit(train_step)/jvp()/pallas_call:", "jvp()/pallas_call"),
    ("jit(train_step)/while:", "while"),
    ("jit(train_step)", None),
    ("", None),
])
def test_scope_of_a_name_stack(stack, scope):
    event = ["%fusion.1 = f32[8] fusion(f32[8] %p)", 0.0, 5.0,
             {trace_reduce.NAME_STACK_STAT: stack}]
    assert trace_reduce.scope_of(event) == scope
    assert scope is None or len(scope) <= 48


def test_scope_of_an_event_without_stats_and_the_names_of_top_ops():
    assert trace_reduce.scope_of(["%copy.4 = f32[8] copy(f32[8] %p)", 0.0, 5.0]) is None
    assert trace_reduce.scope_of(["x", 0.0, 5.0, {"hlo_category": "copy"}]) is None
    text = "%fusion.389 = bf16[4096,20,20,32]{3,2,1,0:T(8,128)} fusion(bf16[8,8,2,32]{3,2,1,0} %p), kind=kOutput"
    assert trace_reduce.short_name(text) == \
        "%fusion.389 = bf16[4096,20,20,32] fusion(bf16[8,8,2,32] %p), kind=kOutput"
    assert trace_reduce.short_name(text, "while/~/conv_0/conv_general_dilated") == (
        "while/~/conv_0/conv_general_dilated|fusion.389 bf16[4096,20,20,32] "
        "fusion(bf16[8,8,2,32] %p), kind=kOutput")
    assert trace_reduce.short_name("no-equals-sign", "while") == "while|no-equals-sign"
    # In a reduction: the scope leads where the event has a stack, the name
    # stays where it has none; self times, order and count do not change.
    stat = {trace_reduce.NAME_STACK_STAT: STACK}
    ops = [["%while.1 = () while()", 0, 90], ["%fusion.2 = f32[8] fusion()", 10, 30, stat],
           ["%conv.3 = f32[8] convolution()", 50, 40], ["%fusion.2 = f32[8] fusion()", 100, 50, stat]]
    with_stats = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops}]}]}
    without = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [e[:3] for e in ops]}]}]}
    got, plain = trace_reduce.reduce(with_stats), trace_reduce.reduce(without)
    assert [n for n, _ in got["top_ops"]] == [
        "while/~/conv_0/conv_general_dilated|fusion.2 f32[8] fusion()",
        "%conv.3 = f32[8] convolution()", "%while.1 = () while()"]
    assert [n for n, _ in plain["top_ops"]] == [
        "%fusion.2 = f32[8] fusion()", "%conv.3 = f32[8] convolution()",
        "%while.1 = () while()"]
    assert [v for _, v in got["top_ops"]] == [v for _, v in plain["top_ops"]]
    assert {k: v for k, v in got.items() if k != "top_ops"} == \
        {k: v for k, v in plain.items() if k != "top_ops"}


@pytest.mark.parametrize("host, label", [
    ([["ac:log", 100, 45]], "ac:log"),
    ([["bench:scrape", 90, 60, {"a": 1}]], "bench:scrape"),
    ([["np.asarray(jax.Array)", 100, 45]], "unattributed;host=np.asarray(jax.Array)"),
    ([["np.asarray(jax.Array)", 100, 50], ["ac:update", 120, 25]], "ac:update"),
    ([["ac:log", 100, 20]], "unattributed"),
    ([["acme:log", 100, 45]], "unattributed;host=acme:log"),
    ([], "unattributed"),
])
def test_label_gap_attributes_to_the_programs_spans(host, label):
    """A gap of 50 ns from 100 to 150: the harness's own `bench:` and the
    program's `ac:<span>` annotations attribute it where one covers half of
    it; a foreign host event is only ever a hint."""
    assert trace_reduce.label_gap((100.0, 150.0), host) == label


def test_load_xplane_joins_event_metadata_stats(tmp_path):
    """A profiler trace taken here on the CPU, read back three ways: without
    stats an event has three elements; with `stats=` it carries those it
    has; and the wire reader finds the stats of the planes' event metadata
    (where the v5e keeps `tf_op`; the CPU backend writes none, so here only
    the reader's walk over a real file is held, not a name stack)."""
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("ac:log"):
            jax.jit(lambda x: jnp.tanh(x @ x).sum())(
                jnp.ones((64, 64))).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    assert path is not None
    bare = trace_reduce.load_xplane(path)
    events = [e for p in bare["planes"] for l in p["lines"] for e in l["events"]]
    assert events and all(len(e) == 3 for e in events)
    assert any(e[0] == "ac:log" for e in trace_reduce.host_events(bare))
    full = trace_reduce.load_xplane(path, stats=("hlo_module", "no_such_stat"))
    tagged = [e for p in full["planes"] for l in p["lines"] for e in l["events"]
              if len(e) > 3]
    assert tagged and all(set(e[3]) == {"hlo_module"} for e in tagged)
    assert any(e[3]["hlo_module"].startswith("jit_") for e in tagged)
    assert any("stats of the first" in line for line in trace_reduce.describe(
        trace_reduce.load_xplane(path, stats=("*",))))
    by_plane = trace_reduce.metadata_stats(path, ("*",))
    assert isinstance(by_plane, dict)
    for of_name in by_plane.values():
        assert all(isinstance(k, str) and isinstance(v, dict) and v
                   for k, v in of_name.items())
    assert trace_reduce.metadata_stats(path, ()) == {}


def _pb(*fields) -> bytes:
    """A protobuf message from (field number, int | bytes | str) pairs."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    msg = b""
    for number, value in fields:
        if isinstance(value, int):
            msg += varint(number << 3) + varint(value)
        else:
            raw = value.encode() if isinstance(value, str) else value
            msg += varint(number << 3 | 2) + varint(len(raw)) + raw
    return msg


def test_name_stack_from_a_hand_made_xplane_file(tmp_path):
    """An `.xplane.pb` written field by field as the v5e's profiler lays it
    out: the name stack is a stat (`tf_op`) of the event's METADATA entry,
    not of the event. `load_xplane(stats=)` joins it to the events, a
    referenced value (`ref_value`) resolves to its string, and `top_ops`
    leads with the scope."""
    stat_names = {1: "tf_op", 2: "hlo_category", 3: "flops", 4: "convolution"}
    conv = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput"
    metadata = {
        1: _pb((1, 1), (2, conv),
               (5, _pb((1, 1), (5, STACK))),        # tf_op, a string
               (5, _pb((1, 2), (7, 4))),            # hlo_category, a reference
               (5, _pb((1, 3), (3, 1234)))),        # flops, an integer
        2: _pb((1, 2), (2, "%copy.3 = f32[8]{0} copy(f32[8]{0} %p)")),
    }
    line = _pb((2, "XLA Ops"), (3, 0),
               (4, _pb((1, 1), (2, 0), (3, 30_000))),
               (4, _pb((1, 2), (2, 40_000), (3, 10_000))))
    plane = _pb(
        (1, 0), (2, "/device:TPU:0"), (3, line),
        *[(4, _pb((1, k), (2, v))) for k, v in metadata.items()],
        *[(5, _pb((1, k), (2, _pb((1, k), (2, v))))) for k, v in stat_names.items()])
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_pb((1, plane)))
    assert trace_reduce.metadata_stats(str(path), ("*",)) == {"/device:TPU:0": {
        conv: {"tf_op": STACK, "hlo_category": "convolution", "flops": 1234}}}
    trace = trace_reduce.load_xplane(
        str(path), keep_lines=(trace_reduce.OPS_LINE,),
        stats=(trace_reduce.NAME_STACK_STAT,))
    events = trace["planes"][0]["lines"][0]["events"]
    assert events == [[conv, 0.0, 30.0, {"tf_op": STACK}],
                      ["%copy.3 = f32[8]{0} copy(f32[8]{0} %p)", 40.0, 10.0]]
    got = trace_reduce.reduce(trace)
    assert got["top_ops"] == [
        ["while/~/conv_0/conv_general_dilated|fusion.2 f32[8] fusion(f32[8] %p), "
         "kind=kOutput", pytest.approx(30e-9)],
        ["%copy.3 = f32[8] copy(f32[8] %p)", pytest.approx(10e-9)]]
    assert got["busy_s"] == pytest.approx(40e-9)
    assert got["window_s"] == pytest.approx(50e-9)


def test_trace_reduce_on_the_recorded_chip_trace():
    """The small trace recorded on the v5e (benchmark/trace_fixture.json.gz,
    cut from impala_pong.fleet's traced run): the reduction gives the numbers
    worked out by hand in trace_fixture.expected.json."""
    with gzip.open(os.path.join(BENCH, "trace_fixture.json.gz"), "rt") as fh:
        trace = json.load(fh)
    with open(os.path.join(BENCH, "trace_fixture.expected.json")) as fh:
        want = json.load(fh)
    got = trace_reduce.reduce(trace)
    assert got["device_planes"] == want["device_planes"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["idle_pct"] == pytest.approx(want["idle_pct"], rel=1e-6)
    for name, module in want["modules"].items():
        assert got["modules"][name]["count"] == module["count"]
        assert got["modules"][name]["total_s"] == pytest.approx(module["total_s"])
    # Names: the scope first where the chip wrote a name stack for the
    # operation, the HLO text as it was where it wrote none (%while.10).
    assert [n[:100] for n, _ in got["top_ops"][:3]] == want["top_ops_names"]
    assert got["top_ops"][0][0].startswith("%while.10 = ")
    assert sum("|" in n.split(" ")[0] for n, _ in got["top_ops"]) == 9
    # The name stacks and the `ac:log` annotation moved no number.
    assert [v for _, v in got["top_ops"]] == pytest.approx(
        want["top_ops_self_s"], rel=1e-9)
    assert [label for label, _ in got["idle_gaps"]] == want["idle_gap_labels"]
    assert got["idle_gaps"][0] == ["ac:log", pytest.approx(1.1651e-05)]
    # Busy is a union: never more than the window, never more than the sum.
    ops = next(l for p in trace["planes"] if trace_reduce.DEVICE_PLANE.match(p["name"])
               for l in p["lines"] if l["name"] == trace_reduce.OPS_LINE)
    assert got["busy_s"] <= sum(e[2] for e in ops["events"]) / 1e9
    assert 0.0 <= got["idle_pct"] < 100.0


# -- the load generator against a stub server ----------------------------------

class _Stub(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    fail_every = 0
    seen = 0

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        type(self).seen += 1
        bad = self.fail_every and type(self).seen % self.fail_every == 0
        out = json.dumps({"actions": [[0.0]] * len(body["obs"])}).encode()
        self.send_response(503 if bad else 200)
        self.send_header("Content-Length", str(len(out)))
        self.end_headers()
        self.wfile.write(out)

    def log_message(self, *args):
        pass


def _loadgen():
    return harness.load_module("traffic", "loadgen")


def test_loadgen_percentile():
    lg = _loadgen()
    vals = sorted(float(i) for i in range(1, 101))
    assert lg.percentile(vals, 50) == 50.0
    assert lg.percentile(vals, 99) == 99.0
    assert lg.percentile(vals, 100) == 100.0
    assert lg.percentile([7.0], 99) == 7.0
    assert lg.percentile([], 99) == 0.0


@pytest.mark.parametrize("fail_every", [0, 5])
def test_loadgen_closed_loop_counts(fail_every):
    lg = _loadgen()
    handler = type("H", (_Stub,), {"fail_every": fail_every, "seen": 0})
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        traffic = {"clients": [{"count": 2, "rows": 1}, {"count": 1, "rows": 5}],
                   "bodies_per_client": 4, "timeout_s": 5.0}
        out = lg.run(f"http://127.0.0.1:{server.server_address[1]}", traffic,
                     seed=3, seconds=0.6, obs_dim=3)
    finally:
        server.shutdown()
        server.server_close()
    assert out["clients"] == 3 and out["mode"] == "closed"
    assert out["attempted"] == out["ok_requests"] + out["failed"]
    assert out["ok_requests"] > 10
    assert out["act_per_s"] == pytest.approx(out["ok_rows"] / 0.6)
    assert out["ok_requests"] <= out["ok_rows"] <= 5 * out["ok_requests"]
    assert 0.0 < out["p50_ms"] <= out["p99_ms"] <= out["max_ms"]
    assert (out["failed"] > 0) == bool(fail_every)
    # The same seed draws the same bodies.
    import random
    assert lg.make_bodies(random.Random(1), 2, 3, 2) == \
        lg.make_bodies(random.Random(1), 2, 3, 2)


# -- the references bite -------------------------------------------------------

def _impala_case():
    import jax
    import jax.numpy as jnp

    ref = harness.load_module("reference", "impala_pong")
    T, E = 6, 5
    rng = np.random.default_rng(0)
    done = (rng.random((T, E)) < 0.3).astype(np.float32)
    args = dict(
        target_lp=jnp.asarray(rng.normal(-1.0, 0.3, (T, E)), jnp.float32),
        behaviour_lp=jnp.asarray(rng.normal(-1.0, 0.3, (T, E)), jnp.float32),
        rewards=jnp.asarray(rng.normal(0, 1, (T, E)), jnp.float32),
        values=jnp.asarray(rng.normal(0, 1, (T, E)), jnp.float32),
        dones=jnp.asarray(done),
        bootstrap=jnp.asarray(rng.normal(0, 1, (E,)), jnp.float32),
    )
    return ref, args, jax


def test_impala_reference_agrees_with_the_program_and_bites():
    """The reverse-loop V-trace equals the program's recursion on seeded
    inputs; dropping the `dones` mask, or rounding the inputs to bf16 as a
    bf16 update would, lands far outside the CPU tolerance."""
    import jax.numpy as jnp

    from actor_critic_tpu.ops import returns

    ref, a, _ = _impala_case()
    tol = harness.load_json("configs", "impala_pong.json")["tolerance"]["cpu"]
    vs, pg = ref.vtrace(a["target_lp"], a["behaviour_lp"], a["rewards"],
                        a["values"], a["dones"], a["bootstrap"],
                        0.99, 1.0, 1.0, 1.0)
    prog = returns.vtrace(a["target_lp"], a["behaviour_lp"], a["rewards"],
                          a["values"], a["dones"], a["bootstrap"], 0.99)
    want = {"pg_advantages": pg, "value_targets": vs, "loss": jnp.ones(())}
    good = {"pg_advantages": prog.pg_advantages, "value_targets": prog.vs,
            "loss": jnp.ones(())}
    assert harness.compare(good, want, tol)["ok"]
    vs2, pg2 = ref.vtrace(a["target_lp"], a["behaviour_lp"], a["rewards"],
                          a["values"], a["dones"], a["bootstrap"],
                          0.99, 1.0, 1.0, 1.0, use_dones=False)
    no_mask = harness.compare(
        good, {"pg_advantages": pg2, "value_targets": vs2, "loss": jnp.ones(())}, tol)
    assert not no_mask["ok"] and no_mask["adv_err"] > 0.05
    b = {k: v.astype(jnp.bfloat16).astype(jnp.float32) for k, v in a.items()}
    low = returns.vtrace(b["target_lp"], b["behaviour_lp"], b["rewards"],
                         b["values"], b["dones"], b["bootstrap"], 0.99)
    bf16 = harness.compare(
        {"pg_advantages": low.pg_advantages, "value_targets": low.vs,
         "loss": jnp.ones(())}, want, tol)
    assert not bf16["ok"] and bf16["adv_err"] > 10 * tol["adv_tol"]
    assert not harness.compare({**good, "loss": jnp.asarray(1.01)}, want, tol)["ok"]
    assert not harness.compare({**good, "loss": jnp.asarray(np.nan)}, want, tol)["ok"]


def test_ppo_reference_gae_agrees_with_the_program_and_bites():
    import jax.numpy as jnp

    from actor_critic_tpu.ops import returns

    _, a, _ = _impala_case()
    ref = harness.load_module("reference", "ppo_halfcheetah")
    tol = harness.load_json("configs", "ppo_halfcheetah.json")["tolerance"]["cpu"]
    adv, ret = ref.gae(a["rewards"], a["values"], a["dones"], a["bootstrap"],
                       0.99, 0.95)
    p_adv, p_ret = returns.gae(a["rewards"], a["values"], a["dones"],
                               a["bootstrap"], 0.99, 0.95)
    want = {"pg_advantages": adv, "value_targets": ret, "loss": jnp.ones(())}
    good = {"pg_advantages": p_adv, "value_targets": p_ret, "loss": jnp.ones(())}
    assert harness.compare(good, want, tol)["ok"]
    adv2, ret2 = ref.gae(a["rewards"], a["values"], a["dones"], a["bootstrap"],
                         0.99, 0.95, use_dones=False)
    assert not harness.compare(
        good, {"pg_advantages": adv2, "value_targets": ret2,
               "loss": jnp.ones(())}, tol)["ok"]


def test_ppo_reference_forward_is_the_served_action():
    import jax

    from actor_critic_tpu import config as config_mod
    from actor_critic_tpu import serving
    from actor_critic_tpu.envs.jax_env import EnvSpec

    ref = harness.load_module("reference", "ppo_halfcheetah")
    preset = config_mod.resolve("ppo_halfcheetah", None, None, {})
    spec = EnvSpec(obs_shape=(17,), action_dim=6, discrete=False)
    params = serving.init_params(spec, preset.config, "ppo", seed=4)
    obs = np.random.default_rng(4).standard_normal((9, 17)).astype(np.float32)
    served = jax.jit(serving.make_act_program(spec, preset.config, "ppo"))(params, obs)
    np.testing.assert_allclose(
        np.asarray(ref.greedy_action(params, obs)), np.asarray(served),
        rtol=1e-5, atol=1e-7)
