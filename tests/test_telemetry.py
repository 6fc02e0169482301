"""Unified run telemetry (actor_critic_tpu/telemetry/, ISSUE 1).

Four contracts:
- the span tracer emits VALID Chrome-trace events whose phase spans nest
  inside their iteration span, from a real 3-iteration host-loop run;
- the resource sampler writes monotone-timestamp rows;
- the health monitors fire on synthetic regressions/divergence and stay
  quiet on clean runs;
- the stall watchdog's exit-42 diagnosis names the open span (and, with
  a session installed, writes a durable `stall` event first).

Plus `scripts/run_report.py` rendering the three sinks into markdown
with a per-phase breakdown — the acceptance-criteria path.
"""

import importlib.util
import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

from actor_critic_tpu import telemetry
from actor_critic_tpu.telemetry.health import (
    DivergenceMonitor,
    ThroughputMonitor,
)
from actor_critic_tpu.telemetry.sampler import ResourceSampler, sample_row

_spec = importlib.util.spec_from_file_location(
    "run_report", Path(__file__).parent.parent / "scripts" / "run_report.py"
)
run_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(run_report)


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------- spans


def test_spans_from_host_loop_are_valid_nested_chrome_trace(tmp_path):
    """A 3-iteration PPO host run under an installed session must leave a
    spans.jsonl whose every line is a Chrome Trace Event Format entry and
    whose phase spans (env_step / host_to_device / update / log) sit
    inside an iteration span by ts/dur containment — the property
    Perfetto uses to render nesting."""
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.envs.host_pool import HostEnvPool

    cfg = ppo.PPOConfig(
        num_envs=2, rollout_steps=8, epochs=1, num_minibatches=1, hidden=(16,)
    )
    pool = HostEnvPool("CartPole-v1", num_envs=2, seed=0)
    with telemetry.TelemetrySession(tmp_path, sample_resources=False):
        ppo.train_host(pool, cfg, num_iterations=3, seed=0, log_every=1)
    pool.close()

    events = _read_jsonl(tmp_path / "spans.jsonl")
    assert events, "no span events written"
    for e in events:
        assert e["ph"] in ("M", "X", "i"), e
        assert "name" in e and "pid" in e and "tid" in e, e
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0, e
    # The line-per-event file wraps into the standard trace container.
    json.loads(json.dumps({"traceEvents": events}))

    complete = [e for e in events if e["ph"] == "X"]
    iters = [e for e in complete if e["name"] == "iteration"]
    assert len(iters) == 3, [e["name"] for e in complete]
    for phase in ("env_step", "host_to_device", "update", "log"):
        kids = [e for e in complete if e["name"] == phase]
        assert len(kids) == 3, (phase, [e["name"] for e in complete])
        for kid in kids:  # containment in SOME iteration span (±rounding)
            assert any(
                parent["ts"] - 1 <= kid["ts"]
                and kid["ts"] + kid["dur"] <= parent["ts"] + parent["dur"] + 1
                for parent in iters
            ), (phase, kid, iters)

    report = run_report.render(str(tmp_path))
    assert "| update |" in report and "| env_step |" in report, report
    run_report.write_trace(events, str(tmp_path / "trace.json"))
    assert json.load(open(tmp_path / "trace.json"))["traceEvents"]


def test_run_report_perf_budget_table():
    """ISSUE 15 satellite: with the committed perf_budgets.json present
    the report renders the budget table for every steady-state program
    (its committed max_* values), with actuals joined only when a
    perfsan_actuals.json report sits next to the manifest."""
    lines = run_report.perf_budget_table()
    assert lines, "committed manifest must render a table"
    body = "\n".join(lines)
    for program in (
        "ppo_update_host", "ppo_update_device", "offpolicy_ingest",
        "serving_dispatch", "mixture_fleet_step",
    ):
        assert f"`{program}`" in body
    # the device plane's metered contract is visible in the table
    assert "| `ppo_update_device` | 1 | 1 | 4 | 0 |" in body


def test_span_stack_tracked_without_session():
    """Spans must maintain the open-span stack with NO session installed
    (the watchdog reads it in runs launched without --telemetry-dir)."""
    assert telemetry.current() is None
    assert telemetry.open_spans() == []
    with telemetry.span("update", it=1):
        with telemetry.span("inner"):
            assert telemetry.open_spans() == ["update", "inner"]
            name, open_s = telemetry.last_open_span()
            assert name == "inner" and open_s >= 0
    assert telemetry.open_spans() == []
    telemetry.instant("env_step")  # no-op, must not raise
    telemetry.observe(1, {"loss": 0.0})


def test_span_stacks_are_per_thread():
    """Actor-service threads (algos/traj_queue.py, ISSUE 6) open spans
    concurrently with the learner: each thread gets its OWN stack (no
    stranded entries from interleaved pops), `open_spans` reports the
    calling thread only, and `last_open_span` — the watchdog's view —
    sees the most recently entered phase across all threads."""
    import threading
    import time as _time

    entered = threading.Event()
    release = threading.Event()
    seen_in_thread: list = []

    def worker():
        with telemetry.span("env_step", steps=1):
            seen_in_thread.append(telemetry.open_spans())
            entered.set()
            release.wait(5.0)

    t = threading.Thread(target=worker, daemon=True)
    with telemetry.span("update"):
        t.start()
        assert entered.wait(5.0)
        _time.sleep(0.01)
        assert telemetry.open_spans() == ["update"]  # this thread only
        assert seen_in_thread == [["env_step"]]
        # Cross-thread innermost: the worker's span opened later.
        assert telemetry.last_open_span()[0] == "env_step"
        release.set()
        t.join(5.0)
        assert telemetry.open_spans() == ["update"]
    assert telemetry.open_spans() == []
    assert telemetry.last_open_span() is None  # worker stack reclaimed


# -------------------------------------------------------------- sampler


def test_sampler_rows_are_monotone(tmp_path):
    path = tmp_path / "resources.jsonl"
    with open(path, "a", buffering=1) as fh:
        s = ResourceSampler(fh, interval_s=0.02).start()
        time.sleep(0.12)
        s.stop()
    rows = _read_jsonl(path)
    assert len(rows) >= 3  # start sample + >=1 tick + stop sample
    ts = [r["ts"] for r in rows]
    assert ts == sorted(ts)
    rec = [r["recompiles"] for r in rows]
    assert rec == sorted(rec) and all(isinstance(c, int) for c in rec)
    assert all(r["rss_bytes"] > 0 for r in rows if "rss_bytes" in r)


def test_session_plumbs_sampler_cadence(tmp_path):
    """`train.py --telemetry-sample-s` overrides the 5 s default via
    TelemetrySession(resource_interval_s=...)."""
    with telemetry.TelemetrySession(
        tmp_path, resource_interval_s=0.02
    ) as s:
        assert s.sampler is not None and s.sampler._interval == 0.02
        time.sleep(0.1)
    rows = _read_jsonl(tmp_path / "resources.jsonl")
    assert len(rows) >= 3  # the faster cadence actually ticked


def test_sample_row_shape():
    row = sample_row()
    assert set(row) >= {"ts", "recompiles"}
    for d in row.get("devices", []):
        assert "id" in d and "platform" in d
        # absent allocator stats must be ABSENT, never fake zeros
        assert d.get("live_bytes") != 0 or "live_bytes" not in d or d["live_bytes"] >= 0


# --------------------------------------------------------------- health


def test_throughput_monitor_confirms_fires_once_and_rearms():
    fired = []
    m = ThroughputMonitor(
        lambda kind, **f: fired.append((kind, f)),
        drop_threshold=0.5, warmup_observations=2,
    )
    t = 0.0
    for it in range(1, 8):  # steady 1 iter/s: quiet
        t += 1.0
        m.observe(it, {}, t)
    assert fired == []
    t += 10.0  # 0.1 iter/s — 90% below the ~1 EMA, but UNCONFIRMED
    m.observe(8, {}, t)
    assert fired == []
    t += 10.0  # second consecutive sub-floor window: fires once
    m.observe(9, {}, t)
    assert [k for k, _ in fired] == ["throughput_regression"]
    assert fired[0][1]["iters_per_s"] < fired[0][1]["ema_iters_per_s"]
    t += 10.0  # still slow: ALREADY tripped, no second event
    m.observe(10, {}, t)
    assert len(fired) == 1
    for it in range(11, 40):  # recovery re-arms...
        t += 1.0
        m.observe(it, {}, t)
    t += 30.0  # ...so a second CONFIRMED regression fires again
    m.observe(40, {}, t)
    t += 30.0
    m.observe(41, {}, t)
    assert [k for k, _ in fired] == ["throughput_regression"] * 2


def test_throughput_monitor_quiet_on_checkpoint_blips():
    """A healthy run's periodic one-window stalls (a checkpoint save or
    eval inside the observation interval inflates dt) must NOT fire —
    the confirm_observations=2 default makes isolated blips invisible."""
    fired = []
    m = ThroughputMonitor(
        lambda kind, **f: fired.append(kind),
        drop_threshold=0.5, warmup_observations=2,
    )
    t = 0.0
    for it in range(1, 30):
        t += 5.0 if it % 7 == 0 else 1.0  # save blip every 7th window
        m.observe(it, {}, t)
    assert fired == []


def test_throughput_monitor_threshold_boundary():
    """drop_threshold=0.5 means the floor is half the EMA: a sustained
    rate just ABOVE the floor must stay quiet, just BELOW must fire —
    the trigger/no-trigger edge the flag documents. ema_alpha=0 freezes
    the EMA at the baseline rate so the floor is exactly 0.5 iter/s
    (with the default alpha the EMA tracks a mild slowdown down and a
    45% rate stops counting as regressed — adaptive by design)."""
    for rate_frac, should_fire in ((0.55, False), (0.45, True)):
        fired = []
        m = ThroughputMonitor(
            lambda kind, **f: fired.append(kind),
            drop_threshold=0.5, warmup_observations=2, ema_alpha=0.0,
        )
        t = 0.0
        for it in range(1, 10):  # steady 1 iter/s baseline
            t += 1.0
            m.observe(it, {}, t)
        for it in range(10, 16):  # sustained slowdown at rate_frac
            t += 1.0 / rate_frac
            m.observe(it, {}, t)
        assert bool(fired) == should_fire, (rate_frac, fired)


def test_divergence_monitor_collapse_boundary():
    """collapse_frac=0.1 of best=100: 11 (above the line) must stay
    quiet, 9 (below) must fire."""
    for value, should_fire in ((11.0, False), (9.0, True)):
        fired = []
        m = DivergenceMonitor(
            lambda kind, **f: fired.append(kind), collapse_frac=0.1
        )
        m.observe(0, {"avg_return_ema": 100.0})
        m.observe(1, {"avg_return_ema": value})
        assert bool(fired) == should_fire, (value, fired)


def test_divergence_monitor_nonfinite_loss():
    fired = []
    m = DivergenceMonitor(lambda kind, **f: fired.append((kind, f)))
    for it in range(5):
        m.observe(it, {"loss": 0.5, "critic_loss": 0.1})
    assert fired == []
    m.observe(5, {"loss": float("nan")})
    m.observe(6, {"loss": math.inf})  # one event covers the run
    assert len(fired) == 1
    kind, f = fired[0]
    assert kind == "divergence" and f["reason"] == "non_finite_loss"


def test_divergence_monitor_return_collapse():
    fired = []
    m = DivergenceMonitor(
        lambda kind, **f: fired.append((kind, f)), collapse_frac=0.1
    )
    for it, r in enumerate([10.0, 120.0, 200.0, 190.0, 150.0]):
        m.observe(it, {"avg_return_ema": r})  # healthy wobble: quiet
    assert fired == []
    m.observe(5, {"avg_return_ema": 5.0})  # < 10% of best 200: collapse
    assert [k for k, _ in fired] == ["divergence"]
    assert fired[0][1]["reason"] == "return_collapse"
    m.observe(6, {"avg_return_ema": 4.0})  # still collapsed: no repeat
    assert len(fired) == 1


def test_divergence_monitor_quiet_below_progress_floor():
    """A run still at its random-policy floor has nothing to collapse
    from — near-zero watermarks must not trip the fraction test."""
    fired = []
    m = DivergenceMonitor(lambda kind, **f: fired.append(kind), min_progress=1.0)
    m.observe(0, {"avg_return_ema": 0.4})
    m.observe(1, {"avg_return_ema": 0.01})
    assert fired == []


def test_session_routes_observe_to_events(tmp_path):
    with telemetry.TelemetrySession(
        tmp_path, sample_resources=False
    ) as sess:
        sess.observe(1, {"loss": 1.0})
        sess.observe(2, {"loss": float("nan")})
    kinds = [r["kind"] for r in _read_jsonl(tmp_path / "events.jsonl")]
    assert kinds == ["session_start", "divergence", "session_end"]


# ------------------------------------------------------------- watchdog


def test_stall_report_names_open_span(tmp_path):
    with telemetry.TelemetrySession(tmp_path, sample_resources=False):
        with telemetry.span("update", it=7):
            msg = telemetry.stall_report(12.3)
    assert "update" in msg and "12.3" not in msg  # phase named, not the raw s
    rows = _read_jsonl(tmp_path / "events.jsonl")
    stall = [r for r in rows if r["kind"] == "stall"]
    assert len(stall) == 1
    assert stall[0]["phase"] == "update" and stall[0]["stalled_s"] == 12.3
    assert telemetry.stall_report() == ""  # no open span → empty clause


def test_stall_report_names_deepest_open_span(tmp_path):
    """Under nesting the diagnosis must name the INNERMOST open span —
    the phase actually executing when progress stopped — not the
    enclosing iteration."""
    with telemetry.TelemetrySession(tmp_path, sample_resources=False):
        with telemetry.span("iteration", it=3):
            with telemetry.span("env_step", steps=64):
                msg = telemetry.stall_report(7.0)
    assert "'env_step'" in msg and "'iteration'" not in msg, msg
    stall = [
        r for r in _read_jsonl(tmp_path / "events.jsonl")
        if r["kind"] == "stall"
    ]
    assert len(stall) == 1 and stall[0]["phase"] == "env_step"


def test_health_events_are_fsynced(tmp_path, monkeypatch):
    """A health event() must flush+fsync the sinks (SIGKILL durability):
    count fsync calls on the events file descriptor."""
    synced = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: (synced.append(fd),
                                                 real_fsync(fd))[1])
    with telemetry.TelemetrySession(tmp_path, sample_resources=False) as s:
        s.event("session_note")  # lifecycle: no fsync required
        assert synced == []
        s.observe(1, {"loss": float("nan")})  # divergence → durable
    assert len(synced) >= 3  # all three sinks synced at least once


def test_watchdog_exit42_diagnosis_includes_open_span(tmp_path):
    """End-to-end: a process wedged INSIDE a span dies with exit 42, the
    stderr diagnosis names the span, and the stall event is durable in
    events.jsonl despite the os._exit teardown."""
    from actor_critic_tpu.utils import watchdog

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", (
            "import time\n"
            "from actor_critic_tpu import telemetry\n"
            "from actor_critic_tpu.utils.watchdog import StallWatchdog\n"
            f"s = telemetry.TelemetrySession({str(tmp_path)!r}, "
            "sample_resources=False)\n"
            "telemetry.set_current(s)\n"
            "StallWatchdog(1.0, startup_grace_s=0.0).start()\n"
            "with telemetry.span('update', it=681):\n"
            "    time.sleep(30)\n"  # the wedged device call
        )],
        capture_output=True, text=True, timeout=60, env=env,
    )
    assert proc.returncode == watchdog.STALL_EXIT_CODE, (
        proc.returncode, proc.stderr,
    )
    assert "last open telemetry span: 'update'" in proc.stderr, proc.stderr
    stall = [
        r for r in _read_jsonl(tmp_path / "events.jsonl")
        if r["kind"] == "stall"
    ]
    assert len(stall) == 1 and stall[0]["phase"] == "update", stall


# ------------------------------------------------------------ reporting


def test_run_report_renders_health_and_resources(tmp_path):
    (tmp_path / "spans.jsonl").write_text(
        json.dumps({"name": "iteration", "ph": "X", "ts": 0.0, "dur": 100.0,
                    "pid": 1, "tid": 1}) + "\n"
        + json.dumps({"name": "update", "ph": "X", "ts": 10.0, "dur": 80.0,
                      "pid": 1, "tid": 1}) + "\n"
        + '{"torn'  # stall-kill mid-write: must not abort the report
    )
    (tmp_path / "resources.jsonl").write_text(
        json.dumps({"ts": 1.0, "recompiles": 2, "rss_bytes": 1 << 20}) + "\n"
        + json.dumps({"ts": 2.0, "recompiles": 2, "rss_bytes": 2 << 20}) + "\n"
    )
    (tmp_path / "events.jsonl").write_text(
        json.dumps({"ts": 1.0, "kind": "session_start", "algo": "sac"}) + "\n"
        + json.dumps({"ts": 2.0, "kind": "divergence",
                      "reason": "non_finite_loss"}) + "\n"
    )
    (tmp_path / "metrics.jsonl").write_text(
        json.dumps({"iter": 3, "wall_s": 2.0, "loss": 0.5,
                    "env_steps": 300, "eval_return": 21.0}) + "\n"
    )
    report = run_report.render(str(tmp_path))
    assert "divergence" in report
    assert "| update | 1 |" in report
    assert "80.0%" in report  # 80/100 of iteration wall
    assert "RSS" in report and "recompiles" in report.lower()
    assert "eval: best 21.0" in report


def test_run_report_stitches_resume_segments(tmp_path):
    """The sinks append across resume retries (exit-42 loop): the
    recompile counter resets per process (sum positive deltas, never
    endpoints), the report names the segment count, and --trace
    re-anchors each segment's perf_counter clock via its clock_sync
    epoch so Perfetto shows retries end to end."""
    (tmp_path / "resources.jsonl").write_text(
        "".join(
            json.dumps({"ts": ts, "recompiles": rec}) + "\n"
            for ts, rec in [(0, 0), (5, 40), (10, 47), (70, 0), (75, 30), (80, 31)]
        )
    )
    (tmp_path / "events.jsonl").write_text(
        json.dumps({"ts": 0.0, "kind": "session_start", "seed": 0}) + "\n"
        + json.dumps({"ts": 65.0, "kind": "stall", "phase": "update"}) + "\n"
        + json.dumps({"ts": 70.0, "kind": "session_start", "seed": 0}) + "\n"
    )
    seg = lambda epoch: json.dumps({  # noqa: E731
        "name": "clock_sync", "ph": "M", "pid": 1, "tid": 0,
        "args": {"unix_epoch_at_ts0": epoch},
    })
    upd = json.dumps({"name": "update", "ph": "X", "ts": 0.0, "dur": 10.0,
                      "pid": 1, "tid": 1})
    (tmp_path / "spans.jsonl").write_text(
        seg(1000.0) + "\n" + upd + "\n" + seg(1060.0) + "\n" + upd + "\n"
    )
    report = run_report.render(str(tmp_path))
    assert "2 session segments" in report
    assert "78 total" in report  # 47 + 31, NOT the raw endpoint 31
    assert "stall" in report
    run_report.write_trace(
        run_report.read_jsonl(str(tmp_path / "spans.jsonl")),
        str(tmp_path / "trace.json"),
    )
    ts = [e["ts"] for e in json.load(open(tmp_path / "trace.json"))["traceEvents"]
          if e["ph"] == "X"]
    assert ts == [0.0, 60.0 * 1e6]  # segment 2 shifted by the epoch gap


def test_read_jsonl_tolerates_torn_final_line(tmp_path, capsys):
    """A half-written final record (SIGKILL mid-write) must cost exactly
    that record, silently; undecodable INTERIOR lines are dropped too
    but announced on stderr (they mean real corruption, not a kill)."""
    p = tmp_path / "events.jsonl"
    p.write_text(
        json.dumps({"kind": "a"}) + "\n"
        + json.dumps({"kind": "b"}) + "\n"
        + '{"kind": "stall", "stalled_s": 3'  # torn: no close, no newline
    )
    rows = run_report.read_jsonl(str(p))
    assert [r["kind"] for r in rows] == ["a", "b"]
    assert capsys.readouterr().err == ""  # torn tail is expected, quiet

    p.write_text(
        json.dumps({"kind": "a"}) + "\n"
        + "{corrupt\n"
        + json.dumps({"kind": "c"}) + "\n"
    )
    rows = run_report.read_jsonl(str(p))
    assert [r["kind"] for r in rows] == ["a", "c"]
    assert "1 undecodable" in capsys.readouterr().err


def test_run_report_recompile_attribution_and_slowest_spans(tmp_path):
    """The report's new sections: compile events group into the
    attribution table naming distinct arg signatures, the slowest-spans
    table ranks raw durations, and profile_done events become links."""
    (tmp_path / "spans.jsonl").write_text(
        "".join(
            json.dumps({"name": n, "ph": "X", "ts": float(i), "dur": d,
                        "pid": 1, "tid": 1}) + "\n"
            for i, (n, d) in enumerate(
                [("update", 10.0), ("checkpoint", 4e7), ("update", 30.0)]
            )
        )
    )
    sig_a = "(tensor<8x3xf32>) -> tensor<8x8xf32>"
    sig_b = "(tensor<16x3xf32>) -> tensor<16x16xf32>"
    (tmp_path / "events.jsonl").write_text(
        "".join(
            json.dumps(r) + "\n"
            for r in [
                {"ts": 1.0, "kind": "session_start"},
                {"ts": 2.0, "kind": "compile", "name": "jit_update",
                 "compile_s": 2.0, "flops": 1e9, "signature": sig_a},
                {"ts": 3.0, "kind": "compile", "name": "jit_update",
                 "compile_s": 3.0, "flops": 4e9, "signature": sig_b},
                {"ts": 4.0, "kind": "profile_done",
                 "path": str(tmp_path / "profile_001"), "wall_s": 1.5},
            ]
        )
    )
    report = run_report.render(str(tmp_path))
    assert "## Recompile attribution" in report
    assert "| `jit_update` | 2 | 0 | 5.00s" in report, report
    assert "2 argument signatures" in report
    assert sig_a in report and sig_b in report
    assert "## Slowest spans" in report
    slow_sec = report.split("## Slowest spans")[1].split("##")[0]
    # checkpoint (40 s) outranks both updates
    assert slow_sec.splitlines()[4].startswith("| 1 | checkpoint | 40.00s")
    assert "## Profile captures" in report
    assert "profile_001" in report
    # compile/profile diagnostics must NOT flood the health table
    assert "| **compile**" not in report and "| **profile_done**" not in report


def test_phase_breakdown_separates_worker_lanes():
    """Relayed env_step_worker spans run in W processes CONCURRENT with
    the parent iteration wall: they must not enter the share table
    (workers=4 at ~90% busy would print a 360% row) — they get their
    own per-pid summary line instead."""
    spans = [
        {"name": "iteration", "ph": "X", "ts": 0.0, "dur": 100.0,
         "pid": 1, "tid": 1},
        {"name": "env_step", "ph": "X", "ts": 5.0, "dur": 90.0,
         "pid": 1, "tid": 1},
    ] + [
        {"name": "env_step_worker", "ph": "X", "ts": float(10 * i),
         "dur": 80.0, "pid": pid, "tid": 0, "args": {"worker": pid - 100}}
        for pid in (100, 101, 102, 103)
        for i in range(2)
    ]
    lines = "\n".join(run_report.phase_breakdown(spans))
    assert "| env_step_worker" not in lines
    assert "4 worker process(es)" in lines
    assert "pid 100: 2 steps" in lines
    # shares stay interpretable: the only table row is env_step at 90%
    assert "90.0%" in lines and "360" not in lines


def test_run_report_cli(tmp_path):
    d = tmp_path / "t"
    d.mkdir()
    (d / "spans.jsonl").write_text(
        json.dumps({"name": "update", "ph": "X", "ts": 0.0, "dur": 5.0,
                    "pid": 1, "tid": 1}) + "\n"
    )
    out = tmp_path / "report.md"
    assert run_report.main([str(d), "--trace", "-o", str(out)]) == 0
    assert "# Run report" in out.read_text()
    assert json.load(open(d / "trace.json"))["traceEvents"]


def test_checkpointed_train_emits_fused_loop_spans(tmp_path):
    """The fused-loop boundary (utils/checkpoint.checkpointed_train)
    must emit an update span per dispatch, a log span per log_fn call,
    and a checkpoint span at every should_save boundary EVEN with
    ckpt=None (args record saved=False) so checkpointed and
    checkpoint-free runs compare phase-for-phase."""
    import jax.numpy as jnp

    from actor_critic_tpu.utils.checkpoint import checkpointed_train

    def step(state):
        return state + 1, {"loss": jnp.asarray(0.0)}

    with telemetry.TelemetrySession(tmp_path, sample_resources=False):
        state, _ = checkpointed_train(
            step, jnp.asarray(0), num_iterations=3,
            log_fn=lambda it, m: None,
        )
    assert int(state) == 3
    complete = [
        e for e in _read_jsonl(tmp_path / "spans.jsonl") if e["ph"] == "X"
    ]
    names = [e["name"] for e in complete]
    assert names.count("update") == 3 and names.count("log") == 3, names
    ck = [e for e in complete if e["name"] == "checkpoint"]
    assert len(ck) == 1 and ck[0]["args"]["saved"] is False, ck
