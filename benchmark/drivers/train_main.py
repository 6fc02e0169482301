"""Driver for every cell whose traffic is a training run: `train.main(argv)`
in this process, with the preset of the cell's configuration and the flags of
its traffic file, and nothing else: flags not named are the program's
defaults, so a PR that changes `train.py`'s loop, a default or a data plane
shows in the cell. Fused (`jax:*`) and host-environment presets both go
through here; the workload file names the rate metric.

How a run fits `--seconds`: the correctness check first (outside the window,
at the cell's own widths and T), then the measured call of `train.main` with
the `--iterations` that fill the window at the cell's pace. The pace is what
the cell's last run in this checkout measured (`.bench_scratch/pace/`); only a
cell's first run there, which compiles anyway, finds it by a short calibration
call of `train.main`. The pace sets the window's length, never the metric. The
window runs from the first row of the measured call (after `lead_s`, where
the traffic file gives one) to its last row: re-tracing and cache loads fall
before the first row, into `setup_s`. The rate is iterations times the cell's
decisions an iteration over the time between the two rows on the benchmark's
own clock (harness.steps_per_s); a row's appearance is a fence.
"""

from __future__ import annotations

import json
import os
import time

from benchmark import harness


def overrides(ctx, check_envs=None) -> tuple[dict, dict]:
    """(--set, --env-set) of the cell: the configuration's, then the traffic
    file's; `check_envs` narrows the fleet for the correctness check only."""
    sets = {**ctx.config.get("overrides", {}), **ctx.param("set", {})}
    if check_envs is not None:
        sets["num_envs"] = check_envs
    env_sets = {**ctx.config.get("env_overrides", {}), **ctx.param("env_set", {})}
    return sets, env_sets


def train_argv(ctx, iterations: int, metrics: str) -> list[str]:
    """The command line a user would type for this cell."""
    sets, env_sets = overrides(ctx)
    argv = ["--preset", ctx.config["preset"], "--seed", str(ctx.seed),
            "--iterations", str(iterations), "--metrics", metrics]
    for k, v in sets.items():
        argv += ["--set", f"{k}={v}"]
    for k, v in env_sets.items():
        argv += ["--env-set", f"{k}={v}"]
    return argv + [str(f) for f in ctx.param("flags", [])]


def resolve_preset(ctx, check_envs=None):
    """The program's own resolution of that command line's preset."""
    from actor_critic_tpu import config as config_mod

    sets, env_sets = overrides(ctx, check_envs)
    flags = [str(f) for f in ctx.param("flags", [])]
    if "--update-dtype" in flags:  # train.py: "equivalent to --set bf16_compute"
        sets["bf16_compute"] = flags[flags.index("--update-dtype") + 1] == "bf16"
    return config_mod.resolve(
        ctx.config["preset"], None, None,
        {k: str(v) for k, v in sets.items()}, env_overrides=env_sets)


def check(ctx) -> dict:
    """One seeded rollout through the program's update path and through the
    plain reference; advantage targets and the loss compared, and the traced
    update held to the configuration's precision."""
    import jax

    seam = harness.load_module("seams", ctx.config["seam"])
    ref = harness.load_module("reference", ctx.config["reference"])
    preset = resolve_preset(ctx, ctx.param("check_envs"))
    got = seam.sample(preset, ctx.seed, int(ctx.param("check_burn_in", 0)))
    network = ctx.config["network"]

    @jax.jit
    def reference(params, traj, bootstrap_obs):
        return ref.loss_and_targets(
            params, traj, bootstrap_obs, ctx.config["algorithm"], network)

    want = reference(got["params"], got["traj"], got["bootstrap_obs"])
    out = harness.compare(
        got["program"], want, harness.platform_tolerance(ctx.config, ctx.rehearsal))
    narrow = harness.narrow_matmuls(got["update_jaxpr"], network["compute_dtype"])
    out.update(shape=got["shape"], dones=got["dones"], narrow=sorted(set(narrow)),
               ok=bool(out["ok"] and not narrow))
    return out


def pace_file(ctx) -> str:
    tag = ".rehearsal" if ctx.rehearsal else ""
    return os.path.join(harness.ROOT, ".bench_scratch", "pace",
                        f"{ctx.workload['name']}{tag}.json")


def known_pace(ctx):
    """The pace the last run of this cell in this checkout measured, if any:
    only the first run of a cell pays the calibration call."""
    try:
        with open(pace_file(ctx)) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


def run(ctx) -> dict:
    import train
    from actor_critic_tpu.telemetry import profiler
    from actor_critic_tpu.utils import compile_cache

    # The program's own rule for the cache directory, before the first
    # compile of the process: the benchmark's programs share it.
    cache_dir = compile_cache.resolve_cache_dir(None)
    compile_cache.enable_persistent_cache(cache_dir)
    profiler.ensure_compile_introspection()
    ctx.say(f"compile cache: {cache_dir}")

    t0 = time.monotonic()
    verdict = check(ctx)
    ctx.say(f"check: {verdict} ({time.monotonic() - t0:.1f}s)")

    log_every = int(ctx.param("log_every", 10))
    lead_s = float(ctx.param("lead_s", 0.0))
    pace = known_pace(ctx)
    if pace is None:
        calib = os.path.join(ctx.scratch, "calibration.jsonl")
        t0 = time.monotonic()
        train.main(train_argv(ctx, int(ctx.param("calibration_iterations")), calib))
        pace = harness.pace_of(harness.read_rows(calib))
        ctx.say(f"calibration: {time.monotonic() - t0:.1f}s -> first row at "
                f"iteration {pace['first_iter']}, {pace['iters_per_s']:.4g} "
                f"iterations/s")
    iterations = harness.iterations_for(pace, ctx.seconds + lead_s, log_every)
    ctx.say(f"{iterations} iterations fill {ctx.seconds + lead_s:g}s")

    metrics = os.path.join(ctx.scratch, "metrics.jsonl")
    argv = train_argv(ctx, iterations, metrics)
    tracer = None
    telemetry_dir = None
    if ctx.trace:
        telemetry_dir = os.path.join(ctx.scratch, "telemetry")
        argv += ["--telemetry-dir", telemetry_dir]
        tracer = harness.TraceWindow(
            os.path.join(ctx.scratch, "trace"),
            lead_s=lead_s + float(ctx.param("trace_lead_s", 0.25 * ctx.seconds)),
            length_s=float(ctx.param("trace_s", 3.0)))
    setup_cache = {}

    def on_row(index: int, _t: float) -> None:
        if index == 0:
            setup_cache.update(compile_cache.cache_stats())
            if tracer is not None:
                tracer.arm()

    watcher = harness.RowWatcher(metrics, profiler.compile_event_count, on_row)
    watcher.start()
    try:
        train.main(argv)
    finally:
        watcher.finish()
        if tracer is not None:
            tracer.finish()
    compiles_end = profiler.compile_event_count()

    rows = harness.read_rows(metrics)
    times = [t for t, _ in watcher.marks]
    if len(times) != len(rows) or len(rows) < 2:
        raise harness.NoResult(
            3, f"the measured call wrote {len(rows)} rows, {len(times)} seen")
    start = harness.window_start(times, lead_s)
    win = rows[start:]
    settings = harness.cell_settings(ctx)
    spi = int(settings["rollout_steps"]) * int(settings["num_envs"])
    rate = harness.steps_per_s(win, times[start:], spi)
    if rate is None:
        raise harness.NoResult(
            3, f"rows {start}..{len(rows) - 1} give no rate at {spi} steps "
               f"an iteration (program: {win[0]['env_steps']}.."
               f"{win[-1]['env_steps']})")
    compiles_in_window = compiles_end - watcher.marks[start][1]
    failed = sum(harness.row_failed(r) for r in win[1:])
    seconds = times[-1] - times[start]
    ctx.say(f"window: rows {start}..{len(rows) - 1}, {seconds:.3f}s, "
            f"{win[-1]['env_steps'] - win[0]['env_steps']} steps, "
            f"{compiles_in_window} compiles inside; by the program's own "
            f"wall_s: {win[-1]['wall_s'] - win[0]['wall_s']:.3f}s")
    os.makedirs(os.path.dirname(pace_file(ctx)), exist_ok=True)
    with open(pace_file(ctx), "w") as fh:
        json.dump({"first_iter": rows[0]["iter"],
                   "iters_per_s": (win[-1]["iter"] - win[0]["iter"]) / seconds}, fh)
    tol = harness.platform_tolerance(ctx.config, ctx.rehearsal)
    return {
        "correct": bool(verdict["ok"] and compiles_in_window == 0 and failed == 0),
        "compared": {
            "adv_err": [verdict["adv_err"], tol["adv_tol"]],
            "loss_err": [verdict["loss_err"], tol["loss_tol"]],
            "narrow_ops": [len(verdict["narrow"]), 0],
            "compiles_in_window": [compiles_in_window, 0],
            "nonfinite_rows": [failed, 0],
        },
        "attempted": win[-1]["iter"] - win[0]["iter"],
        "failed": failed,
        "end_to_end": {ctx.workload["rate_metric"]: rate},
        "window_seconds": seconds,
        "window_iters": (win[0]["iter"], win[-1]["iter"]),
        "check": verdict,
        "compiles_in_window": compiles_in_window,
        "compile_records": profiler.compile_records_since(0),
        "cache_stats_setup": setup_cache,
        "spans": harness.read_spans(telemetry_dir) if telemetry_dir else [],
        "trace": tracer.reduced() if tracer is not None else None,
        "trace_path": tracer.path() if tracer is not None else None,
        "rows": win,
    }
