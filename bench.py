"""Headline benchmark: A2C CartPole-v1 fused-trainer throughput, plus a
block of counts and wall-clocks taken on the CPU backend.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "env-steps/sec/chip", "vs_baseline": N,
   "platform": ..., "device_kind": ...,
   "cpu_metrics": {"host_pool_scaling": {...}, "startup_to_first_step": {...},
                   "async_decoupling": {...}, "update_wall": {...}}}
or, when the headline cannot run (no chip, backend error):
  {"metric": ..., "value": 0.0, ..., "error": "...",
   "cpu_metrics": {...}}  (exit code 1)

`cpu_metrics` is measured on the CPU backend every run, each metric in
its own CPU-pinned subprocess with its own timeout (DEFAULT_CPU_METRICS /
BENCH_CPU_METRICS / BENCH_CPU_METRIC_TIMEOUT). None of it is a speed of
the system: the device decides that (ROADMAP S0/D1 replace this block).

`vs_baseline` is relative to the BASELINE.json:5 north-star target of
1,000,000 env-steps/sec (the reference publishes no numbers of its own —
empty mount, SURVEY.md §0 / BASELINE.md).

Process layout. A chip belongs to one process at a time, so this file
runs as a parent that never imports jax and two children in sequence:

  parent (this file, default mode)
    ├─ preflight: `jax.devices()` in a subprocess, killed after
    │  BENCH_PREFLIGHT_TIMEOUT → fast {"error": ...} JSON when the backend
    │  cannot start; it has exited (and released the chip) before
    └─ child (`bench.py --child`): the real benchmark, killed after
       BENCH_TIMEOUT → {"error": ...} JSON if it hangs

The child is a fresh process on purpose: earlier device allocations in the
same process depress later benchmark numbers (see bench/suite.py, which
subprocess-isolates every case for the same reason).

Design: the entire rollout(T)×E + GAE + update is one jitted program, and
ITERS_PER_CALL iterations are scanned inside a single dispatch so the
per-dispatch host cost is amortized. Steps/sec counts actual environment
transitions: calls × iters × T × E.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

METRIC = "a2c_cartpole_fused_throughput"
UNIT = "env-steps/sec/chip"
NORTH_STAR = 1_000_000.0


def _error_record(msg: str) -> dict:
    return {
        "metric": METRIC,
        "value": 0.0,
        "unit": UNIT,
        "vs_baseline": 0.0,
        "error": msg,
    }


# CPU-runnable bench/suite.py metrics attached to every bench.py record
# (ISSUE 6 satellite, extended by ISSUE 8's replay_sample_throughput,
# ISSUE 9's multihost_scaling, ISSUE 10's serving_latency, ISSUE 11's
# scenario_fleet and ISSUE 13's consumed_env_steps_per_s data-plane A/B):
# host_pool_scaling, startup_to_first_step, async_decoupling,
# update_wall, replay_sample_throughput, multihost_scaling,
# serving_latency, serving_fleet_scaling (N gateway replicas behind the
# fleet proxy), scenario_fleet (heterogeneous mixture + the
# steps/s-vs-instance-count sweep) and consumed_env_steps_per_s (host vs
# device data plane) are measured on the CPU backend whether or not the
# headline ran. BENCH_CPU_METRICS overrides the set (comma
# list of bench/suite.py names); "0"/"none"/"off" disables. Trend the
# block across rounds with scripts/bench_trend.py. Budget note: the
# multihost grid adds ~2 minutes of multi-process cluster runs and the
# scenario_fleet mixture/sweep adds ~4-5 minutes (bounded by
# BENCH_FLEET_MAX_E) on top of the 2-3 minutes the rest of the block
# costs on this host — hence the 480 s default per-metric timeout.
DEFAULT_CPU_METRICS = (
    "host_pool_scaling,startup_to_first_step,async_decoupling,update_wall,"
    "fused_update_wall,replay_sample_throughput,multihost_scaling,"
    "serving_latency,serving_fleet_scaling,scenario_fleet,"
    "consumed_env_steps_per_s,pad_overhead"
)


def _cpu_metric_names() -> list[str]:
    raw = os.environ.get("BENCH_CPU_METRICS", "").strip()
    if raw.lower() in ("0", "none", "off"):
        return []
    if not raw:
        raw = DEFAULT_CPU_METRICS
    return [n for n in (s.strip() for s in raw.split(",")) if n]


def collect_cpu_metrics() -> dict:
    """{suite name: its JSON record (or {'error': ...})} for each
    configured CPU metric, each in its own CPU-pinned subprocess (the
    suite's own isolation rationale; a CPU child never contends for the
    chip) with a per-metric timeout — one wedged bench must not take the
    record down."""
    names = _cpu_metric_names()
    if not names:
        return {}
    suite = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench", "suite.py"
    )
    timeout_s = float(os.environ.get("BENCH_CPU_METRIC_TIMEOUT", 480))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out: dict = {}
    for name in names:
        try:
            proc = subprocess.run(
                [sys.executable, suite, name],
                capture_output=True, text=True, timeout=timeout_s, env=env,
            )
        except subprocess.TimeoutExpired:
            out[name] = {"error": f"exceeded {timeout_s:.0f}s"}
            continue
        lines = [
            ln for ln in (proc.stdout or "").strip().splitlines()
            if ln.startswith("{")
        ]
        if proc.returncode != 0 or not lines:
            tail = (proc.stderr or proc.stdout or "").strip().splitlines()
            out[name] = {
                "error": f"rc={proc.returncode}: "
                + (tail[-1] if tail else "no output")
            }
            continue
        try:
            out[name] = json.loads(lines[-1])
        except json.JSONDecodeError:
            out[name] = {"error": "unparseable JSON"}
    return out


def _with_cpu_metrics(record: dict) -> dict:
    """Attach the CPU multi-metric block; measurement failure must never
    break the one-parseable-JSON-line contract."""
    try:
        metrics = collect_cpu_metrics()
    except Exception as e:  # pragma: no cover - defensive
        metrics = {"error": str(e)[:200]}
    if metrics:
        record["cpu_metrics"] = metrics
    return record


def _allow_cpu() -> bool:
    # "0"/"false"/"no"/"" all mean OFF — raw truthiness would treat
    # BENCH_ALLOW_CPU=0 as enabled and defeat the honest-platform guard.
    return os.environ.get("BENCH_ALLOW_CPU", "").strip().lower() not in (
        "", "0", "false", "no",
    )


def _sub_env() -> dict:
    """Environment for the preflight and bench children: the caller's,
    pinned to the CPU only under BENCH_ALLOW_CPU."""
    env = dict(os.environ)
    if _allow_cpu():
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _run_sub(code_or_args: list[str], timeout_s: float):
    """Run a python subprocess; returns (rc_or_None_on_timeout, stdout, stderr)."""
    try:
        proc = subprocess.run(
            [sys.executable, *code_or_args],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=_sub_env(),
        )
    except subprocess.TimeoutExpired as e:
        out = e.stdout or b""
        err = e.stderr or b""
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        if isinstance(err, bytes):
            err = err.decode(errors="replace")
        return None, out, err
    return proc.returncode, proc.stdout, proc.stderr


def supervise() -> int:
    # Outer-timeout floor for callers: worst case is preflight + bench
    # ≈ 60 + 420 = 480s; any external kill budget must exceed that or the
    # parent can't emit its structured-error JSON first.
    preflight_s = float(os.environ.get("BENCH_PREFLIGHT_TIMEOUT", 60))
    bench_s = float(os.environ.get("BENCH_TIMEOUT", 420))

    def emit_error(msg: str) -> int:
        # The CPU block rides every error line too.
        print(json.dumps(_with_cpu_metrics(_error_record(msg))))
        return 1

    rc, out, err = _run_sub(
        ["-c", "import jax; print('platform:', jax.devices()[0].platform)"],
        preflight_s,
    )
    if rc is None:
        return emit_error(
            f"backend preflight exceeded {preflight_s:.0f}s — the chip is "
            "unreachable or held by another process; no benchmark run"
        )
    if rc != 0:
        tail = (err or out).strip().splitlines()
        return emit_error(
            "backend preflight failed: " + (tail[-1] if tail else f"rc={rc}")
        )
    platform = next(
        (
            ln.split("platform:", 1)[1].strip()
            for ln in out.splitlines()
            if "platform:" in ln
        ),
        "unknown",
    )
    if platform != "tpu" and not _allow_cpu():
        # Refuse to pass a CPU fallback off as a per-chip TPU number
        # (VERDICT.md round-1 weakness #2: the perf story must be honest).
        return emit_error(
            f"backend resolved to {platform!r}, not a TPU — set "
            "BENCH_ALLOW_CPU=1 to benchmark it anyway"
        )

    rc, out, err = _run_sub([os.path.abspath(__file__), "--child"], bench_s)
    if rc is None:
        return emit_error(
            f"benchmark exceeded {bench_s:.0f}s (preflight had passed)"
        )
    lines = [ln for ln in out.strip().splitlines() if ln.startswith("{")]
    if rc != 0 or not lines:
        tail = (err or out).strip().splitlines()
        return emit_error(
            f"benchmark child rc={rc}: " + (tail[-1] if tail else "no output")
        )
    try:
        record = json.loads(lines[-1])
    except json.JSONDecodeError:
        return emit_error("benchmark child emitted unparseable JSON")
    # Re-check the platform the child ACTUALLY ran on: a CPU number must
    # never pass as a per-chip TPU figure.
    child_platform = record.get("platform", "unknown")
    if child_platform != "tpu" and not _allow_cpu():
        return emit_error(
            f"benchmark ran on {child_platform!r}, not a TPU (backend "
            "changed after preflight) — set BENCH_ALLOW_CPU=1 to accept"
        )
    print(json.dumps(_with_cpu_metrics(record)))
    return 0


def main() -> None:
    import jax

    from actor_critic_tpu.algos import a2c
    from actor_critic_tpu.envs import make_cartpole
    from actor_critic_tpu.utils.device_peaks import peak_bf16_tflops

    E = int(os.environ.get("BENCH_ENVS", 4096))
    T = int(os.environ.get("BENCH_ROLLOUT", 32))
    iters_per_call = int(os.environ.get("BENCH_ITERS_PER_CALL", 50))
    calls = int(os.environ.get("BENCH_CALLS", 5))

    env = make_cartpole()
    cfg = a2c.A2CConfig(num_envs=E, rollout_steps=T, lr=1e-3)
    state = a2c.init_state(env, cfg, jax.random.key(0))
    train_step = a2c.make_train_step(env, cfg)

    def run_block(state):
        def body(s, _):
            s, _m = train_step(s)
            return s, None

        s, _ = jax.lax.scan(body, state, None, length=iters_per_call)
        return s

    run_block_donating = jax.jit(run_block, donate_argnums=0)

    # Warm-up / compile.
    state = run_block_donating(state)
    jax.block_until_ready(state)

    t0 = time.perf_counter()
    for _ in range(calls):
        state = run_block_donating(state)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0

    steps = calls * iters_per_call * T * E
    sps = steps / dt

    # FLOPs sanity line (round-2 verdict weak #1): per-env-step compute is
    # 5 forward-equivalents of the ACTUAL bench network (rollout fwd = 1,
    # update fwd+bwd ≈ 3, truncation final-obs values fwd = 1) at
    # 2·Σ(in·out) FLOPs each — derived from cfg/env so the emitted model
    # can never silently drift from what ran. The implied sustained-FLOPs
    # figure lets a reader check the number against the published peak of
    # the chip that ran it (utils/device_peaks.py): a figure above the
    # peak means the timing is wrong, not that the chip is fast.
    dims = (env.spec.obs_shape[0], *cfg.hidden)
    fwd_flops = 2 * sum(a * b for a, b in zip(dims, dims[1:]))
    fwd_flops += 2 * cfg.hidden[-1] * (env.spec.action_dim + 1)
    flops_per_step = 5 * fwd_flops
    implied_tflops = sps * flops_per_step / 1e12
    device = jax.devices()[0]
    record = {
        "metric": METRIC,
        "value": round(sps, 1),
        "unit": UNIT,
        "vs_baseline": round(sps / NORTH_STAR, 4),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "flops_per_step": flops_per_step,
        "implied_tflops": round(implied_tflops, 1),
    }
    if device.platform == "tpu":
        # A chip that is not in the table raises: no default peak.
        peak = peak_bf16_tflops(device.device_kind)
        record["peak_bf16_tflops"] = peak
        record["implied_over_peak"] = round(implied_tflops / peak, 4)
    print(json.dumps(record))


if __name__ == "__main__":
    if "--child" in sys.argv[1:]:
        main()
    else:
        sys.exit(supervise())
