#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell on the machine it is started on and prints, as the last line of
standard output, one JSON object with `correct`, `attempted`, `failed`,
`metrics` and `device` (with `--trace 1` the per-layer metrics, the device's
`busy_s` and `window_s`, and `breakdown`; with `--trace 0` the end-to-end
metrics), and last `compared`: each number that decided `correct` beside its
limit, which are also the last lines of standard error. It exits with another
code than 0 and prints no result line when JAX finds no TPU or fewer chips
than the cell asks for, or when the program under test is not beside it.
`--rehearsal` walks the same control flow at tiny shapes on the CPU, tags every
line REHEARSAL and prints no result line: it checks the harness, never the
system's speed.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args(argv)

    from benchmark import harness

    for needed in ("train.py", "scripts/serve.py", "actor_critic_tpu"):
        if not os.path.exists(os.path.join(ROOT, needed)):
            raise harness.NoResult(
                2, f"the program under test is not here (no {needed})")
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    ctx = harness.make_ctx(
        args.workload, args.seed, args.seconds, bool(args.trace),
        args.rehearsal)
    device = harness.device_block(int(ctx.workload["chips"]), ctx.rehearsal)
    ctx.say(f"device: {device}")

    driver = harness.load_module("drivers", ctx.workload["driver"])
    run = driver.run(ctx)
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    run["device"] = device

    metrics: dict = {}
    if ctx.trace:
        for name in harness.per_layer_names(ctx.workload):
            reader = harness.load_module("layers", name)
            value = reader.read(run, ctx)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": reader.UNIT}
        trace = run.get("trace")
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
    else:
        for name, value in run["end_to_end"].items():
            metrics[name] = {"value": float(value),
                             "unit": ctx.workload["units"][name]}
    result = {
        "correct": bool(run["correct"]),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if ctx.trace and run.get("trace") is not None:
        result["breakdown"] = {
            "device_ops": run["trace"]["top_ops"],
            "idle_gaps": run["trace"]["idle_gaps"],
        }
    # Last in the line: what `correct` compared, `{name: [number, limit]}`.
    result["compared"] = run["compared"]
    for line in run.get("notes", []):
        ctx.say(line)
    if not ctx.trace:
        # Everything outside the measured window is set-up: loading,
        # compiling or reading the cache, the correctness check, warm-up,
        # calibration and tear-down.
        metrics["setup_s"] = {
            "value": time.time() - T_PROCESS_START - run["window_seconds"],
            "unit": "s",
        }
    if ctx.rehearsal:
        ctx.say(f"would print: {json.dumps(result)}")
        return 0 if run["correct"] else 1
    sys.stdout.flush()
    for name, (value, limit) in run["compared"].items():
        print(f"compared {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
