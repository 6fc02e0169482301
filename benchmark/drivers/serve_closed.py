"""Driver for a serving cell under a closed loop.

The gateway is built by `scripts/serve.py`'s own `main(argv)` with the preset
of the cell's configuration and the flags of its traffic file (`serve_flags`),
in this process, which holds the chip; flags not named are the program's
defaults. `main` blocks the main thread until it is interrupted, so the run is
conducted from a second thread, which ends it with SIGINT, as a user would.
Load comes from `traffic/loadgen.py` in a child process that never imports
jax.

`correct`: before the window, seeded observation rows sent through `/v1/act`
equal the plain reference's forward pass of the same seeded parameters within
the configuration's tolerance, and again after the window, so that a swap or
a recompile mid-run cannot go unseen; no compilation inside the window; no
failed request.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

from benchmark import harness

URL_LINE = re.compile(r"serving gateway: (http://[^/\s]+)/v1/act")


class _Tee:
    """Standard output passed through, watched for the gateway's URL."""

    def __init__(self, real, found: threading.Event):
        self.real, self.found, self.url, self._buf = real, found, None, ""

    def write(self, text: str) -> int:
        self.real.write(text)
        if self.url is None:
            self._buf += text
            m = URL_LINE.search(self._buf)
            if m:
                self.url = m.group(1)
                self.found.set()
        return len(text)

    def flush(self) -> None:
        self.real.flush()


def _post(url: str, obs: list) -> list:
    req = urllib.request.Request(
        url + "/v1/act", data=json.dumps({"obs": obs}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())["actions"]


def _get(url: str, path: str) -> str:
    with urllib.request.urlopen(url + path, timeout=30) as resp:
        return resp.read().decode()


def check_rows(ctx, url: str, params, spec) -> dict:
    """Seeded rows through `/v1/act`, in requests of 1, 5 and 8 rows (the
    mix's sizes), against the reference's forward pass."""
    import numpy as np

    ref = harness.load_module("reference", ctx.config["reference"])
    n = int(ctx.param("check_rows", 64))
    obs = np.random.default_rng(ctx.seed).standard_normal(
        (n, *spec.obs_shape)).astype(np.float32)
    got, i = [], 0
    sizes = [int(g["rows"]) for g in ctx.param("clients")]
    while i < n:
        k = min(sizes[len(got) % len(sizes)], n - i)
        got.extend(_post(url, obs[i:i + k].tolist()))
        i += k
    want = np.asarray(ref.greedy_action(params, obs), np.float64)
    err = float(np.max(np.abs(np.asarray(got, np.float64) - want))
                / max(float(np.max(np.abs(want))), 1e-12))
    tol = harness.platform_tolerance(ctx.config, ctx.rehearsal)["act_tol"]
    return {"act_err": err, "ok": bool(err <= tol), "rows": n}


def conduct(ctx, tee: _Tee, out: dict) -> None:
    """Everything but the gateway itself; runs beside `serve.main`."""
    from actor_critic_tpu import serving
    from actor_critic_tpu.telemetry import profiler
    from actor_critic_tpu.utils import compile_cache

    child = None
    tracer = None
    try:
        if not tee.found.wait(600):
            raise RuntimeError("the gateway printed no URL within 600 s")
        url = tee.url
        preset = out["preset"]
        spec = out["spec"]
        params = serving.init_params(
            spec, preset.config, preset.algo, seed=ctx.seed)
        before = check_rows(ctx, url, params, spec)
        ctx.say(f"check before: {before}")

        child = subprocess.Popen(
            [sys.executable, os.path.join(harness.HERE, "traffic", "loadgen.py"),
             "--url", url,
             "--traffic", os.path.join(
                 harness.HERE, "traffic", f"{ctx.workload['traffic']}.json"),
             "--seed", str(ctx.seed), "--seconds", str(ctx.seconds),
             "--obs-dim", str(spec.obs_shape[0])]
            + (["--rehearsal"] if ctx.rehearsal else []),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        if child.stdout.readline().strip() != "READY":
            raise RuntimeError("the load generator did not get ready")
        if ctx.trace:
            tracer = harness.TraceWindow(
                os.path.join(ctx.scratch, "trace"),
                lead_s=float(ctx.param("trace_lead_s", 0.25 * ctx.seconds)),
                length_s=float(ctx.param("trace_s", 3.0)))
        out["cache_stats_setup"] = compile_cache.cache_stats()
        out["metrics_before"] = _get(url, "/metrics")
        compiles0 = profiler.compile_event_count()
        out["window_epoch"] = [time.time(), None]
        if tracer is not None:
            tracer.arm()
        child.stdin.write("GO\n")
        child.stdin.flush()
        load = json.loads(child.stdout.readline())
        out["window_epoch"][1] = out["window_epoch"][0] + load["seconds"]
        out["compiles_in_window"] = profiler.compile_event_count() - compiles0
        out["metrics_after"] = _get(url, "/metrics")
        child.wait(30)
        if tracer is not None:
            tracer.finish()
            out["trace"] = tracer.reduced()
            out["trace_path"] = tracer.path()
            tracer = None
        after = check_rows(ctx, url, params, spec)
        ctx.say(f"check after: {after}")
        ctx.say(f"load: {load}")
        out.update(load=load, check={"before": before, "after": after})
    except BaseException as e:  # reported by the main thread
        out["error"] = repr(e)
    finally:
        if tracer is not None:
            tracer.finish()
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        # What a user does to stop the gateway: Ctrl-C.
        os.kill(os.getpid(), signal.SIGINT)


def run(ctx) -> dict:
    sys.path.insert(0, os.path.join(harness.ROOT, "scripts"))
    import serve  # scripts/serve.py

    from actor_critic_tpu import config as config_mod
    from actor_critic_tpu.telemetry import profiler

    profiler.ensure_compile_introspection()
    argv = ["--preset", ctx.config["preset"], "--seed", str(ctx.seed)]
    for k, v in ctx.config.get("overrides", {}).items():
        argv += ["--set", f"{k}={v}"]
    argv += [str(f) for f in ctx.param("serve_flags", [])]
    telemetry_dir = None
    if ctx.trace:
        telemetry_dir = os.path.join(ctx.scratch, "telemetry")
        argv += ["--telemetry-dir", telemetry_dir]
    preset = config_mod.resolve(
        ctx.config["preset"], None, None,
        {k: str(v) for k, v in ctx.config.get("overrides", {}).items()})
    out: dict = {"preset": preset,
                 "spec": serve.spec_for(preset.env, preset.env_kwargs)}

    found = threading.Event()
    tee = _Tee(sys.stdout, found)
    conductor = threading.Thread(
        target=conduct, args=(ctx, tee, out), name="bench-conductor", daemon=True)
    conductor.start()
    with contextlib.redirect_stdout(tee):
        try:
            serve.main(argv)
        except KeyboardInterrupt:
            pass
    conductor.join()
    if "error" in out:
        raise harness.NoResult(3, f"serving run failed: {out['error']}")
    load, chk = out["load"], out["check"]
    tol = harness.platform_tolerance(ctx.config, ctx.rehearsal)["act_tol"]
    return {
        "compared": {
            "act_err_before": [chk["before"]["act_err"], tol],
            "act_err_after": [chk["after"]["act_err"], tol],
            "compiles_in_window": [out["compiles_in_window"], 0],
            "failed_requests": [load["failed"], 0],
        },
        "correct": bool(chk["before"]["ok"] and chk["after"]["ok"]
                        and out["compiles_in_window"] == 0
                        and load["failed"] == 0 and load["ok_requests"] > 0),
        "attempted": load["attempted"],
        "failed": load["failed"],
        "end_to_end": {"act_per_s": load["act_per_s"],
                       "act_p99_ms": load["p99_ms"]},
        "window_seconds": load["seconds"],
        "window_epoch": out["window_epoch"],
        "check": chk,
        "compiles_in_window": out["compiles_in_window"],
        "compile_records": profiler.compile_records_since(0),
        "cache_stats_setup": out["cache_stats_setup"],
        "metrics_before": out["metrics_before"],
        "metrics_after": out["metrics_after"],
        "spans": harness.read_spans(telemetry_dir) if telemetry_dir else [],
        "trace": out.get("trace"),
        "trace_path": out.get("trace_path"),
        "notes": [f"act latency: p50 {load['p50_ms']:.3f} ms, p99 "
                  f"{load['p99_ms']:.3f} ms over {load['ok_requests']} requests "
                  f"({load['requests_per_s']:.1f} requests/s, "
                  f"{load['clients']} clients)"],
    }
