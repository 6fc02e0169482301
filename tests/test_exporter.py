"""Live run introspection (telemetry/exporter.py + profiler.py, ISSUE 3).

Contracts, all against an EPHEMERAL port (serve_port=0):
- `/metrics` is valid Prometheus text exposing steps/s, the recompile
  counter, registered sampler gauges, and the last observe() row;
- `/healthz` reports open span + watchdog staleness, and flips to 503
  exactly when an armed watchdog is past timeout outside grace;
- `/profile?iters=N` (and SIGUSR2) arm a windowed jax.profiler capture
  that the training-loop tick starts/stops, leaving a trace directory
  under the telemetry dir plus profile_start/profile_done events;
- the compile listener turns XLA compilations into structured `compile`
  events carrying the abstract argument signature, so a recompile names
  the shape/dtype that changed;
- `train.py --telemetry-port` refuses to run without --telemetry-dir,
  and (slow) a live CPU run answers /metrics + /healthz mid-training.
"""

import json
import os
import re
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from actor_critic_tpu import telemetry
from actor_critic_tpu.telemetry.exporter import render_metrics
from actor_critic_tpu.utils import watchdog as watchdog_mod

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? \S+$"
)


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _session(tmp_path, **kw):
    kw.setdefault("sample_resources", False)
    kw.setdefault("serve_port", 0)
    return telemetry.TelemetrySession(tmp_path, **kw)


# ---------------------------------------------------------------- /metrics


def test_metrics_is_valid_prometheus_text_with_rates(tmp_path):
    with _session(tmp_path) as s:
        telemetry.observe(1, {"loss": 0.5, "env_steps": 100})
        time.sleep(0.02)
        telemetry.observe(3, {"loss": 0.25, "env_steps": 300})
        status, body = _get(s.exporter.url + "/metrics")
    assert status == 200
    samples = {}
    for line in body.splitlines():
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE ")), line
            continue
        assert _PROM_LINE.match(line), line
        name_part, value = line.rsplit(" ", 1)
        samples[name_part] = float(value)  # every sample parses numeric
    assert samples["actor_critic_up"] == 1
    assert samples["actor_critic_xla_recompiles_total"] >= 0
    assert samples["actor_critic_rss_bytes"] > 0
    # steps/s + iters/s from the two observe() rows
    assert samples["actor_critic_env_steps_per_s"] > 0
    assert samples["actor_critic_iters_per_s"] > 0
    # the last training row rides along, labeled per metric
    assert samples['actor_critic_train_metric{metric="loss"}'] == 0.25
    assert samples["actor_critic_train_iteration"] == 3


def test_metrics_includes_registered_gauges(tmp_path):
    from actor_critic_tpu.telemetry import sampler

    key = sampler.register_gauge(
        "host_pool", lambda: {"utilization": 0.75, "workers": 2}
    )
    try:
        with _session(tmp_path) as s:
            body = render_metrics(s)  # pure render, no socket needed
    finally:
        sampler.unregister_gauge(key)
    assert "actor_critic_host_pool_utilization 0.75" in body
    assert "actor_critic_host_pool_workers 2" in body


def test_metrics_drops_nan_training_values(tmp_path):
    with _session(tmp_path) as s:
        telemetry.observe(1, {"loss": float("nan"), "ok": 1.0})
        body = render_metrics(s)
    assert 'metric="ok"' in body
    assert 'metric="loss"' not in body  # NaN would break scrapers


# ---------------------------------------------------------------- /healthz


def test_healthz_reports_open_span_and_ok(tmp_path):
    with _session(tmp_path) as s:
        with telemetry.span("update", it=5):
            status, body = _get(s.exporter.url + "/healthz")
    h = json.loads(body)
    assert status == 200 and h["status"] == "ok"
    assert h["open_span"]["name"] == "update"
    assert h["open_span"]["open_s"] >= 0
    assert h["profiler"]["state"] == "idle"


def test_healthz_503_when_watchdog_stalled(tmp_path):
    """An armed watchdog past its timeout outside grace must flip
    /healthz to 503/stalled — the condition tpu_watch-style probes key
    on. The watchdog is injected un-started (its firing thread would
    os._exit the test runner)."""
    w = watchdog_mod.StallWatchdog(timeout_s=1.0, startup_grace_s=0.0)
    now = time.monotonic()
    w._last = now - 10.0
    w._grace_until = now - 5.0
    watchdog_mod._ACTIVE.append(w)
    try:
        with _session(tmp_path) as s:
            url = s.exporter.url + "/healthz"
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url, timeout=10)
            assert ei.value.code == 503
            h = json.loads(ei.value.read())
            assert h["status"] == "stalled"
            assert h["watchdog"]["staleness_s"] > h["watchdog"]["timeout_s"]
            # a heartbeat landing brings it back to 200
            w.touch()
            status, body = _get(url)
            assert status == 200 and json.loads(body)["status"] == "ok"
    finally:
        watchdog_mod._ACTIVE.remove(w)


def test_unknown_route_404(tmp_path):
    with _session(tmp_path) as s:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(s.exporter.url + "/nope", timeout=10)
        assert ei.value.code == 404


# ---------------------------------------------------------------- /profile


def test_profile_endpoint_captures_a_window(tmp_path):
    import jax
    import jax.numpy as jnp

    with _session(tmp_path) as s:
        status, body = _get(s.exporter.url + "/profile?iters=2")
        assert status == 202 and json.loads(body)["state"] == "armed"
        f = jax.jit(lambda x: x * 2.0)
        telemetry.profiler_tick()  # capture starts here
        assert s.profiler.status()["state"] == "active"
        jax.block_until_ready(f(jnp.ones(4)))
        telemetry.profiler_tick()
        telemetry.profiler_tick()  # window of 2 ends: capture stops
        assert s.profiler.status() == {"state": "idle", "captures": 1}
    # trace directory under the telemetry dir, named by the events
    events = _read_jsonl(tmp_path / "events.jsonl")
    start = [e for e in events if e["kind"] == "profile_start"]
    done = [e for e in events if e["kind"] == "profile_done"]
    assert len(start) == 1 and len(done) == 1
    assert start[0]["iters"] == 2
    path = done[0]["path"]
    assert path.startswith(str(tmp_path)) and os.path.isdir(path)
    assert any(os.scandir(path)), "profiler wrote an empty directory"
    # the capture window also lands as a phase span
    names = [
        e["name"] for e in _read_jsonl(tmp_path / "spans.jsonl")
        if e.get("ph") == "X"
    ]
    assert "profile" in names


def test_profile_rejects_bad_iters(tmp_path):
    with _session(tmp_path) as s:
        for q in ("iters=0", "iters=abc"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    s.exporter.url + "/profile?" + q, timeout=10
                )
            assert ei.value.code == 400


def test_arming_twice_keeps_first_window(tmp_path):
    with _session(tmp_path, serve_port=None) as s:
        assert s.profiler.arm(3)["iters"] == 3
        assert s.profiler.arm(50)["iters"] == 3  # no-op report, no error
        s.profiler._armed_iters = 0  # disarm without starting a capture


@pytest.mark.skipif(
    not hasattr(signal, "SIGUSR2"), reason="no SIGUSR2 on this platform"
)
def test_sigusr2_arms_capture(tmp_path):
    from actor_critic_tpu.telemetry.profiler import install_sigusr2

    assert install_sigusr2(iters=4)
    try:
        with _session(tmp_path, serve_port=None) as s:
            os.kill(os.getpid(), signal.SIGUSR2)
            deadline = time.monotonic() + 5.0
            while (
                s.profiler.status()["state"] != "armed"
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            assert s.profiler.status()["state"] == "armed"
            assert s.profiler.status()["iters"] == 4
            # disarm without starting a capture (no jax work here)
            s.profiler._arm_seen = s.profiler._arm_requests
    finally:
        signal.signal(signal.SIGUSR2, signal.SIG_DFL)


# ------------------------------------------------------- compile listener


def test_compile_events_name_the_changed_signature(tmp_path):
    """Two dispatches of one jitted function at different shapes must
    produce `compile` events whose abstract argument signatures DIFFER —
    the recompile-attribution contract."""
    import jax
    import jax.numpy as jnp

    def distinctly_named_fn(x):
        return x * 3.0

    f = jax.jit(distinctly_named_fn)
    with _session(tmp_path, serve_port=None):
        jax.block_until_ready(f(jnp.ones(7)))
        jax.block_until_ready(f(jnp.ones(13)))  # shape change → recompile
    comps = [
        e for e in _read_jsonl(tmp_path / "events.jsonl")
        if e["kind"] == "compile" and "distinctly_named_fn" in e.get("name", "")
    ]
    assert len(comps) == 2, [e.get("name") for e in comps]
    sigs = {e.get("signature") for e in comps}
    assert len(sigs) == 2 and all(s for s in sigs), sigs
    assert "7" in "".join(sigs) and "13" in "".join(sigs)
    assert all(e["compile_s"] >= 0 for e in comps)


# ------------------------------------------------------------- train.py


def test_cli_telemetry_port_requires_dir():
    import train as train_cli

    with pytest.raises(SystemExit, match="telemetry-dir"):
        train_cli.main(["--preset", "a2c_cartpole", "--telemetry-port", "0"])
    with pytest.raises(SystemExit, match="sample-s"):
        train_cli.main(
            ["--preset", "a2c_cartpole", "--telemetry-dir", "/tmp/x",
             "--telemetry-sample-s", "0"]
        )


@pytest.mark.slow
def test_cli_live_introspection_end_to_end(tmp_path):
    """A real CPU train.py run with --telemetry-port 0 must answer
    /metrics (steps/s + recompile count) and /healthz while training."""
    tel = tmp_path / "tel"
    cmd = [
        sys.executable, "train.py",
        "--algo", "a2c", "--env", "jax:two_state",
        "--iterations", "30000", "--log-every", "5", "--quiet",
        "--set", "num_envs=8", "--set", "rollout_steps=4",
        "--set", "hidden=16",
        "--metrics", str(tmp_path / "m.jsonl"),
        "--telemetry-dir", str(tel), "--telemetry-port", "0",
    ]
    env = {"JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    env.update({k: v for k, v in os.environ.items() if k not in env})
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, bufsize=1, cwd="/root/repo", env=env,
    )
    try:
        url = None
        for line in proc.stdout:
            m = re.search(r"telemetry exporter: (http://\S+)/metrics", line)
            if m:
                url = m.group(1)
                break
        assert url, "exporter URL never printed"
        # Wait for training rows (first compile dominates), then scrape.
        deadline = time.monotonic() + 120
        body = ""
        while time.monotonic() < deadline:
            _, body = _get(url + "/metrics")
            if "actor_critic_env_steps_per_s" in body:
                break
            time.sleep(1.0)
        assert "actor_critic_env_steps_per_s" in body, body[-2000:]
        assert "actor_critic_xla_recompiles_total" in body
        status, h = _get(url + "/healthz")
        assert status == 200 and json.loads(h)["status"] == "ok"
    finally:
        proc.terminate()
        proc.wait(timeout=30)
    comps = [
        e for e in _read_jsonl(tel / "events.jsonl")
        if e["kind"] == "compile"
    ]
    assert comps, "no compile events from a fresh jit process"


def test_ephemeral_port_reported_on_session_object(tmp_path):
    """ISSUE 10 satellite: serve_port=0 binds an OS-assigned port, and
    the ACTUAL bound port is readable off the session (exporter_port)
    and recorded in the exporter_start event — scripts and CI read it
    instead of racing for a fixed port."""
    with _session(tmp_path) as s:
        port = s.exporter_port
        assert port not in (None, 0)
        assert s.exporter.url.endswith(f":{port}")
        status, _ = _get(s.exporter.url + "/healthz")
        assert status == 200
    events = _read_jsonl(os.path.join(tmp_path, "events.jsonl"))
    starts = [e for e in events if e.get("kind") == "exporter_start"]
    assert starts and starts[0]["port"] == port
    # No exporter -> None, not an attribute error.
    with _session(tmp_path, serve_port=None) as s2:
        assert s2.exporter_port is None


# ------------------------------------------------------------- ISSUE 16


def test_closed_session_renders_tombstone(tmp_path):
    """A scraper hitting a session that already close()d must read
    `up 0` — down, not frozen: stale gauges from a dead process are
    indistinguishable from a healthy flatline."""
    with _session(tmp_path) as s:
        telemetry.observe(1, {"loss": 0.5})
        live = render_metrics(s)
        assert "actor_critic_up 1" in live and "loss" in live
    dead = render_metrics(s)  # the with-block close()d it
    assert dead.strip().splitlines()[-1] == "actor_critic_up 0"
    assert "loss" not in dead  # no stale training row
    assert len(dead.strip().splitlines()) <= 3


def test_histogram_gauge_renders_prometheus_family(tmp_path):
    """A histogram snapshot inside a registered gauge row renders as a
    `_bucket/_sum/_count` family (policy-labeled), not as a skipped
    non-numeric value."""
    from actor_critic_tpu.telemetry import histo, sampler

    h = histo.Histogram((1.0, 10.0))
    h.observe_many([0.5, 5.0, 50.0])
    snap = h.snapshot(labels={"policy": "champ"})
    snap["metric"] = "latency_ms"
    key = sampler.register_gauge(
        "serving", lambda: {
            "requests_total": 3, "latency_ms_hist_champ": snap,
        },
    )
    try:
        with _session(tmp_path) as s:
            body = render_metrics(s)
    finally:
        sampler.unregister_gauge(key)
    fam = "actor_critic_serving_latency_ms"
    assert f'{fam}_bucket{{policy="champ",le="1"}} 1' in body
    assert f'{fam}_bucket{{policy="champ",le="+Inf"}} 3' in body
    assert f'{fam}_count{{policy="champ"}} 3' in body
    assert "actor_critic_serving_requests_total 3" in body
    # every line still parses as Prometheus text
    for line in body.splitlines():
        if line and not line.startswith("#"):
            assert _PROM_LINE.match(line), line


def test_concurrent_scrape_during_hot_swap_and_sampler_tick(tmp_path):
    """/metrics scraped continuously while (a) the policy store
    hot-swaps under live traffic and (b) the resource sampler ticks at
    high cadence: every scrape must be complete, parseable Prometheus
    text with monotone histogram counts — never a torn view or a 500."""
    import numpy as np

    from actor_critic_tpu import serving

    class _Eng:
        max_rows = 8

        def prepare_params(self, params):
            return {k: np.array(v) for k, v in params.items()}

        def act(self, params, obs):
            return np.asarray(obs)[:, 0] * params["scale"][0]

    store = serving.PolicyStore()
    store.register(
        "default", _Eng(), {"scale": np.ones(1, np.float32)}, slo_ms=50.0
    )
    session = telemetry.TelemetrySession(
        tmp_path, resource_interval_s=0.02, serve_port=0
    )
    gw = serving.ServeGateway(store, port=0, session=session)
    stop = None
    try:
        import threading

        stop = threading.Event()
        errors: list = []

        def traffic():
            i = 0
            while not stop.is_set():
                body = json.dumps(
                    {"obs": [[float(i + 1), 0.0]]}
                ).encode()
                req = urllib.request.Request(
                    gw.url + "/v1/act", data=body,
                    headers={"Content-Type": "application/json"},
                )
                try:
                    urllib.request.urlopen(req, timeout=10).read()
                except Exception as e:  # noqa: BLE001
                    errors.append(("traffic", repr(e)))
                    return
                i += 1

        def swapper():
            v = 0
            while not stop.is_set():
                v += 1
                store.swap(
                    "default",
                    {"scale": np.full(1, float(v + 1), np.float32)},
                    version=v,
                )
                time.sleep(0.002)

        threads = [
            threading.Thread(target=traffic),
            threading.Thread(target=swapper),
        ]
        for t in threads:
            t.start()
        last_count = 0.0
        deadline = time.monotonic() + 2.0
        scrapes = 0
        count_re = re.compile(
            r'actor_critic_serving_latency_ms_count\{policy="default"\} '
            r"(\S+)"
        )
        while time.monotonic() < deadline:
            status, text = _get(session.exporter.url + "/metrics")
            assert status == 200
            for line in text.splitlines():
                if line and not line.startswith("#"):
                    assert _PROM_LINE.match(line), line
            m = count_re.search(text)
            if m:
                count = float(m.group(1))
                assert count >= last_count  # counters never run backwards
                last_count = count
            scrapes += 1
        stop.set()
        for t in threads:
            t.join(10)
        assert not errors, errors[:3]
        assert scrapes >= 10 and last_count > 0
    finally:
        if stop is not None:
            stop.set()
        gw.close()
        session.close()


def test_validate_bind_refuses_non_loopback_without_distributed():
    from actor_critic_tpu.telemetry.exporter import validate_bind

    for host in ("127.0.0.1", "localhost", "::1"):
        validate_bind(host)  # loopback always fine
    with pytest.raises(ValueError, match="distributed"):
        validate_bind("0.0.0.0")
    with pytest.raises(ValueError):
        validate_bind("10.0.0.7")
    validate_bind("0.0.0.0", distributed=True)  # fleet scrape path


def test_cli_telemetry_bind_refused_without_distributed():
    import train as train_cli

    with pytest.raises(SystemExit, match="loopback"):
        train_cli.main(
            ["--preset", "a2c_cartpole", "--telemetry-dir", "/tmp/x",
             "--telemetry-bind", "0.0.0.0"]
        )
