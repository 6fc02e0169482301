"""Spans over start-up: from `train.main`'s parsed arguments to its first
step (`startup` > `init_env`, `init_state`; the first `update` >
`jit_trace`, `jit_lower`, `compile`), the AOT warm-up's `warmup` spans, and
the `gc` hook for full collections.
"""

import gc
import json
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from actor_critic_tpu import telemetry
from actor_critic_tpu.telemetry import session as session_mod
from actor_critic_tpu.utils import compile_cache


def _read(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def _complete(directory):
    return [e for e in _read(os.path.join(directory, "spans.jsonl"))
            if e["ph"] == "X"]


def _inside(child, parent):
    return (parent["ts"] - 1 <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1)


@pytest.fixture(scope="module")
def two_runs(tmp_path_factory):
    """`train.main` twice on one fresh cache, each with its own telemetry
    directory; the warm-up runs before the loop's first step, so its spans
    fall inside each run."""
    import train

    root = tmp_path_factory.mktemp("startup")
    real_run_fused = train.run_fused

    def run_fused(*args, **kwargs):
        for runner in compile_cache.started_warmups():
            runner.wait(300)
        return real_run_fused(*args, **kwargs)

    dirs = []
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        mp.setattr(train, "run_fused", run_fused)
        with compile_cache.temporary_cache(root / "snapshot"):
            for n in range(2):
                tel = str(root / f"tel{n}")
                assert train.main([
                    "--preset", "impala_pong", "--set", "num_envs=8",
                    "--iterations", "2", "--log-every", "1", "--quiet",
                    "--metrics", str(root / f"m{n}.jsonl"),
                    "--compile-cache-dir", str(root / "cache"),
                    "--telemetry-dir", tel,
                ]) == 0
                dirs.append(tel)
    assert telemetry.current() is None and telemetry.open_spans() == []
    return dirs


def _first_update(spans):
    return min((e for e in spans if e["name"] == "update"),
               key=lambda e: e["args"]["it"])


def test_startup_holds_env_and_state(two_runs):
    spans = _complete(two_runs[0])
    (startup,) = [e for e in spans if e["name"] == "startup"]
    # Opened before the session's tracer existed: its start is before ts 0.
    assert startup["ts"] < 0
    for name in ("init_env", "init_state"):
        (child,) = [e for e in spans if e["name"] == name]
        assert child["tid"] == startup["tid"] and _inside(child, startup)
    update = _first_update(spans)
    assert update["tid"] == startup["tid"]
    assert startup["ts"] + startup["dur"] <= update["ts"]


@pytest.mark.parametrize("name", ["jit_trace", "jit_lower", "compile"])
def test_first_update_holds_the_steps_trace_lowering_and_load(two_runs, name):
    spans = _complete(two_runs[0])
    update = _first_update(spans)
    inner = [e for e in spans if e["name"] == name
             and e["tid"] == update["tid"] and _inside(e, update)]
    assert inner, name
    if name == "compile":
        args = inner[-1]["args"]
        assert args["name"] == "jit_train_step" and args["thread"] == "MainThread"
        assert {"cache_hit", "retrieve_s", "compile_s"} <= set(args)


def test_second_run_loads_the_step_from_the_cache(two_runs):
    spans = _complete(two_runs[1])
    update = _first_update(spans)
    (load,) = [e for e in spans if e["name"] == "compile"
               and e["tid"] == update["tid"] and _inside(e, update)]
    assert load["args"]["cache_hit"] is True
    assert load["args"]["retrieve_s"] > 0


def test_warmup_is_a_span_on_its_own_thread(two_runs):
    spans = _complete(two_runs[0])
    update = _first_update(spans)
    warmups = [e for e in spans if e["name"] == "warmup"]
    assert warmups and all(e["tid"] != update["tid"] for e in warmups)
    assert {e["args"]["entry"] for e in warmups} == {"impala.make_train_step"}
    # The warm-up's own compile is nested in it, on its thread.
    assert any(e["name"] == "compile" and e["tid"] == warmups[0]["tid"]
               and _inside(e, warmups[0]) for e in spans)
    kinds = {e["kind"] for e in _read(os.path.join(two_runs[0], "events.jsonl"))}
    assert "compile" in kinds
    assert not kinds & {"warmup_compile", "warmup_done"}


def test_a_failed_warmup_entry_is_a_span_with_its_error(tmp_path):
    def boom():
        raise RuntimeError("planted")

    with telemetry.TelemetrySession(tmp_path, sample_resources=False,
                                    profile=False, flight=False):
        runner = compile_cache.WarmupRunner([("planted.entry", boom)]).start()
        assert runner.wait(30)
    (span,) = [e for e in _complete(tmp_path) if e["name"] == "warmup"]
    assert span["args"]["entry"] == "planted.entry"
    assert "planted" in span["args"]["error"]
    assert runner.results == [{"entry": "planted.entry",
                               "error": span["args"]["error"]}]


class _Annotation:
    def __init__(self, opened, label):
        self.opened, self.label = opened, label

    def __enter__(self):
        self.opened.append(self.label)

    def __exit__(self, *exc):
        self.opened.append("/" + self.label)


def test_compile_phases_are_spans_mirrored_as_ac_names(tmp_path, monkeypatch):
    from actor_critic_tpu.telemetry import profiler

    assert profiler.ensure_compile_introspection()
    opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda label: _Annotation(opened, label))
    x = jnp.arange(5.0).block_until_ready()

    @jax.jit
    def fresh(x):
        return jnp.tanh(x) * 3.0 + 1.0

    with telemetry.TelemetrySession(tmp_path, sample_resources=False,
                                    flight=False):
        fresh(x).block_until_ready()
    for name in ("jit_trace", "jit_lower", "compile"):
        assert "ac:" + name in opened and "/ac:" + name in opened, opened
    spans = _complete(tmp_path)
    traces = [e for e in spans if e["name"] == "jit_trace"]
    # The jitted `jnp` operations it calls are traced inside it: no span each.
    assert [e["args"]["fun"] for e in traces] == ["fresh"]
    (load,) = [e for e in spans if e["name"] == "compile"]
    assert load["args"]["name"] == "jit_fresh"
    assert telemetry.open_spans() == []


def test_a_full_collection_is_a_gc_span_mirrored_as_ac_gc(tmp_path, monkeypatch):
    opened = []
    enabled = gc.isenabled()
    gc.disable()  # the one collection is the test's own
    try:
        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            lambda label: _Annotation(opened, label))
        with telemetry.TelemetrySession(tmp_path, sample_resources=False,
                                        profile=False, flight=False):
            gc.collect()
    finally:
        if enabled:
            gc.enable()
    assert opened == ["ac:gc", "/ac:gc"]
    (span,) = [e for e in _complete(tmp_path) if e["name"] == "gc"]
    assert span["args"]["generation"] == 2 and span["args"]["collected"] >= 0
    assert span["tid"] == threading.get_ident() & 0x7FFFFFFF


def test_gc_hook_lives_only_while_a_session_is_installed(tmp_path):
    before = list(gc.callbacks)
    telemetry.set_current(None)
    assert gc.callbacks == before
    with telemetry.TelemetrySession(tmp_path, sample_resources=False,
                                    profile=False, flight=False):
        assert session_mod._on_gc in gc.callbacks
    assert gc.callbacks == before


def test_gc_spans_from_many_threads_are_each_written_once(tmp_path):
    """Full collections triggered on eight threads that write spans of their
    own, switching every microsecond: no deadlock on the tracer's lock, no
    torn line, and one `gc` span a collection."""
    import sys

    counted = []

    def count(phase, info):
        if phase == "stop" and info["generation"] == 2:
            counted.append(1)

    def work():
        for i in range(40):
            with telemetry.span("update", it=i):
                cycle = [[] for _ in range(50)]
                cycle.append(cycle)
                if i % 8 == 0:
                    gc.collect()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with telemetry.TelemetrySession(tmp_path, sample_resources=False,
                                        profile=False, flight=False):
            gc.callbacks.append(count)
            threads = [threading.Thread(target=work) for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            gc.callbacks.remove(count)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    spans = _complete(tmp_path)  # every line parses
    assert sum(e["name"] == "update" for e in spans) == 8 * 40
    # A collection asked for while another runs returns at once, so the
    # count is the collector's own.
    assert sum(e["name"] == "gc" for e in spans) == len(counted) > 0
