"""One timeline for the fused step (ISSUE 26), the program's side, on the CPU:

(a) the fused IMPALA step carries a `jax.named_scope` round each phase, the
    pass over `obs` and the pass over `final_obs` under different ones, and
    the scopes are metadata (the compiled step has the same operations);
(b) both `pallas_call` sites of `ops/pallas_scan.py` carry their kernel's name;
(c) a `telemetry.span` lands in a `jax.profiler` capture as `ac:<span>`, on
    the profiler's clock, only while a session is installed.

What reads these names is `benchmark/phases.py` (tests/benchmark/test_phases.py).
"""

import collections
import contextlib
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from actor_critic_tpu import telemetry  # noqa: E402
from actor_critic_tpu.ops import pallas_scan  # noqa: E402
from actor_critic_tpu.telemetry import session as session_mod  # noqa: E402
from benchmark import phases, trace_reduce  # noqa: E402


# -- (a) phase scopes -----------------------------------------------------

def _step_hlo() -> str:
    """The compiled step of `impala_pong` at a tiny fleet (E=4, T=3, 36 px),
    as text: `op_name` is the name stack the profiler shows as `tf_op`."""
    import train
    from actor_critic_tpu import config as config_mod

    preset = config_mod.resolve(
        "impala_pong", None, None, {"num_envs": "4", "rollout_steps": "3"},
        env_overrides={"size": 36})
    env, fused = train.build_env(
        preset.env, preset.algo, preset.config, 0, env_kwargs=preset.env_kwargs)
    assert fused
    mod = train.fused_module(preset.algo)
    state = mod.init_state(env, preset.config, jax.random.key(0))
    step = jax.jit(mod.make_train_step(env, preset.config))
    return step.lower(state).compile().as_text()


@pytest.fixture(scope="module")
def step_hlo():
    return _step_hlo()


def _op_names(hlo: str, opcode: str = r"[a-z\-]+") -> list[str]:
    return re.findall(rf" {opcode}\(.*?op_name=\"([^\"]+)\"", hlo)


def test_the_step_carries_every_scope_of_the_table(step_hlo):
    found = collections.Counter(phases.phase_of(s) for s in _op_names(step_hlo))
    for scope in phases.PHASES:
        assert found[scope], f"no operation of the step runs under {scope!r}"
    assert found[phases.BACKWARD]
    # The rule reads the scope through JAX's own wrappers.
    assert any(s.startswith("jit(train_step)/jvp(forward)/")
               for s in _op_names(step_hlo))
    assert any(s.startswith("jit(train_step)/transpose(jvp(forward))/")
               for s in _op_names(step_hlo))
    assert any(s.startswith("jit(train_step)/rollout/while/body/")
               for s in _op_names(step_hlo))
    # The truncation bootstrap is a loop over the truncated rows (ISSUE
    # 27): the scope is on its body, where the critic's operations are.
    assert any(s.startswith("jit(train_step)/jvp(final_obs)/while/body/")
               for s in _op_names(step_hlo))


def test_obs_and_final_obs_convolutions_carry_different_scopes(step_hlo):
    convs = collections.Counter(
        phases.phase_of(s) for s in _op_names(step_hlo, "convolution"))
    # Three convolutions a forward pass; the backward's are transposed.
    for scope in ("rollout", "forward", "final_obs", "bootstrap"):
        assert convs[scope] == 3, convs
    assert convs[phases.BACKWARD] >= 2
    assert convs[phases.UNSCOPED] == 0 and convs[None] == 0
    # final_obs's three run inside the loop over the truncated rows.
    in_loop = [s for s in _op_names(step_hlo, "convolution")
               if phases.phase_of(s) == "final_obs"]
    assert all("/jvp(final_obs)/while/body/" in s for s in in_loop), in_loop


def test_scopes_are_metadata_the_compiled_step_is_the_same(step_hlo, monkeypatch):
    def opcodes(hlo):
        return collections.Counter(re.findall(r"= \S+ ([a-z\-]+)\(", hlo))

    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _step_hlo()
    assert not any(phases.phase_of(s) in phases.PHASES for s in _op_names(bare))
    assert opcodes(bare) == opcodes(step_hlo)


# -- (b) kernel names -----------------------------------------------------

def _pallas_names(fn, *args) -> list[str]:
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


@pytest.mark.parametrize("on_tpu", [False, True], ids=["interpret", "compiled"])
@pytest.mark.parametrize("kernel", ["gae", "lambda_returns", "vtrace"])
def test_each_pallas_call_carries_its_kernels_name(kernel, on_tpu, monkeypatch):
    # Tracing only: `on_tpu` decides `interpret=`, the name is the same.
    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: on_tpu)
    x = jnp.zeros((8, 128), jnp.float32)
    boot = jnp.zeros((128,), jnp.float32)
    if kernel == "vtrace":
        fn = lambda: pallas_scan.vtrace(x, x, x, x, x, boot, 0.99)  # noqa: E731
    else:
        fn = lambda: getattr(pallas_scan, kernel)(x, x, x, boot, 0.99, 0.95)  # noqa: E731
    assert _pallas_names(fn) == [kernel]


# -- (c) the program's spans on the profiler's clock ----------------------

def _capture(log_dir: str):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return jax.profiler.trace(log_dir, profiler_options=opts)


def test_a_span_lands_in_the_profilers_trace_as_ac_name(tmp_path):
    with telemetry.TelemetrySession(
            tmp_path / "telemetry", sample_resources=False, profile=False,
            flight=False):
        with _capture(str(tmp_path / "trace")):
            with telemetry.span("log", it=7):
                time.sleep(0.02)
            # Measured elsewhere, emitted afterwards: spans.jsonl only.
            telemetry.complete_span("env_step_worker", time.perf_counter(), 0.01)
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(str(tmp_path / "trace")))
    host = trace_reduce.host_events(trace)
    mirrored = [e for e in host if str(e[0]).startswith("ac:")]
    assert [e[0] for e in mirrored] == ["ac:log"]
    _, start, dur = mirrored[0][:3]
    with open(tmp_path / "telemetry" / "spans.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r.get("name") == "log" and r.get("ph") == "X")
    assert row["args"] == {"it": 7}
    assert abs(dur / 1e3 - row["dur"]) < 1000.0  # both microseconds: within 1 ms
    assert dur >= 0.02e9
    assert any(r.get("name") == "env_step_worker" for r in rows)
    # A gap of the device inside the span is the span's.
    gap = (start + 0.25 * dur, start + 0.75 * dur)
    assert trace_reduce.label_gap(gap, host) == "ac:log"
    assert phases.idle_attributed_pct(trace) is None  # no device in a CPU trace


def test_without_a_session_no_annotation_is_made():
    assert telemetry.current() is None and session_mod._ANNOTATION is None
    with telemetry.span("log") as span:
        assert span._annotation is None
    assert telemetry.open_spans() == []


@pytest.mark.parametrize("with_log_due", [True, False])
def test_the_loop_waits_for_the_device_under_its_own_span(tmp_path, with_log_due):
    """Where the caller says which dispatches write a row, the traced loop
    takes the device sync itself as `device_wait` (the dispatch before, then
    this one: no wait longer than a dispatch), so `log` begins when the
    device is done (a span that began before a capture is not in it)."""
    from actor_critic_tpu.utils.checkpoint import checkpointed_train

    step = jax.jit(lambda s: (s + 1, {"loss": s * 0.5}))
    logged = []
    with telemetry.TelemetrySession(
            tmp_path, sample_resources=False, profile=False, flight=False):
        checkpointed_train(
            step, jnp.zeros(()), 4, log_fn=lambda it, m: logged.append(it),
            log_due=(lambda it: it % 2 == 0) if with_log_due else None)
    with open(tmp_path / "spans.jsonl") as fh:
        spans = [json.loads(line) for line in fh]
    order = [(s["name"], s["args"]["it"]) for s in spans
             if s.get("ph") == "X" and s["name"] in ("device_wait", "log")]
    want = [("log", 1), ("device_wait", 1), ("device_wait", 2), ("log", 2),
            ("log", 3), ("device_wait", 3), ("device_wait", 4), ("log", 4)]
    if not with_log_due:
        want = [w for w in want if w[0] == "log"]
    assert order == want and logged == [1, 2, 3, 4]


def test_an_untraced_loop_takes_no_wait_of_its_own(monkeypatch):
    """Without a session the sync stays where it was, in `log_fn`: the
    measured, untraced loop is the loop as it ran before the span existed."""
    from actor_critic_tpu.utils import checkpoint

    waits = []
    monkeypatch.setattr(checkpoint.jax, "block_until_ready", waits.append)
    step = jax.jit(lambda s: (s + 1, {"loss": s * 0.5}))
    assert telemetry.current() is None
    checkpoint.checkpointed_train(
        step, jnp.zeros(()), 4, log_fn=lambda it, m: None,
        log_due=lambda it: True)
    assert waits == []
