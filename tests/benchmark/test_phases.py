"""benchmark/phases.py and the seven readers built on it (ISSUE 26): the rule
from a name stack to a phase, self time per phase per whole step, the share of
idle time the program's `ac:<span>` annotations cover; on hand-made traces and
on `benchmark/phase_fixture.json.gz`, two whole steps cut from a v5e trace of
`impala_pong.fleet` (my chip run, PR 26). No chip, no JAX device.
"""

import copy
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, phases, trace_reduce  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CELLS = ["impala_pong.fleet", "impala_pong.fleet2048"]
PHASE_READERS = {
    "rollout_device_ms": "rollout",
    "update_forward_ms": "forward",
    "final_obs_ms": "final_obs",
    "update_backward_ms": phases.BACKWARD,
    "adv_kernel_ms": "kernel",
}
READERS = [*PHASE_READERS, "phase_unscoped_pct", "idle_attributed_pct"]


def _ctx(traffic=None):
    traffic = {"step_module": "jit_train_step"} if traffic is None else traffic
    return harness.Ctx({"rate_metric": "fused_steps_per_s", "name": "t"}, {},
                       traffic, 0, 1.0, True, False, "")


def _read(name, run, ctx=None):
    return harness.load_module("layers", name).read(run, ctx or _ctx())


@pytest.fixture(scope="module")
def fixture():
    with gzip.open(os.path.join(BENCH, "phase_fixture.json.gz"), "rt") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(BENCH, "phase_fixture.expected.json")) as fh:
        return json.load(fh)


@pytest.fixture
def as_run(monkeypatch, tmp_path):
    """A `run` dict whose `trace_path` gives a plain trace: the file is a
    stand-in, `phases.load` is what reads it."""
    def make(trace):
        path = tmp_path / f"t{len(os.listdir(tmp_path))}.xplane.pb"
        path.write_bytes(b"")
        phases.load.cache_clear()
        phases._steps_of.cache_clear()
        monkeypatch.setattr(
            trace_reduce, "load_xplane", lambda p, **kw: trace)
        return {"trace_path": str(path)}
    yield make
    phases.load.cache_clear()
    phases._steps_of.cache_clear()


# -- the rule --------------------------------------------------------------

@pytest.mark.parametrize("stack, phase", [
    ("jit(train_step)/rollout/while/body/closed_call/ActorCriticDiscrete/torso/"
     "conv_0/conv_general_dilated:", "rollout"),
    ("jit(train_step)/jvp(forward)/ActorCriticDiscrete/torso/conv_0/"
     "conv_general_dilated:", "forward"),
    ("jit(train_step)/jvp(final_obs)/ActorCriticDiscrete/torso/div:", "final_obs"),
    ("jit(train_step)/jvp(bootstrap)/ActorCriticDiscrete/policy/dot_general:",
     "bootstrap"),
    ("jit(train_step)/transpose(jvp(forward))/ActorCriticDiscrete/torso/conv_0/"
     "conv_general_dilated:", phases.BACKWARD),
    ("jit(train_step)/jit(main)/transpose(jvp(final_obs))/mul:", phases.BACKWARD),
    ("jit(train_step)/jvp(advantage)/pallas_call:", "advantage"),
    ("pjit/jit(train_step)/jvp(loss)/reduce_sum:", "loss"),
    ("jit(train_step)/optimizer/jit(remainder)/rem:", "optimizer"),
    # The tree before the scopes, and a scope nobody entered in the table.
    ("jit(train_step)/while/body/closed_call/conv_general_dilated:", phases.UNSCOPED),
    ("jit(train_step)/jvp(ActorCriticDiscrete)/torso/div:", phases.UNSCOPED),
    ("jit(train_step)/jvp()/pallas_call:", phases.UNSCOPED),
    ("jit(train_step)/jvp(forwards)/mul:", phases.UNSCOPED),
    ("jit(train_step):", phases.UNSCOPED),
    ("", None),
    (None, None),
])
def test_phase_of_a_name_stack(stack, phase):
    assert phases.phase_of(stack) == phase


def test_every_scope_of_the_table_is_entered_by_the_program():
    """The table and the program say the same names: each scope is a literal
    `jax.named_scope("<scope>")` in `algos/impala.py` or `algos/common.py`."""
    source = ""
    for name in ("impala.py", "common.py"):
        with open(os.path.join(ROOT, "actor_critic_tpu", "algos", name)) as fh:
            source += fh.read()
    for scope in phases.PHASES:
        assert f'jax.named_scope("{scope}")' in source, scope


# -- a hand-made trace -----------------------------------------------------

def _op(name, start, dur, stack=None, category=None):
    stats = {}
    if stack:
        stats["tf_op"] = stack
    if category:
        stats["hlo_category"] = category
    return [name, float(start), float(dur), stats] if stats else \
        [name, float(start), float(dur)]


S = "jit(train_step)/"


def _hand_made(annotations=True):
    """Four executions of the step: the first and the last cut by the
    capture's edges (their module events clamped to them), two whole. A step:
    rollout while 40 with a body op 30 inside (which has a stack-less child of
    10), forward 20, final_obs 15 (a fusion with a stack-less child of 5),
    backward 12, the kernel 1 and its padding 2, unscoped 4. Idle: 3 and 21
    round the first cut step's last operation, 10 between the whole steps, of
    which `ac:log` covers 5, and 4 before the last cut step."""
    def step(t0):
        return [
            _op("%while.1", t0, 40, S + "rollout/while:"),
            _op("%fusion.1", t0 + 5, 30, S + "rollout/while/body/conv_general_dilated:"),
            _op("%copy.9", t0 + 10, 10),
            _op("%fusion.2", t0 + 40, 20, S + "jvp(forward)/conv_general_dilated:"),
            _op("%fusion.3", t0 + 60, 15, S + "jvp(final_obs)/conv_general_dilated:"),
            _op("%bitcast.3", t0 + 62, 5),
            _op("%fusion.4", t0 + 75, 12, S + "transpose(jvp(forward))/mul:"),
            _op("%vtrace.1 = custom-call()", t0 + 87, 1,
                S + "jvp(advantage)/pallas_call:", "custom-call"),
            _op("%pad.1", t0 + 88, 2, S + "jvp(advantage)/pad:"),
            _op("%copy.1", t0 + 90, 4),
        ]
    ops = [_op("%fusion.4", 60, 12, S + "transpose(jvp(forward))/mul:"),
           _op("%copy.1", 75, 4), *step(100), *step(204),
           _op("%while.1", 302, 6, S + "rollout/while:")]
    modules = [["jit_train_step(77)", 60.0, 19.0], ["jit_train_step(77)", 100.0, 94.0],
               ["jit_train_step(77)", 204.0, 94.0], ["jit_eval(3)", 150.0, 1.0],
               ["jit_train_step(77)", 302.0, 6.0]]
    host = [["ac:log", 197.0, 5.0], ["ac:update", 10.0, 2.0],
            ["PjitFunction(train_step)", 190.0, 20.0]] if annotations else \
        [["PjitFunction(train_step)", 190.0, 20.0]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]}]}


def test_per_step_on_a_hand_made_trace():
    steps = phases.per_step(_hand_made(), "jit_train_step")
    # The cut steps are left out; the two whole ones read the same.
    assert len(steps) == 2 and steps[0] == steps[1]
    assert steps[0] == {
        "step": 94.0, "rollout": 40.0, "forward": 20.0, "final_obs": 15.0,
        phases.BACKWARD: 12.0, "advantage": 3.0, "kernel": 1.0,
        phases.UNSCOPED: 4.0}
    # A stack-less event took its encloser's phase, not `unscoped`.
    attributed = phases.attributed(_hand_made()["planes"][0]["lines"][1]["events"])
    assert (110.0, 10.0, "rollout", False) in attributed
    assert (162.0, 5.0, "final_obs", False) in attributed
    # The closure: the phases sum to the step's device time.
    assert sum(v for k, v in steps[0].items() if k not in ("step", "kernel")) == 94.0


@pytest.mark.parametrize("reader, want", [
    ("rollout_device_ms", 40e-6), ("update_forward_ms", 20e-6),
    ("final_obs_ms", 15e-6), ("update_backward_ms", 12e-6),
    ("adv_kernel_ms", 1e-6), ("phase_unscoped_pct", 100 * 4 / 94),
    ("idle_attributed_pct", 100 * 5 / 38),
])
def test_reader_on_a_hand_made_trace(reader, want, as_run):
    assert _read(reader, as_run(_hand_made())) == pytest.approx(want)


def test_a_chunked_dispatch_is_divided_by_its_chunk(as_run):
    ctx = _ctx({"step_module": "jit_train_step", "iterations_per_dispatch": 4})
    assert _read("rollout_device_ms", as_run(_hand_made()), ctx) == \
        pytest.approx(10e-6)


def test_idle_attributed_pct_is_none_without_annotations_and_counts_all_idle():
    assert phases.idle_attributed_pct(_hand_made(annotations=False)) is None
    trace = _hand_made()
    # Idle gaps: 72-75 (3), 79-100 (21), 194-204 (10), 298-302 (4); `ac:log`
    # covers 197-202.
    trace["planes"][1]["lines"][0]["events"] = [["ac:log", 197.0, 5.0]]
    assert phases.idle_attributed_pct(trace) == pytest.approx(100 * 5 / 38)
    trace["planes"][1]["lines"][0]["events"] += [
        ["bench:arm", 70.0, 31.0], ["ac:update", 90.0, 5.0]]
    assert phases.idle_attributed_pct(trace) == pytest.approx(100 * 29 / 38)


# -- nothing to read -------------------------------------------------------

def _without_scopes(trace):
    """The same trace as the tree before the scopes wrote it."""
    bare = copy.deepcopy(trace)
    for plane in bare["planes"]:
        for line in plane["lines"]:
            for event in line["events"]:
                if len(event) > 3 and "tf_op" in event[3]:
                    stack = event[3]["tf_op"]
                    for scope in phases.PHASES:
                        stack = stack.replace(f"jvp({scope})", "jvp(ActorCriticDiscrete)")
                        stack = stack.replace(f"/{scope}/", "/")
                    event[3]["tf_op"] = stack
            line["events"] = [e for e in line["events"]
                              if not str(e[0]).startswith("ac:")]
    return bare


@pytest.mark.parametrize("reader", READERS)
def test_reader_returns_none_where_there_is_nothing_to_read(reader, as_run):
    empty = {"device": {"kind": "cpu", "count": 1}, "end_to_end": {},
             "window_iters": (0, 0)}
    assert _read(reader, empty) is None
    assert _read(reader, {**empty, "trace_path": None}) is None
    assert _read(reader, {**empty, "trace_path": "/nonexistent/x.xplane.pb"}) is None
    # The parent's trace: `transpose(` is there, no scope and no `ac:` is.
    bare = _without_scopes(_hand_made())
    assert phases.per_step(bare, "jit_train_step")[0][phases.BACKWARD] == 12.0
    assert _read(reader, as_run(bare)) is None
    if reader != "idle_attributed_pct":
        assert _read(reader, as_run(_hand_made()), _ctx({})) is None
        assert _read(reader, as_run(_hand_made()),
                     _ctx({"step_module": "jit_full"})) is None


@pytest.mark.parametrize("reader", READERS)
def test_reader_is_in_the_manifest_for_both_fleets(reader):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    entry = next(e for e in manifest["per_layer"] if e["name"] == reader)
    assert entry["workloads"] == CELLS and entry["moves"] == "fused_steps_per_s"
    # Appended: the eight that were there come first, in their order.
    names = [e["name"] for e in manifest["per_layer"]]
    assert names[:8] == [
        "device_idle_pct", "peak_hbm_gb", "step_device_ms", "mfu_pct",
        "enqueue_ms", "compiles_in_window", "cache_miss_count", "adv_kernel_calls"]
    assert names[8:] == READERS


# -- the recorded chip trace -----------------------------------------------

def test_fixture_is_two_whole_steps_round_a_log_row_gap(fixture, expected):
    plane = trace_reduce.device_planes(fixture)[0]
    spans = phases.whole_steps(plane, "jit_train_step")
    assert len(spans) == 2
    assert (spans[1][0] - spans[0][1]) / 1e6 == pytest.approx(
        expected["gap_ms"], rel=1e-9)
    assert os.path.getsize(os.path.join(BENCH, "phase_fixture.json.gz")) < 300 * 1024


@pytest.mark.parametrize("reader", READERS)
def test_reader_on_the_recorded_chip_trace(reader, fixture, expected, as_run):
    got = _read(reader, as_run(fixture))
    assert got == pytest.approx(expected["readers"][reader], rel=1e-9)


def test_closure_on_the_recorded_chip_trace(fixture, expected):
    """The four big phases, the small scoped ones and the unscoped rest close
    on the step's device time (`step_device_ms`) within 1%."""
    steps = phases.per_step(fixture, "jit_train_step")
    assert len(steps) == 2
    for step in steps:
        parts = sum(v for k, v in step.items() if k not in ("step", "kernel"))
        assert parts == pytest.approx(step["step"], rel=0.01)
        for scope in phases.PHASES:
            assert step.get(scope, 0.0) > 0.0, scope
    assert [s["step"] / 1e6 for s in steps] == pytest.approx(
        expected["step_ms"], rel=1e-9)
    assert expected["readers"]["phase_unscoped_pct"] < 6.0


def test_stackless_events_inherit_on_the_recorded_chip_trace(fixture, expected):
    plane = trace_reduce.device_planes(fixture)[0]
    ops = next(l for l in plane["lines"] if l["name"] == "XLA Ops")["events"]
    bare = [e for e in ops if len(e) < 4 or not e[3].get("tf_op")]
    assert len(bare) == expected["stackless_events"] > 0
    # Flat, every stack-less event would be `unscoped`; nested, only those
    # that no event encloses are.
    flat = sum(phases.attributed([e])[0][1] for e in bare)
    total = sum(v for s in phases.per_step(fixture, "jit_train_step")
                for k, v in s.items() if k not in ("step", "kernel"))
    unscoped = sum(s.get(phases.UNSCOPED, 0.0)
                   for s in phases.per_step(fixture, "jit_train_step"))
    assert unscoped <= flat
    assert 100 * unscoped / total == pytest.approx(
        expected["readers"]["phase_unscoped_pct"], rel=1e-9)


def test_a_cut_step_is_left_out_of_the_recorded_chip_trace(fixture, expected, as_run):
    """The fixture as cut holds a sliver of the step before and of the step
    after, their module events clamped to the sliver as the profiler clamps a
    step to the capture's edge: neither counts. A capture that had started
    half a step later leaves one whole step, and the readers give its values."""
    plane = trace_reduce.device_planes(fixture)[0]
    modules = next(l for l in plane["lines"] if l["name"] == "XLA Modules")["events"]
    assert len(modules) == 4
    assert len(phases.whole_steps(plane, "jit_train_step")) == 2
    late = copy.deepcopy(fixture)
    plane = trace_reduce.device_planes(late)[0]
    spans = phases.whole_steps(plane, "jit_train_step")
    middle = 0.5 * (spans[0][0] + spans[0][1])
    for line in plane["lines"]:
        line["events"] = [e for e in line["events"] if e[1] + e[2] > middle]
        if line["name"] == "XLA Ops":
            line["events"] = [e for e in line["events"] if e[1] >= middle]
            first = min(e[1] for e in line["events"])
    for line in plane["lines"]:
        if line["name"] == "XLA Modules":  # clamped to the capture's start
            head = line["events"][0]
            head[1], head[2] = first, head[1] + head[2] - first
    steps = phases.per_step(late, "jit_train_step")
    assert len(steps) == 1
    assert steps[0]["step"] / 1e6 == pytest.approx(expected["step_ms"][1], rel=1e-9)
    assert _read("rollout_device_ms", as_run(late)) == pytest.approx(
        expected["steps"][1]["rollout"], rel=1e-9)


def test_idle_attribution_on_the_recorded_chip_trace(fixture, expected):
    assert phases.idle_attributed_pct(fixture) == pytest.approx(
        expected["readers"]["idle_attributed_pct"], rel=1e-9)
    assert expected["readers"]["idle_attributed_pct"] > 80.0
    assert phases.idle_attributed_pct(_without_scopes(fixture)) is None
    # `breakdown.idle_gaps` names the longest gap by the same annotations.
    got = trace_reduce.reduce(fixture)
    assert got["idle_gaps"][0][0] == expected["longest_gap_label"]
    assert got["idle_gaps"][0][0].startswith("ac:")
    assert trace_reduce.reduce(_without_scopes(fixture))["idle_gaps"][0][0] \
        .startswith("unattributed")
