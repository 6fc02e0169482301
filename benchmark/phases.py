"""One timeline for the fused step: from the names the program puts on its
work to device time per phase, a step.

The program names its phases with `jax.named_scope` (`algos/impala.py`,
`algos/common.py`) and its kernels with `pallas_call(name=)`
(`ops/pallas_scan.py`); the profiler keeps the scope as the first component
of an operation's name stack (`tf_op` on the `XLA Ops` event's metadata).
This file holds the table of phases, the one rule from a stack to a phase,
and the reduction the readers `layers/{rollout_device,update_forward,
final_obs,update_backward,adv_kernel}_ms.py`, `phase_unscoped_pct.py` and
`idle_attributed_pct.py` share. It is not under `layers/`: every file there
is a reader.

The rule (`phase_of`), from the stacks as they come out of a v5e trace
(`jit(train_step)/jvp(forward)/ActorCriticDiscrete/torso/conv_0/...:`,
`jit(train_step)/transpose(jvp(forward))/...`, `jit(train_step)/rollout/
while/body/...`, `jit(train_step)/jvp(advantage)/pallas_call:`):

- `transpose(` anywhere in the stack -> `backward` (JAX wraps the forward's
  scope as `transpose(jvp(<scope>))`; the backward pass has no scope of its
  own);
- else the first component after the `jit(...)` wrappers, without its
  `jvp(...)` wrapper, where the table has it;
- an event WITHOUT a stack takes the phase of the event that encloses it
  on the `XLA Ops` line (a fusion's child), where one does;
- anything else is `unscoped`: the readers' own honesty check
  (`phase_unscoped_pct`), so that a renamed scope shows as a number.

Per whole step: the `XLA Modules` events named by the traffic file's
`step_module` give the step intervals; a step that the capture's edge cut
(its module event reaches the edge of the `XLA Ops` line's extent) is left out;
an operation belongs to the step its start falls in; a reader's value is the
median over the whole steps, as `step_device_ms` is a median.
"""

from __future__ import annotations

import functools
import os
import re
from typing import Optional

from benchmark import harness, trace_reduce

# Scope -> what it holds (ISSUE 26's table; the program's side is
# `algos/impala.py::impala_loss` / `make_train_step` and `algos/common.py`).
PHASES = {
    "rollout": "common.rollout_scan: the lax.scan and everything in its body",
    "forward": "impala_loss: apply_fn(params, obs), log-prob, entropy",
    "bootstrap": "apply_fn(params, bootstrap_obs)",
    "final_obs": "reshape of traj.final_obs, apply_fn over it, truncation_bootstrap_rewards",
    "advantage": "common.corrected_advantages / gae_targets: the Pallas seam and its padding",
    "loss": "the three loss terms",
    "optimizer": "pmean_tree, opt.update, apply_updates, the actor refresh",
}
BACKWARD = "backward"
UNSCOPED = "unscoped"
CATEGORY_STAT = "hlo_category"
KERNEL_CATEGORY = "custom-call"
STATS = (trace_reduce.NAME_STACK_STAT, CATEGORY_STAT)

_JVP = re.compile(r"^jvp\((.*)\)$")


def phase_of(stack: Optional[str]) -> Optional[str]:
    """The phase a name stack belongs to (module docstring), `UNSCOPED`
    where the table cannot place it, None where there is no stack."""
    if not stack:
        return None
    stack = str(stack).rstrip(":")
    if "transpose(" in stack:
        return BACKWARD
    for part in stack.split("/"):
        if not part or trace_reduce._WRAPPER.match(part):
            continue
        m = _JVP.match(part)
        first = m.group(1) if m else part
        return first if first in PHASES else UNSCOPED
    return UNSCOPED


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """The trace with name stacks and categories, parsed once for all
    readers of a run."""
    return trace_reduce.load_xplane(
        path, keep_lines=(trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE),
        stats=STATS)


def trace_of(run: dict) -> Optional[dict]:
    """The run's own trace, or None where the run took none."""
    path = run.get("trace_path")
    if not path or not os.path.isfile(path):
        return None
    return load(path)


def _line_events(plane: dict, name: str) -> list[list]:
    line = trace_reduce._line(plane, name)
    return line["events"] if line is not None else []


def attributed(events: list[list]) -> list[tuple[float, float, str, bool]]:
    """(start, self time, phase, is a kernel) of each event of one `XLA Ops`
    line: self time as `trace_reduce.self_times` takes it (the interval
    minus what the nested events cover), the phase by `phase_of`, inherited
    from the enclosing event where the event has no stack."""
    out = []
    stack: list[list] = []  # [end, self_ns, phase, start, kernel]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _, self_ns, phase, start, kernel = stack.pop()
            out.append((start, max(self_ns, 0.0), phase, kernel))

    for event in sorted(events, key=lambda e: (e[1], -e[2])):
        _, start, dur, *rest = event
        stats = rest[0] if rest else {}
        close(start)
        phase = phase_of(stats.get(trace_reduce.NAME_STACK_STAT))
        if phase is None:
            phase = stack[-1][2] if stack else UNSCOPED
        if stack:
            stack[-1][1] -= dur
        stack.append([start + dur, dur, phase, start,
                      stats.get(CATEGORY_STAT) == KERNEL_CATEGORY])
    close(float("inf"))
    return out


def whole_steps(plane: dict, step_module: str) -> list[tuple[float, float]]:
    """[start, end) of each execution of `step_module` that lies strictly
    inside the extent of the plane's `XLA Ops` line: an operation began
    before it and one ended after it. The profiler clamps a step that the
    capture's edge cut to that edge (its module event starts with the
    line's first operation, or ends with its last), so such a step is out;
    so is, at most, one whole step at either end of a capture that began
    or ended on an idle device."""
    ops = [e for e in _line_events(plane, trace_reduce.OPS_LINE) if e[2] > 0]
    if not ops:
        return []
    first = min(e[1] for e in ops)
    last = max(e[1] + e[2] for e in ops)
    return sorted(
        (start, start + dur)
        for name, start, dur, *_ in _line_events(plane, trace_reduce.MODULES_LINE)
        if trace_reduce.module_key(name) == step_module
        and start > first and start + dur < last)


def per_step(trace: dict, step_module: str) -> list[dict[str, float]]:
    """For every whole step of every device plane: nanoseconds of self time
    per phase, the kernels under `advantage` once more under `kernel`, and
    the step's own duration under `step`."""
    steps = []
    for plane in trace_reduce.device_planes(trace):
        spans = whole_steps(plane, step_module)
        if not spans:
            continue
        found = [{"step": end - start} for start, end in spans]
        i = 0
        for start, self_ns, phase, kernel in sorted(
                attributed(_line_events(plane, trace_reduce.OPS_LINE))):
            while i < len(spans) and start >= spans[i][1]:
                i += 1
            if i == len(spans):
                break
            if start < spans[i][0]:
                continue
            found[i][phase] = found[i].get(phase, 0.0) + self_ns
            if kernel and phase == "advantage":
                found[i]["kernel"] = found[i].get("kernel", 0.0) + self_ns
        steps.extend(found)
    return steps


@functools.lru_cache(maxsize=2)
def _steps_of(path: str, step_module: str) -> tuple:
    return tuple(per_step(load(path), step_module))


def steps_of(run: dict, ctx) -> Optional[list[dict[str, float]]]:
    """The run's whole steps, or None where there is nothing to read: no
    trace, no step module, no whole step, or a program without the scopes
    (no operation of any whole step runs under a scope of the table, as in
    the tree before the scopes, whose backward pass alone could be placed)."""
    name = ctx.param("step_module")
    if trace_of(run) is None or name is None:
        return None
    steps = list(_steps_of(run["trace_path"], name))
    if not any(PHASES.keys() & step.keys() for step in steps):
        return None
    return steps


def phase_ms(run: dict, ctx, phase: str) -> Optional[float]:
    """Median over the whole steps of the phase's device time, in
    milliseconds an iteration (a chunked dispatch divided by its chunk)."""
    steps = steps_of(run, ctx)
    if steps is None:
        return None
    per_dispatch = harness.median([s.get(phase, 0.0) for s in steps]) / 1e6
    return per_dispatch / float(ctx.param("iterations_per_dispatch", 1))


def unscoped_pct(run: dict, ctx) -> Optional[float]:
    """Device time of the whole steps that the table cannot place, over all
    device time of the whole steps."""
    steps = steps_of(run, ctx)
    if steps is None:
        return None
    total = sum(v for s in steps for k, v in s.items()
                if k not in ("step", "kernel"))
    return 100.0 * sum(s.get(UNSCOPED, 0.0) for s in steps) / total


def idle_attributed_pct(trace: dict) -> Optional[float]:
    """Of the device's idle time inside the trace's extent (the gaps between
    the merged `XLA Ops` intervals), the share that the union of the
    program's `ac:*` and the harness's `bench:*` annotations covers. None
    where the trace has no such annotation, no device or no idle time."""
    marks = trace_reduce.merged(
        (start, start + dur) for name, start, dur, *_
        in trace_reduce.host_events(trace)
        if str(name).startswith(trace_reduce.GAP_PREFIXES))
    if not marks:
        return None
    idle = covered = 0.0
    for plane in trace_reduce.device_planes(trace):
        busy = trace_reduce.merged(
            (e[1], e[1] + e[2])
            for e in _line_events(plane, trace_reduce.OPS_LINE) if e[2] > 0)
        i = 0  # both lists are sorted and disjoint: one sweep
        for (_, gs), (ge, _) in zip(busy, busy[1:]):
            idle += ge - gs
            while i < len(marks) and marks[i][1] <= gs:
                i += 1
            j = i
            while j < len(marks) and marks[j][0] < ge:
                covered += min(ge, marks[j][1]) - max(gs, marks[j][0])
                j += 1
    return 100.0 * covered / idle if idle > 0 else None
