"""What every driver shares: finding files by name, the device check, the
arithmetic from metric rows to a rate, the comparison that decides `correct`,
the row watcher and the trace window.

Driven by data: a cell is `workloads/<cell>.json`, which names its
configuration (`configs/<name>.json`), its traffic mix
(`traffic/<name>.json`), its driver (`drivers/<name>.py`) and its end-to-end
metrics; a per-layer metric is a reader, `layers/<metric>.py`. Nothing here
lists cells, metrics or readers.

Which cell reads which per-layer metric is said once (`per_layer_names`). For
an accepted cell, one that `BENCHMARK.json` lists, the manifest says it: the
`per_layer` entries whose `workloads` hold the cell, and the cell's own file
has no `per_layer` key. So a per-layer metric for an accepted cell = one new
`benchmark/layers/<name>.py` + one new entry in `BENCHMARK.json`, and no edit
to any file that is there. A cell that is only a file (the three held out of
the manifest by the memory floor, a test's own) differs: its file carries its
own `per_layer` list, since no manifest entry can name it.

What a reader gets: the driver's `run` dict (end-to-end numbers, rows, spans,
compile records, the reduced trace under `trace`, and under `trace_path` the
profiler's `.xplane.pb` itself, for a reader that reduces it its own way with
`trace_reduce.load_xplane(run["trace_path"], ..., stats=...)`) and the `Ctx`.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time
from typing import Callable, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoResult(SystemExit):
    """Exit with a code other than 0 and print no result line."""

    def __init__(self, code: int, why: str):
        print(f"benchmark: {why}", file=sys.stderr, flush=True)
        super().__init__(code)


def load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise NoResult(2, f"no file {os.path.relpath(path, ROOT)}") from None


def load_manifest() -> dict:
    """`BENCHMARK.json` at the root of the checkout."""
    return load_json(os.pardir, "BENCHMARK.json")


def per_layer_names(workload: dict) -> list[str]:
    """The per-layer metrics a cell reads. For a cell the manifest lists:
    its `per_layer` entries whose `workloads` hold the cell, in the
    manifest's order (an entry without the key is read in every cell that
    reports the end-to-end metric it moves). For a cell that is only a file:
    the `per_layer` list of that file."""
    manifest = load_manifest()
    if not any(w["name"] == workload["name"] for w in manifest["workloads"]):
        return list(workload["per_layer"])
    reported = set(workload["end_to_end"])
    return [e["name"] for e in manifest["per_layer"]
            if (workload["name"] in e["workloads"] if "workloads" in e
                else e["moves"] in reported)]


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, found by name (never from a list)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise NoResult(2, f"no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(f"benchmark.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Ctx:
    """One run of one cell."""

    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    scratch: str

    def say(self, msg: str) -> None:
        tag = "REHEARSAL platform=cpu " if self.rehearsal else ""
        print(f"{tag}[bench {self.workload['name']}] {msg}", flush=True)

    def param(self, key: str, default=None):
        """A traffic parameter, with the rehearsal's tiny value on top."""
        if self.rehearsal and key in self.traffic.get("rehearsal", {}):
            return self.traffic["rehearsal"][key]
        return self.traffic.get(key, default)


def cell_settings(ctx: Ctx) -> dict:
    """The algorithm settings the cell runs with, from its own files: the
    configuration's `algorithm` group, its overrides, the traffic's `set`."""
    return {**ctx.config["algorithm"], **ctx.config.get("overrides", {}),
            **ctx.param("set", {})}


def make_ctx(name: str, seed: int, seconds: float, trace: bool,
             rehearsal: bool) -> Ctx:
    workload = load_json("workloads", f"{name}.json")
    config = load_json("configs", f"{workload['config']}.json")
    traffic = load_json("traffic", f"{workload['traffic']}.json")
    scratch = os.path.join(ROOT, ".bench_scratch", name)
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch, exist_ok=True)
    return Ctx(workload, config, traffic, seed, seconds, trace, rehearsal,
               scratch)


# -- the device -----------------------------------------------------------

def device_block(chips: int, rehearsal: bool) -> dict:
    """The device as JAX reports it. A measurement that finds no TPU, a TPU
    the table of peaks does not list, or fewer chips than the cell asks for
    fails here: nothing falls back to the CPU."""
    import jax

    devices = jax.devices()
    block = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearsal:
        return block
    if block["platform"] != "tpu":
        raise NoResult(2, f"no accelerator: jax reports platform "
                          f"{block['platform']!r} ({block['kind']})")
    if block["count"] < chips:
        raise NoResult(2, f"the cell asks for {chips} chip(s), jax sees "
                          f"{block['count']}")
    from benchmark import flops

    try:
        flops.peak_flops(block["kind"])
    except KeyError as e:
        raise NoResult(2, str(e)) from None
    return block


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as JAX reports it: the peak of
    live arrays plus the peak the runtime reserved for running programs'
    temporaries. On the v5e runtime `peak_bytes_in_use` alone counts live
    arrays only (it read 0.69 GB while a step program with 11.3 GB of
    temporaries ran; a probe with 6.4 GB of temporaries showed them under
    `peak_bytes_reserved`: my chip runs, PR 22). 0 where the backend reports
    no memory statistics, as the CPU does."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


# -- rows to a rate -------------------------------------------------------

def read_rows(path: str) -> list[dict]:
    rows = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    return rows


def window_start(times: list[float], lead_s: float = 0.0) -> int:
    """Index of the row that starts the measured window: the first that is at
    least `lead_s` after the first row of all. It only marks the start (what
    came before it is set-up)."""
    return next(i for i, t in enumerate(times) if t >= times[0] + lead_s)


def steps_per_s(rows: list[dict], times: list[float],
                steps_per_iteration: int) -> Optional[float]:
    """Iterations between the first and the last row, times the decisions an
    iteration makes (from the cell's own files), over the time between the
    two rows on the BENCHMARK's clock (`times`: when each row appeared). A
    row is written after `float()` on that iteration's device metrics, so its
    appearance is a fence. The program's own `env_steps` must agree."""
    if len(rows) < 2 or len(times) != len(rows):
        return None
    dt = times[-1] - times[0]
    steps = (rows[-1]["iter"] - rows[0]["iter"]) * steps_per_iteration
    if dt <= 0 or steps != rows[-1]["env_steps"] - rows[0]["env_steps"]:
        return None
    return steps / dt


def row_failed(row: dict) -> bool:
    """A non-finite loss (the logger writes it as null)."""
    loss = row.get("loss")
    return loss is None or not isinstance(loss, (int, float)) \
        or not math.isfinite(loss)


def pace_of(rows: list[dict]) -> dict:
    """Where the first row falls and how fast rows follow, from a short call."""
    if len(rows) < 2 or rows[-1]["wall_s"] <= rows[0]["wall_s"]:
        raise NoResult(3, "the calibration call wrote fewer than two rows")
    return {
        "first_iter": rows[0]["iter"],
        "iters_per_s": (rows[-1]["iter"] - rows[0]["iter"])
        / (rows[-1]["wall_s"] - rows[0]["wall_s"]),
    }


def iterations_for(pace: dict, seconds: float, log_every: int) -> int:
    """How many iterations fill `seconds` after the first row at that pace,
    rounded up to the row cadence."""
    need = seconds * pace["iters_per_s"]
    return pace["first_iter"] + max(
        log_every, int(math.ceil(need / log_every - 1e-9)) * log_every)


# -- correct --------------------------------------------------------------

def compare(program: dict, reference: dict, tolerance: dict) -> dict:
    """Advantage targets and the scalar loss, program against reference.

    Errors are relative to the reference's own scale: max |p - r| over
    max |r| for the targets (the worse of the policy-gradient advantages and
    the value targets), |p - r| / |r| for the loss."""
    import numpy as np

    def rel(p, r):
        p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
        return float(np.max(np.abs(p - r)) / max(float(np.max(np.abs(r))), 1e-12))

    adv_err = max(rel(program[k], reference[k])
                  for k in ("pg_advantages", "value_targets"))
    loss_err = rel(program["loss"], reference["loss"])
    finite = all(np.all(np.isfinite(np.asarray(program[k])))
                 for k in ("pg_advantages", "value_targets", "loss"))
    return {
        "adv_err": adv_err,
        "loss_err": loss_err,
        "ok": bool(finite and adv_err <= tolerance["adv_tol"]
                   and loss_err <= tolerance["loss_tol"]),
    }


NARROWABLE = ("conv_general_dilated", "dot_general", "pallas_call")


def narrow_matmuls(closed_jaxpr, compute_dtype: str) -> list[str]:
    """The convolutions, matrix multiplications and kernel calls of a traced
    program that take or give a floating type narrower than `compute_dtype`
    (the configuration's `network.compute_dtype`), as `primitive:dtype`.

    Targets and loss cannot tell a bf16 update from the float32 one on a
    TPU (both feed the MXU bf16 operands: PERF.md, Findings, PR 22), so the
    precision the configuration states is held here, on the program's own
    update as it is traced, before any compiler: in a float32 configuration
    one bf16 convolution input, output or gradient makes `correct` false.
    Nested programs (scan and while bodies, pjit, custom derivatives) are
    walked; integer and boolean operands are not floating and do not count."""
    import jax.numpy as jnp

    floor = jnp.dtype(compute_dtype).itemsize

    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (value,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from equations(inner)

    found = []
    for eqn in equations(closed_jaxpr.jaxpr):
        if eqn.primitive.name not in NARROWABLE:
            continue
        for var in (*eqn.invars, *eqn.outvars):
            dtype = getattr(var.aval, "dtype", None)
            if dtype is not None and jnp.issubdtype(dtype, jnp.floating) \
                    and jnp.dtype(dtype).itemsize < floor:
                found.append(f"{eqn.primitive.name}:{jnp.dtype(dtype).name}")
                break
    return found


def platform_tolerance(config: dict, rehearsal: bool) -> dict:
    """The configuration's tolerance: `tolerance.tpu` on the chip,
    `tolerance.cpu` in a rehearsal (where both sides are exact float32)."""
    return config["tolerance"]["cpu" if rehearsal else "tpu"]


# -- watching the program's rows ------------------------------------------

class RowWatcher(threading.Thread):
    """Notes, for every row the program appends to its metrics file, the
    host time and the program's compile count, and fires `on_row` once per
    row. The measured loop is the program's own; this only watches its file
    (a stat every 2 ms), and its clock is the one the rate is taken on."""

    def __init__(self, path: str, compile_count: Callable[[], int],
                 on_row: Optional[Callable[[int, float], None]] = None):
        super().__init__(name="bench-row-watcher", daemon=True)
        self.path = path
        self.marks: list[tuple[float, int]] = []  # (monotonic, compiles)
        self._count = compile_count
        self._on_row = on_row
        self._halt = threading.Event()

    def run(self) -> None:
        offset = 0
        while True:
            stopping = self._halt.is_set()
            try:
                size = os.path.getsize(self.path)
            except OSError:
                size = 0
            if size > offset:
                with open(self.path, "rb") as fh:
                    fh.seek(offset)
                    chunk = fh.read(size - offset)
                complete = chunk.rfind(b"\n") + 1
                offset += complete
                for _ in range(chunk[:complete].count(b"\n")):
                    self.marks.append((time.monotonic(), self._count()))
                    if self._on_row is not None:
                        self._on_row(len(self.marks) - 1, self.marks[-1][0])
            if stopping:
                return
            time.sleep(0.002)

    def finish(self) -> None:
        self._halt.set()
        self.join()


class TraceWindow:
    """A profiler trace of `length_s` seconds, started `lead_s` after
    `arm()`; the python tracer is off (the trace is of the device and of the
    profiler's own host events). Only the process that holds the chip can
    trace it, so this runs on a thread of the benchmark's process."""

    def __init__(self, log_dir: str, lead_s: float, length_s: float):
        self.log_dir = log_dir
        self.lead_s, self.length_s = lead_s, length_s
        self.taken = False
        self._armed = threading.Event()
        self._abort = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="bench-trace", daemon=True)
        self._thread.start()

    def arm(self) -> None:
        self._armed.set()

    def _run(self) -> None:
        import jax

        self._armed.wait()
        if self._abort.wait(self.lead_s):
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._abort.wait(self.length_s)
        jax.profiler.stop_trace()
        self.taken = True

    def finish(self) -> None:
        """Stop waiting (a run that ended before the trace began takes none)
        and wait for the trace to be written."""
        self._abort.set()
        self._armed.set()
        self._thread.join()

    def path(self) -> Optional[str]:
        """The `.xplane.pb` of the trace taken, or None."""
        from benchmark import trace_reduce

        return trace_reduce.find_xplane(self.log_dir) if self.taken else None

    def reduced(self) -> Optional[dict]:
        from benchmark import trace_reduce

        path = self.path()
        if path is None:
            return None
        trace = trace_reduce.load_xplane(
            path, keep_lines=(trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE),
            stats=(trace_reduce.NAME_STACK_STAT,))
        return trace_reduce.reduce(trace)


def read_spans(telemetry_dir: str) -> list[dict]:
    """The program's `spans.jsonl` (Chrome-trace events, microseconds)."""
    path = os.path.join(telemetry_dir, "spans.jsonl")
    if not os.path.isfile(path):
        return []
    out = []
    with open(path) as fh:
        for line in fh:
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def median(values: list[float]) -> Optional[float]:
    if not values:
        return None
    s = sorted(values)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])
