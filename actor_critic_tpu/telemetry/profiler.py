"""On-demand device profiling + compile attribution (ISSUE 3).

Two introspection tools that run INSIDE a live training process:

- `WindowedProfiler` — an armable, windowed `jax.profiler` capture
  (the start/stop plumbing is `utils/profiling.start_trace` /
  `stop_trace`, the same pair `utils/profiling.trace` wraps). Arm it
  with `arm(iters)` — from the exporter's `/profile?iters=N` endpoint,
  from SIGUSR2 (`install_sigusr2`), or programmatically — and the next
  `tick()` (the training loops call one per iteration/dispatch) starts
  a capture that stops `iters` ticks later, leaving a Perfetto-openable
  trace under `<telemetry-dir>/profile_<n>/` and a `profile_done` event
  naming it. The training loop never blocks on an idle profiler: an
  unarmed `tick()` is one lock-free attribute read.

- a compile listener (`ensure_compile_introspection`) — wraps JAX's
  single compile funnel so every XLA compilation becomes a structured
  `compile` event carrying the jitted function's name, the abstract
  argument signature (the MLIR main function type — shapes AND dtypes),
  compile seconds, and the executable's `cost_analysis()` FLOPs/bytes.
  A recompile storm stops being a bare counter: consecutive `compile`
  events for the same name with different signatures name exactly which
  argument shape/dtype changed (scripts/run_report.py renders the
  attribution table). The funnel is internal JAX API, so the hook is
  best-effort: if the import shape changes, telemetry degrades to the
  `jax.monitoring` counter (sampler.py) instead of breaking the run.
  While a session is installed the call is also a `compile` span, and
  jax's own trace and lowering phases are `jit_trace` / `jit_lower` spans
  (a listener on its `jax.monitoring` events), so `spans.jsonl` and a
  profiler capture show where a first dispatch's wait goes.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Optional

from actor_critic_tpu.telemetry import session as _session

DEFAULT_PROFILE_ITERS = 5

# Process-global compile log: like sampler.py's counter, the funnel wrap
# cannot be undone, so records accumulate per process (bounded ring) and
# any current session additionally gets each record as a `compile` event.
_COMPILE_RING_MAX = 256
_compile_records: list[dict] = []
_compile_total = 0  # monotonic; the ring above is capped
_compile_lock = threading.Lock()
_introspection_installed = False


def introspection_active() -> bool:
    """Whether the compile-funnel listener is installed (consumers like
    the chunk-wall ratchet fall back to heuristics when it isn't)."""
    return _introspection_installed


def compile_event_count() -> int:
    """Total compile events observed since the listener was installed
    (monotonic — unlike the capped record ring). Sampling this around a
    dispatch tells whether the dispatch paid XLA compile."""
    return _compile_total


class WindowedProfiler:
    """Armable N-iteration `jax.profiler` capture bound to one telemetry
    directory.

    States: idle → armed (`arm(iters)`) → active (first `tick()` after
    arming starts the trace) → idle (after `iters` more ticks, or
    `close()`). All transitions are lock-guarded; `arm` is safe from the
    exporter's HTTP thread and from a signal handler, `tick` runs on the
    training thread.
    """

    def __init__(self, directory: str):
        self.directory = os.fspath(directory)
        self._lock = threading.Lock()
        self._armed_iters = 0
        # Signal-safe arm request: SIGUSR2 runs its handler ON the main
        # (training) thread, which may already hold self._lock inside
        # tick() — taking the non-reentrant lock there would deadlock
        # the run. The handler therefore only WRITES (_pending_arm,
        # then the request counter), and tick() only READS, comparing
        # the counter against the last value it consumed: a
        # read-and-clear of a shared slot would race the handler (a
        # signal landing between tick's read and its zeroing store
        # would be silently discarded).
        self._pending_arm = DEFAULT_PROFILE_ITERS
        # jaxlint: thread-owned=signal (single writer BY DESIGN: only
        # the signal handler bumps the request counter — taking the
        # non-reentrant lock there would deadlock a handler landing
        # inside tick(); see the comment block above)
        self._arm_requests = 0
        self._arm_seen = 0
        self._remaining = 0
        self._active_dir: Optional[str] = None
        self._captures = 0
        self._t_start = 0.0

    # -- control surface (HTTP thread) ------------------------------------
    def arm(self, iters: int = DEFAULT_PROFILE_ITERS) -> dict:
        """Request a capture of the next `iters` training ticks. Returns
        the status dict; arming while armed/active is a no-op report,
        not an error (two probes racing must not corrupt a capture).
        Safe from other threads, NOT from a signal handler on the
        training thread — that's `request_arm`."""
        iters = max(int(iters), 1)
        with self._lock:
            if (
                self._armed_iters == 0
                and self._arm_requests == self._arm_seen
                and self._active_dir is None
            ):
                self._armed_iters = iters
            return self._status_locked()

    def request_arm(self, iters: int = DEFAULT_PROFILE_ITERS) -> None:
        """Lock-free arm request for signal handlers: two plain
        attribute stores (value, then counter — the handler is the only
        writer of both); the next tick() folds it into the armed state
        (ignored there if a window is already armed/active)."""
        self._pending_arm = max(int(iters), 1)
        self._arm_requests += 1

    def status(self) -> dict:
        with self._lock:
            return self._status_locked()

    def _status_locked(self) -> dict:
        requested = self._arm_requests != self._arm_seen
        armed = self._armed_iters or (requested and self._pending_arm)
        if self._active_dir is not None:
            state = "active"
        elif armed:
            state = "armed"
        else:
            state = "idle"
        out = {"state": state, "captures": self._captures}
        if armed:
            out["iters"] = armed
        if self._active_dir is not None:
            out["directory"] = self._active_dir
            out["remaining_iters"] = self._remaining
        return out

    # -- training-thread surface ------------------------------------------
    def tick(self) -> None:
        """One training iteration boundary. Starts a pending capture or
        counts an active one down; free when idle."""
        requests = self._arm_requests
        with self._lock:
            if (
                requests != self._arm_seen
                and self._armed_iters == 0
                and self._active_dir is None
            ):
                self._armed_iters = self._pending_arm
            self._arm_seen = requests
            if self._active_dir is not None:
                self._remaining -= 1
                if self._remaining > 0:
                    return
                path, dur = self._active_dir, time.perf_counter() - self._t_start
                self._active_dir = None
            elif self._armed_iters > 0:
                self._start_locked()
                return
            else:
                return
        self._stop(path, dur)

    def _start_locked(self) -> None:
        n, self._armed_iters = self._armed_iters, 0
        self._captures += 1
        path = os.path.join(self.directory, f"profile_{self._captures:03d}")
        try:
            from actor_critic_tpu.utils.profiling import start_trace

            start_trace(path)
        except Exception as e:  # profiler unavailable: report, don't die
            _session.event("profile_failed", error=str(e)[:500])
            return
        self._active_dir = path
        self._remaining = n
        self._t_start = time.perf_counter()
        _session.event("profile_start", path=path, iters=n)

    def _stop(self, path: str, dur_s: float) -> None:
        try:
            from actor_critic_tpu.utils.profiling import stop_trace

            stop_trace()
        except Exception as e:
            _session.event("profile_failed", path=path, error=str(e)[:500])
            return
        _session.complete_span(
            "profile", time.perf_counter() - dur_s, dur_s, path=path
        )
        _session.event(
            "profile_done", path=path, wall_s=round(dur_s, 3)
        )

    def close(self) -> None:
        """Stop a capture left active (session teardown mid-window)."""
        with self._lock:
            self._armed_iters = 0
            self._arm_seen = self._arm_requests
            if self._active_dir is None:
                return
            path, dur = self._active_dir, time.perf_counter() - self._t_start
            self._active_dir = None
        self._stop(path, dur)


def tick() -> None:
    """Per-iteration hook the training loops call: routes to the current
    session's profiler (no-op — one import-free attribute read — when no
    session or no profiler is installed)."""
    s = _session.current()
    if s is not None and s.profiler is not None:
        s.profiler.tick()


def install_sigusr2(iters: int = DEFAULT_PROFILE_ITERS) -> bool:
    """`kill -USR2 <pid>` arms a capture on the live run — the escape
    hatch when no --telemetry-port was passed. Main-thread only (POSIX
    signal contract); returns False where unsupported."""
    if threading.current_thread() is not threading.main_thread():
        return False
    usr2 = getattr(signal, "SIGUSR2", None)
    if usr2 is None:  # pragma: no cover - non-POSIX
        return False

    def _handler(signum, frame):
        s = _session.current()
        if s is not None and s.profiler is not None:
            # request_arm, not arm(): the handler runs ON the training
            # thread, which may hold the profiler lock inside tick().
            s.profiler.request_arm(iters)

    signal.signal(usr2, _handler)
    return True


# ---------------------------------------------------------------- compile
def _signature_of(computation) -> Optional[str]:
    """The MLIR main function type of a module about to be compiled —
    '(tensor<8x3xf32>, tensor<f32>) -> tensor<8x8xf32>' — i.e. the
    abstract shapes/dtypes this program is specialized to."""
    try:
        for op in computation.body.operations:
            try:
                if str(op.operation.attributes["sym_name"]) == '"main"':
                    return str(op.operation.attributes["function_type"])
            except KeyError:
                continue
    except Exception:
        pass
    return None


def _module_name(computation) -> str:
    try:
        return str(computation.operation.attributes["sym_name"]).strip('"')
    except Exception:
        return "?"


def _mosaic_calls(computation) -> int:
    """How many Mosaic (Pallas TPU) kernels the module about to be
    compiled launches: `stablehlo.custom_call`s whose target is
    `tpu_custom_call`. Lets a caller tell a program that carries the
    compiled kernel from one that fell back to `lax.scan` or runs the
    interpreter (both lower to plain HLO)."""
    from jax._src.lib.mlir import ir

    count = 0

    def visit(op):
        nonlocal count
        if (
            op.name == "stablehlo.custom_call"
            and "tpu_custom_call" in str(op.attributes["call_target_name"])
        ):
            count += 1
        return ir.WalkResult.ADVANCE

    computation.operation.walk(visit)
    return count


def _cost_fields(executable) -> dict:
    """FLOPs / bytes-accessed from the loaded executable's
    cost_analysis() (one dict in the installed jax); absent (not zero)
    where the backend reports none."""
    out: dict = {}
    try:
        ca = executable.cost_analysis()
        flops = ca.get("flops")
        if flops:
            out["flops"] = float(flops)
        nbytes = ca.get("bytes accessed")
        if nbytes:
            out["bytes_accessed"] = float(nbytes)
    except Exception:
        pass
    return out


# jax times its own compile phases on the calling thread, with the
# function's name: `record_scalar(event, start)` on entry and
# `record_event_duration_secs(event, seconds)` on exit
# (`jax._src.dispatch.log_elapsed_time`). A listener on each end of the
# pair opens and closes a real span, so the phase is mirrored into a running
# profiler capture like any other span.
_TRACE_PHASE = "jit_trace"
_LOWER_PHASE = "jit_lower"
_PHASE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": _TRACE_PHASE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": _LOWER_PHASE,
}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# Per thread: `open`, the phases under way as (event, fun_name, span or
# None for a phase inside another), innermost last; `retrieve_s`, the
# persistent cache's retrieval time in the compile under way.
_phase_tls = threading.local()
# jaxlint: thread-owned=compile-lock (tested and set only inside
# ensure_compile_introspection, under _compile_lock)
_phase_listeners_installed = False


def _on_phase_start(event: str, value, **kwargs) -> None:
    if _session.current() is None:
        return
    try:
        phase = _PHASE_OF_EVENT.get(event)
        if phase is None:
            return
        fun = kwargs.get("fun_name")
        stack = getattr(_phase_tls, "open", None)
        if stack is None:
            stack = _phase_tls.open = []
        sp = None
        # Only the outermost phase is a span: every jitted function a
        # trace calls (each `jnp` operation is one), and every function a
        # lowering traces, is traced inside it, and a span apiece would be
        # thousands a step.
        if not stack:
            sp = _session.span(phase, fun=str(fun))
            sp.__enter__()
        stack.append((event, fun, sp))
    except Exception:
        pass  # a listener that raised would fail jax's own compile


def _on_phase_end(event: str, duration_secs: float, **kwargs) -> None:
    # A span opened while a session was installed is closed even when the
    # session has gone since: it sits on the thread's open-span stack.
    if _session.current() is None and not getattr(_phase_tls, "open", None):
        return
    try:
        if event == _RETRIEVAL_EVENT:
            _phase_tls.retrieve_s = round(float(duration_secs), 6)
            return
        stack = getattr(_phase_tls, "open", None)
        if not stack:
            return
        opened, fun, sp = stack[-1]
        if opened == event and fun == kwargs.get("fun_name"):
            stack.pop()
            if sp is not None:
                sp.__exit__(None, None, None)
    except Exception:
        pass


def _ensure_phase_listeners() -> None:
    global _phase_listeners_installed
    if _phase_listeners_installed:
        return
    try:
        import jax.monitoring

        jax.monitoring.register_scalar_listener(_on_phase_start)
        jax.monitoring.register_event_duration_secs_listener(_on_phase_end)
        _phase_listeners_installed = True
    except (ImportError, AttributeError):
        pass


def ensure_compile_introspection() -> bool:
    """Idempotently wrap JAX's compile funnel
    (`jax._src.compiler.compile_or_get_cached`) so each XLA compilation
    produces one structured record: process-global ring + a `compile`
    event on any installed session, and a `compile` span around the call.
    Also listens to jax's own compile-phase events, which become
    `jit_trace` / `jit_lower` spans while a session is installed.
    Best-effort — returns False (and changes nothing) if the internal
    funnel moved."""
    global _introspection_installed
    with _compile_lock:
        _ensure_phase_listeners()
        if _introspection_installed:
            return True
        try:
            from jax._src import compiler as _jax_compiler

            original = _jax_compiler.compile_or_get_cached
        except (ImportError, AttributeError):
            return False
        # Cache-hit attribution needs the persistent-cache hit/miss
        # counters live even when no --compile-cache-dir was set.
        from actor_critic_tpu.utils.compile_cache import (
            ensure_cache_stats_listener,
        )

        ensure_cache_stats_listener()

        def _wrapped(*args, **kwargs):
            # Fully generic pass-through: the funnel is internal JAX
            # API, so a version that reorders parameters or goes
            # keyword-only must still compile — introspection extracts
            # what it can and never changes the call.
            name = sig = None
            mosaic = 0
            try:
                computation = kwargs.get("computation", None)
                if computation is None and len(args) > 1:
                    computation = args[1]
                if computation is not None:
                    name = _module_name(computation)
                    sig = _signature_of(computation)
                    mosaic = _mosaic_calls(computation)
            except Exception:
                pass
            from actor_critic_tpu.utils.compile_cache import thread_cache_hits

            hits_before = thread_cache_hits()
            _phase_tls.retrieve_s = None
            # The call is a `compile` span (cache key, retrieval and load
            # on a hit, the backend compile on a miss), with the record
            # below as its args.
            with _session.span("compile") as sp:
                t0 = time.perf_counter()
                executable = original(*args, **kwargs)
                record = {
                    "name": name if name is not None else "?",
                    "compile_s": round(time.perf_counter() - t0, 4),
                    **_cost_fields(executable),
                }
                # Persistent-cache attribution: a hit event on THIS thread
                # during the call means this "compile" deserialized a
                # cached executable, not recompiled (the event fires on
                # the compiling thread, so the AOT warmup thread's hits
                # are never counted here).
                if thread_cache_hits() > hits_before:
                    record["cache_hit"] = True
                if mosaic:
                    record["mosaic_calls"] = mosaic
                if sig is not None:
                    record["signature"] = sig[:2000]
                sp.annotate(**{
                    **record, "cache_hit": "cache_hit" in record,
                    "retrieve_s": _phase_tls.retrieve_s,
                    "thread": threading.current_thread().name,
                })
            _record_compile(record)
            return executable

        _jax_compiler.compile_or_get_cached = _wrapped
        _introspection_installed = True
        return True


def _record_compile(record: dict) -> None:
    global _compile_total
    with _compile_lock:
        _compile_total += 1
        _compile_records.append(record)
        del _compile_records[:-_COMPILE_RING_MAX]
    try:
        _session.event("compile", **record)
    except Exception:
        pass  # telemetry must never take the run down


def compile_records() -> list[dict]:
    """Recent structured compile records (process-global ring)."""
    with _compile_lock:
        return list(_compile_records)


def compile_records_since(count0: int) -> list[dict]:
    """The records of the compiles after the `compile_event_count()`
    snapshot `count0` (the newest `_COMPILE_RING_MAX` of them at most).
    Indexed back from the MONOTONIC counter: the ring is capped, so in a
    long process len(records) sits at capacity and slicing by list length
    would silently return []."""
    with _compile_lock:
        delta = _compile_total - count0
        return _compile_records[-delta:] if delta else []
