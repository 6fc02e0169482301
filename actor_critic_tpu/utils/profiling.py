"""Tracing / profiling / numerics-guard harness (SURVEY.md §5.1-5.2).

The reference genre's observability is TensorBoard scalar timings
[RECON; reference mount empty at survey, SURVEY.md §0]. The TPU build's
tools, in one place:

- `trace(logdir)`: profiler context producing TensorBoard/Perfetto
  traces of the XLA programs inside (view with `tensorboard --logdir` or
  ui.perfetto.dev).
- `time_fn(fn, *args)`: dispatch-overhead-aware timing: warmup (compile)
  + `block_until_ready` fencing, returns seconds/call.
- `nan_guard(tree, name)`: jittable non-finite detector for dev runs —
  emits a host-side warning via `jax.debug.callback` (XLA has no cheap
  device-side abort; `jax.config.update("jax_debug_nans", True)` is the
  heavyweight alternative).
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

_log = logging.getLogger(__name__)


def start_trace(logdir: str) -> None:
    """Begin a profiler capture into `logdir` (Perfetto trace included).
    Split out of `trace` so windowed captures that cannot hold a context
    manager open across loop iterations (telemetry/profiler.py's
    on-demand `/profile?iters=N` window) share the same configuration."""
    jax.profiler.start_trace(logdir, create_perfetto_trace=True)


def stop_trace() -> None:
    """End the capture `start_trace` opened."""
    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(logdir: str):
    """`with trace("runs/prof"):` around the iterations to profile."""
    start_trace(logdir)
    try:
        yield
    finally:
        stop_trace()


def time_fn(
    fn: Callable[..., Any],
    *args: Any,
    iters: int = 10,
    warmup: int = 2,
) -> float:
    """Mean seconds per `fn(*args)` call with device-completion fencing.

    `fn` should be jitted (or cheap); the warmup calls absorb compilation.
    All `iters` timed calls are dispatched back-to-back and fenced once —
    the per-call dispatch overhead is real throughput overhead, but a
    fence per call would measure the host round trip instead of device time.
    """
    out = None
    for _ in range(max(warmup, 1)):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def nan_guard(tree: Any, name: str = "value") -> None:
    """Inside jit: log a host-side warning if any leaf has a non-finite
    element. Zero device-side control flow — one fused all-finite
    reduction plus a debug callback."""
    leaves = [x for x in jax.tree.leaves(tree) if jnp.issubdtype(
        jnp.asarray(x).dtype, jnp.floating)]
    if not leaves:
        return
    finite = jnp.all(
        jnp.stack([jnp.all(jnp.isfinite(x)) for x in leaves])
    )

    def _warn(ok):
        if not bool(ok):
            _log.warning("nan_guard: non-finite values detected in %s", name)

    jax.debug.callback(_warn, finite)
