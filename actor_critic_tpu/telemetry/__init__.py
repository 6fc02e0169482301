"""Unified run telemetry (ISSUE 1): phase spans, resource sampling, and
health events behind one `TelemetrySession`.

The framework could already *detect* a device call that never returns
(utils/watchdog.py) and log scalar metrics (utils/logging.py); this
package is the layer that can *explain* a run — which loop phase
stalled, whether device memory crept, when throughput regressed:

- `spans`   — host-side span tracer emitting Chrome-trace-format events
              (`spans.jsonl`, one event per line; Perfetto-viewable via
              `scripts/run_report.py --trace`).
- `sampler` — daemon resource sampler (`resources.jsonl`): process RSS,
              per-device live/peak bytes, XLA recompile counter.
- `health`  — throughput-regression and divergence detectors emitting
              structured events (`events.jsonl`).
- `session` — `TelemetrySession` owning the three sinks, plus the
              module-level current-session API the training loops call.
- `exporter`— live-introspection HTTP daemon (ISSUE 3): `/metrics`
              (Prometheus text), `/healthz` (watchdog staleness + open
              span), `/profile?iters=N` (arm an on-demand capture);
              `train.py --telemetry-port`.
- `profiler`— armable windowed `jax.profiler` capture (endpoint or
              SIGUSR2) and the compile listener that turns every XLA
              compilation into a structured `compile` event with
              cost_analysis() FLOPs/bytes and the abstract argument
              signature.

Instrumentation is ALWAYS on (a span is two `time.perf_counter()` calls
and a list push/pop — no device syncs); the three JSONL sinks only
exist while a session is installed (`train.py --telemetry-dir`). The
open-span stack is maintained even without a session so the stall
watchdog can name the hung phase in its exit-42 diagnosis.
"""

from actor_critic_tpu.telemetry.profiler import (  # noqa: F401
    tick as profiler_tick,
)
from actor_critic_tpu.telemetry.session import (  # noqa: F401
    TelemetrySession,
    complete_span,
    current,
    event,
    instant,
    last_open_span,
    observe,
    open_spans,
    set_current,
    span,
    stall_report,
)
from actor_critic_tpu.telemetry.spans import CANONICAL_PHASES  # noqa: F401
