"""Device time of the rollout a step: self time of the `XLA Ops` events under
the `rollout` scope (`common.rollout_scan`), median over the whole steps of
the trace (benchmark/phases.py)."""
LAYER, UNIT, SOURCE = "fused trainers", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    return phases.phase_ms(run, ctx, "rollout")
