"""Device time of the update's own forward pass a step: self time under the
`forward` scope of `impala_loss` (the pass over `obs`, log-prob, entropy), not
transposed; median over the whole steps (benchmark/phases.py)."""
LAYER, UNIT, SOURCE = "fused trainers", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    return phases.phase_ms(run, ctx, "forward")
