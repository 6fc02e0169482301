"""Device time of the backward pass a step: self time of every operation whose
name stack holds `transpose(` (JAX wraps the forward's scope as
`transpose(jvp(<scope>))`); median over the whole steps (benchmark/phases.py)."""
LAYER, UNIT, SOURCE = "fused trainers", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    return phases.phase_ms(run, ctx, phases.BACKWARD)
