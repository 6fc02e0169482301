"""Device time of the pass over `final_obs` a step (its reshape, the forward
over it, the truncation bootstrap): self time under the `final_obs` scope of
`impala_loss`; median over the whole steps (benchmark/phases.py)."""
LAYER, UNIT, SOURCE = "fused trainers", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    return phases.phase_ms(run, ctx, "final_obs")
