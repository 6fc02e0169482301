"""Device time of latent attention a step (scope `mla` of `models/seq_policy.py`):
the projections, decoding through the latent cache in the rollout, the causal
pass and its backward in the update. Median over the
whole steps of the trace, at any depth of the name stack
(benchmark/phases.py::scope_ms); a program without the scope reads nothing."""
LAYER, UNIT, SOURCE = "sequence policy", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    value = phases.scope_ms(run, ctx, "mla", "all")
    return value if value else None
