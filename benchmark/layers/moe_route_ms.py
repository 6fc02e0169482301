"""Device time of the expert layers' routing a step (scope `moe_route` of
`models/seq_policy.moe`): router, top-k, the sort by expert, the gather of the
held assignments' tokens and the weighted scatter back; every pass. Median over the
whole steps of the trace, at any depth of the name stack
(benchmark/phases.py::scope_ms); a program without the scope reads nothing."""
LAYER, UNIT, SOURCE = "sequence policy", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    value = phases.scope_ms(run, ctx, "moe_route", "all")
    return value if value else None
