"""Device time a step of the prompt's one causal pass inside the rollout (scope
`prefill` of `algos/common.rollout_scan`): the env's prompt observations, the
policy's pass over them with the fill of both kinds of cache, the last
position's logits.
Median over the whole steps of the trace, at any depth of the name stack
(benchmark/phases.py::scope_ms); a program without the scope reads nothing.

A file and NOT a manifest entry, like the token cells' other readers (PERF.md
section 7: an entry that lists one cell alone fails two harness tests)."""
LAYER, UNIT, SOURCE = "sequence policy", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    value = phases.scope_ms(run, ctx, "prefill", "all")
    return value if value else None
