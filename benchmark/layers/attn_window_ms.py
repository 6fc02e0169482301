"""Device time a step of the window layers' attention halves (scope `attn_window`
of `models/seq_policy.py`): norm, projections, RoPE, in the rollout the ring's
write and the scores over its slots, in the update the causal pass over each
block's band and its backward.
Median over the whole steps of the trace, at any depth of the name stack
(benchmark/phases.py::scope_ms); a program without the scope reads nothing.

A file and NOT a manifest entry, like the token cells' other readers (PERF.md
section 7: an entry that lists one cell alone fails two harness tests)."""
LAYER, UNIT, SOURCE = "sequence policy", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    value = phases.scope_ms(run, ctx, "attn_window", "all")
    return value if value else None
