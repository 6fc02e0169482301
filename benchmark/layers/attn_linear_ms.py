"""Device time a step of the linear layers' mixer halves (scope `attn_linear`
of `models/seq_policy.py`, Gated DeltaNet): norm, projections, the causal
convolution, the gates, the delta rule (`gdn_scan_ms` is its part), the gated
norm and the output projection; in the rollout a decode step's state update,
in the prefill and the update the chunked pass and its backward.
Median over the whole steps of the trace, at any depth of the name stack
(benchmark/phases.py::scope_ms); a program without the scope reads nothing.

A file and NOT a manifest entry, like the token cells' other readers (PERF.md
section 7: an entry that lists one cell alone fails two harness tests)."""
LAYER, UNIT, SOURCE = "sequence policy", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    value = phases.scope_ms(run, ctx, "attn_linear", "all")
    return value if value else None
