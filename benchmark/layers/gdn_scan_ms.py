"""Device time a step of the linear layers' delta rule (scope `gdn_scan` of
`models/gated_delta.py`): in the causal passes (prefill, the update's forward,
its recomputation and backward) the intra-chunk solve and the scan across
chunks; in the rollout each decode step's state update.
Median over the whole steps of the trace, at any depth of the name stack
(benchmark/phases.py::scope_ms); a program without the scope reads nothing.

A file and NOT a manifest entry (see `attn_linear_ms.py`)."""
LAYER, UNIT, SOURCE = "sequence policy", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    value = phases.scope_ms(run, ctx, "gdn_scan", "all")
    return value if value else None
