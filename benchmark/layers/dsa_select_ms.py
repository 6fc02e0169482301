"""Device time a step of the sparse layers' exact top-`topk` selection (scope
`dsa_select` of `models/seq_policy.py`, inside `dsa_index`): on a TPU the
kernel of `ops/topk_select.py` (its `custom-call` events), elsewhere the radix
select of `seq_policy.top_k_mask`; in the rollout (the prefill and every
decode step) and in the update's causal passes, the forward and the
rematerialized forward of the backward pass. `dsa_index_ms` holds it, beside
the indexer's keys and scores.
Median over the whole steps of the trace, at any depth of the name stack
(benchmark/phases.py::scope_ms); a program without the scope reads nothing.

A file and NOT a manifest entry, like the token cells' other readers (PERF.md
section 7: an entry that lists one cell alone fails two harness tests)."""
LAYER, UNIT, SOURCE = "sequence policy", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    value = phases.scope_ms(run, ctx, "dsa_select", "all")
    return value if value else None
