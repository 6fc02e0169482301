"""Device time of the head over the vocabulary slice a step (scope `lm_head` of
`models/seq_policy.py`): the rollout's logits, the update's log-probabilities
and entropies `HEAD_ROWS` token rows a trip, and their backward. Median over the
whole steps of the trace, at any depth of the name stack
(benchmark/phases.py::scope_ms); a program without the scope reads nothing."""
LAYER, UNIT, SOURCE = "sequence policy", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    value = phases.scope_ms(run, ctx, "lm_head", "all")
    return value if value else None
