"""Device time of the decode's experts kernel a step: the operations whose
name stack holds `moe_decode`, the name `actor_critic_tpu/ops/moe_decode.py`
gives its Pallas kernel: one `custom-call` an expert layer and decode step
(`rollout/while/body/.../moe_experts/moe_decode`) and nothing else. The
kernel's own DMA of the chosen experts' weights is inside its event, so this
is all the time those reads take. Median over the whole steps of the trace
(benchmark/phases.py::scope_ms); a program without the kernel (the batched
matmuls, the parent) reads nothing.

A file and NOT a manifest entry (see `decode_experts_read_pct.py`)."""
LAYER, UNIT, SOURCE = "sequence policy", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    value = phases.scope_ms(run, ctx, "moe_decode", "all")
    return value if value else None
