"""Device time of the decode's attention kernel a step: the operations whose
name stack holds `mla_decode`, the name `actor_critic_tpu/ops/mla_decode.py`
gives its Pallas kernel: one `custom-call` a layer and decode step
(`rollout/while/body/mla/mla_decode`) and nothing else. The kernel's own DMA
of the cache is inside its event, so this is all the time the cache's reads
take. Median over the whole steps of the trace (benchmark/phases.py::
scope_ms); a program without the kernel (the einsum path, the parent) reads
nothing.

A file and NOT a manifest entry, like PR 29's eight (PERF.md section 7: an
entry that lists the token cell alone fails two harness tests)."""
LAYER, UNIT, SOURCE = "sequence policy", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    value = phases.scope_ms(run, ctx, "mla_decode", "all")
    return value if value else None
