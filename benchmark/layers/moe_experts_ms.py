"""Device time of the held experts' matmuls a step: the rollout's decode
steps, the update's forward (rematerialized passes too) and backward.

`models/seq_policy.py` runs them under the `moe_experts` scope: a decode
step's batched matmuls over every held expert, the update's `lax.ragged_dot`
over the assignments sorted by expert. XLA's TPU compiler writes each
`ragged_dot` as kernels of its own and replaces their name stack by its own
name for them (`ragged-dot-none`, and `ragged-dot-metadata` for the group
offsets they share), so those are found by that name; everything else by the
scope (the batched matmuls, the operands' casts, `silu`). Median over the
whole steps of the trace (benchmark/phases.py); a program without such an
operation reads nothing."""
LAYER, UNIT, SOURCE = "sequence policy", "ms", "device_trace"
MOVES = "fused_steps_per_s"
# What XLA:TPU puts in place of the name stack of a `ragged_dot`'s kernels.
RAGGED_DOT = "ragged-dot-"


def read(run, ctx):
    from benchmark import harness, phases

    if phases.steps_of(run, ctx) is None:
        return None
    per_step = [
        sum(self_ns for self_ns, stack, kernel in events
            if phases.in_scope(stack, "moe_experts")
            or (kernel and str(stack or "").startswith(RAGGED_DOT)))
        for _, events in phases.step_events(
            phases.trace_of(run), ctx.param("step_module"))]
    value = harness.median(per_step) / 1e6 / float(
        ctx.param("iterations_per_dispatch", 1))
    return value if value else None
