"""The grouped-query layers' causal kernel against the chip's roofline: the
least time an iteration's window and full attention can take
(`kernels/causal_attention.py::roofline_s`: per phase, the prefill, the
update's forward, its rematerialization and backward, the larger of the
visited tiles' operations over the peak and the bytes of q, k, v, o and the
log-sum-exp, dO, dq, dk and dv in the backward, over the bandwidth) over
the kernel's device time (`kernel_ms`: the `custom-call` events under the
scope `attn_causal` of `models/seq_policy.py::gqa_unroll`, its forward, dq
and dkv kernels). Nothing where the program has no such kernel (the dense
blocks, off a TPU, or the tree before it).

A file and NOT a manifest entry, like the token cells' other readers (PERF.md
section 7: an entry that lists one cell alone fails two harness tests)."""
LAYER, UNIT, SOURCE = "sequence policy", "%", "device_trace"
MOVES = "fused_steps_per_s"
SCOPE = "attn_causal"


def kernel_ms(run, ctx):
    """The kernel's self time a step, median over the whole steps, in
    milliseconds an iteration; None where nothing is read."""
    from benchmark import phases

    if phases.steps_of(run, ctx) is None:
        return None
    per_step = [sum(self_ns for self_ns, stack, kernel in events
                    if kernel and phases.in_scope(stack, SCOPE))
                for _, events in phases._step_events_of(
                    run["trace_path"], ctx.param("step_module"))]
    value = phases._ms_an_iteration(per_step, ctx)
    return value if value else None


def read(run, ctx):
    import json
    import os

    from benchmark import harness

    spent_ms = kernel_ms(run, ctx)
    if not spent_ms:
        return None
    with open(os.path.join(harness.HERE, "peaks.json")) as fh:
        peak = json.load(fh)[run["device"]["kind"]]
    least_s = harness.load_module("kernels", "causal_attention").roofline_s(
        ctx.config["network"], harness.cell_settings(ctx),
        ctx.param("env_set", {}).get("prefill_len", 0),
        peak["bf16_tflops"] * 1e12, peak["hbm_gbps"] * 1e9)
    return 100.0 * least_s * 1e3 / spent_ms
