"""The decode's experts kernel against the chip's roofline: the least time an
iteration's decode steps can take for the held experts some token chose
(`kernels/moe_decode.py::roofline_s`: the chosen experts' three matrices read
once an expert layer and decode step, at the window's own
`decode_experts_read_frac`, the rows in and the result out, over the chip's
bandwidth; the operations over the peak if that were larger, which at a
decode step's few rows it is not) over the kernel's device time,
`moe_decode_ms`. Nothing where the program has no such kernel or counter.

A file and NOT a manifest entry (see `decode_experts_read_pct.py`)."""
LAYER, UNIT, SOURCE = "sequence policy", "%", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    import json
    import os

    from benchmark import harness

    kernel_ms = harness.load_module("layers", "moe_decode_ms").read(run, ctx)
    read_pct = harness.load_module("layers", "decode_experts_read_pct").read(run, ctx)
    if not kernel_ms or read_pct is None:
        return None
    with open(os.path.join(harness.HERE, "peaks.json")) as fh:
        peak = json.load(fh)[run["device"]["kind"]]
    least_s = harness.load_module("kernels", "moe_decode").roofline_s(
        ctx.config["network"], harness.cell_settings(ctx),
        ctx.param("env_set", {}).get("prefill_len", 0), read_pct / 100.0,
        peak["bf16_tflops"] * 1e12, peak["hbm_gbps"] * 1e9)
    return 100.0 * least_s * 1e3 / kernel_ms
