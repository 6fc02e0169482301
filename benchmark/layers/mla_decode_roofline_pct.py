"""The decode's attention kernel against the chip's roofline: the least time
an iteration's decode attention can take (`kernels/mla_decode.py::roofline_s`:
the filled prefix of both latent caches read once a layer and decode step at
a grain of 128 positions, the queries and the output, over the chip's
bandwidth; its operations over the peak if that were larger, which at 32
queries a row it is not) over the kernel's device time, `mla_decode_ms`.
Nothing where the program has no such kernel.

A file and NOT a manifest entry (see `mla_decode_ms.py`)."""
LAYER, UNIT, SOURCE = "sequence policy", "%", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    import json
    import os

    from benchmark import harness

    kernel_ms = harness.load_module("layers", "mla_decode_ms").read(run, ctx)
    if not kernel_ms:
        return None
    with open(os.path.join(harness.HERE, "peaks.json")) as fh:
        peak = json.load(fh)[run["device"]["kind"]]
    least_s = harness.load_module("kernels", "mla_decode").roofline_s(
        ctx.config["network"], harness.cell_settings(ctx),
        peak["bf16_tflops"] * 1e12, peak["hbm_gbps"] * 1e9)
    return 100.0 * least_s * 1e3 / kernel_ms
