"""The linear layers' delta rule against the chip's roofline: the least time
an iteration's delta rule can take (`kernels/gdn_scan.py::roofline_s`: per
phase, decode, prefill, the update's forward, recomputation and backward, the
larger of the chunked form's operations over the peak and its bytes over the
bandwidth) over its device time, `gdn_scan_ms`. Nothing where the program has
no such scope.

A file and NOT a manifest entry (see `attn_linear_ms.py`)."""
LAYER, UNIT, SOURCE = "sequence policy", "%", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    import json
    import os

    from benchmark import harness

    scan_ms = harness.load_module("layers", "gdn_scan_ms").read(run, ctx)
    if not scan_ms:
        return None
    with open(os.path.join(harness.HERE, "peaks.json")) as fh:
        peak = json.load(fh)[run["device"]["kind"]]
    least_s = harness.load_module("kernels", "gdn_scan").roofline_s(
        ctx.config["network"], harness.cell_settings(ctx),
        ctx.param("env_set", {}).get("prefill_len", 0),
        peak["bf16_tflops"] * 1e12, peak["hbm_gbps"] * 1e9)
    return 100.0 * least_s * 1e3 / scan_ms
