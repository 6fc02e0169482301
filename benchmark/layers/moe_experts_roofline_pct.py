"""The held experts' matmuls against the chip's roofline: the least time an
iteration's expert matmuls can take (`networks/mla_moe.py::
held_experts_roofline_s`: the rollout's decode steps bound by the bandwidth of
the held experts' weights, the update's forward and backward bound by
compute), at the window's own `routed_here_frac`, over the device time of
those matmuls, `moe_experts_ms`. Rematerialized passes are in the denominator
only. Nothing where the program has no such scope or counter.

NOT in `BENCHMARK.json`, and not to be listed as it reads: 133 on the chip (my
chip runs, PR 29), where a share of a roofline cannot pass 100. The
numerator's bytes are right; part of their time is not in the denominator.
XLA's TPU compiler brings a decode step's gate weights of three of the four
expert layers, and the up weights of three, into VMEM ahead of the matmul
that reads them (four `slice-start` / `slice-done` a block into memory space
1, joined by a `ConcatBitcast`, in the compiled step's HLO): 6 of a step's 12
blocks of 50 MB are in flight under the operations scheduled before the
matmul, which then reads VMEM in about 18 us. The 6 blocks read from HBM run at
700-730 GB/s of the 819 (69-71 us a block). Self time cannot hold the time in
flight; what can is the union of the windows from each `slice-start` to its
`slice-done` (PERF.md section 7)."""
LAYER, UNIT, SOURCE = "sequence policy", "%", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    import json
    import os

    from benchmark import harness

    fracs = [row["routed_here_frac"] for row in run.get("rows") or []
             if "routed_here_frac" in row]
    experts_ms = harness.load_module("layers", "moe_experts_ms").read(run, ctx)
    if not fracs or not experts_ms:
        return None
    with open(os.path.join(harness.HERE, "peaks.json")) as fh:
        peak = json.load(fh)[run["device"]["kind"]]
    kind = harness.load_module("networks", ctx.config["network"]["kind"])
    least_s = kind.held_experts_roofline_s(
        ctx.config["network"], harness.cell_settings(ctx), sum(fracs) / len(fracs),
        peak["bf16_tflops"] * 1e12, peak["hbm_gbps"] * 1e9)
    return 100.0 * least_s * 1e3 / experts_ms
