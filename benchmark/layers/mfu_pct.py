"""Operations the algorithm needs per decision (benchmark/flops.py, from
shapes) times decisions per second of the traced run's window, over the chip's
published bf16 peak: the whole step's model FLOP/s utilization, the share
that bounds every claimed gain. Not a kernel's roofline share."""
LAYER, UNIT, SOURCE = "fused trainers", "%", "host_clock"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import flops, harness

    rate = run["end_to_end"].get(ctx.workload["rate_metric"])
    kind = run["device"]["kind"]
    if rate is None or ctx.rehearsal:
        return None
    settings = harness.cell_settings(ctx)
    need = flops.flops_per_decision(
        ctx.config["network"], int(settings["rollout_steps"]),
        float(settings.get("update_passes", 1.0)))
    return 100.0 * need * rate / (flops.peak_flops(kind) * run["device"]["count"])
