"""Device time of the advantage kernel a step: the `custom-call` events under
the `advantage` scope (the Mosaic kernel `ops/pallas_scan.py` names `vtrace`,
`gae` or `lambda_returns`); median over the whole steps (benchmark/phases.py)."""
LAYER, UNIT, SOURCE = "advantage kernels", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    return phases.phase_ms(run, ctx, "kernel")
