"""Share of the whole steps' device time that benchmark/phases.py's table cannot
place under a scope or the backward pass: the phase readers' own honesty
check, so that a renamed scope shows as a number and not as a silent hole."""
LAYER, UNIT, SOURCE = "fused trainers", "%", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    return phases.unscoped_pct(run, ctx)
