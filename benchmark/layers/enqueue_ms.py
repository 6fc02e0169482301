"""Host time to dispatch one iteration in the fused path: median of the
program's `update` span (enqueue to return, never a step time:
utils/checkpoint.py says so) over the window."""
LAYER, UNIT, SOURCE = "CLI / drivers", "ms", "program_span"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import harness, spans

    durs = spans.durations_ms(run, "update")
    return harness.median(durs)
