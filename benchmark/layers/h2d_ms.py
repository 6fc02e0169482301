"""Median `host_to_device` span per iteration (one block's upload)."""
LAYER, UNIT, SOURCE = "data plane", "ms", "program_span"
MOVES = "host_steps_per_s"


def read(run, ctx):
    from benchmark import harness, spans

    return harness.median(spans.durations_ms(run, "host_to_device"))
