"""Share of the traced window in which no operation ran on the device."""
LAYER, UNIT, SOURCE = "device", "%", "device_trace"
MOVES = "the cell's own rate metric"


def read(run, ctx):
    trace = run.get("trace")
    return None if trace is None else trace["idle_pct"]
