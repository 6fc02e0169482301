"""Peak device memory in use on the fullest chip, after the window."""
LAYER, UNIT, SOURCE = "device", "GB", "program_counter"
MOVES = "none: guards the cells' sizing"


def read(run, ctx):
    peak = run["device"].get("memory_peak_bytes", 0)
    return peak / 1e9 if peak else None
