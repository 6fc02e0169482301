"""`train.main`'s own wait before the device is handed its first step: from
the start of its `startup` span (arguments parsed) to the end of the first
`update` span, which holds the step's re-trace, lowering and cache load
(benchmark/startup.py)."""
LAYER, UNIT, SOURCE = "CLI / drivers", "ms", "program_span"
MOVES = "setup_s"


def read(run, ctx):
    from benchmark import startup

    w = startup.window(run)
    return None if w is None else (w[1] - w[0]) / 1e3
