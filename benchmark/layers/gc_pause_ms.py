"""Full (generation-2) garbage collections on the training thread during
start-up, from the start of `startup` to the end of the first `update`: the
`gc` spans of telemetry/session.py's hook, 0 where none ran. Part of the
other start-up readers' time, not beside it (benchmark/startup.py)."""
LAYER, UNIT, SOURCE = "CLI / drivers", "ms", "program_span"
MOVES = "setup_s"


def read(run, ctx):
    from benchmark import startup

    return startup.startup_gc_ms(run)
