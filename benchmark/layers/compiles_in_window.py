"""Compilations (cache loads included) inside the measured window, from the
program's compile records. Must be 0; `correct` is false otherwise."""
LAYER, UNIT, SOURCE = "compile-once", "count", "program_counter"
MOVES = "the cell's rate metric and act_p99_ms"


def read(run, ctx):
    return run.get("compiles_in_window")
