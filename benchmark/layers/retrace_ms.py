"""The step's Python tracing and MLIR lowering, which no cache saves: the
`jit_trace` and `jit_lower` spans on the training thread inside the first
`update` span (jax's own compile-phase events, telemetry/profiler.py;
benchmark/startup.py)."""
LAYER, UNIT, SOURCE = "compile-once", "ms", "program_span"
MOVES = "setup_s"


def read(run, ctx):
    from benchmark import startup

    return startup.in_first_update_ms(run, ("jit_trace", "jit_lower"))
