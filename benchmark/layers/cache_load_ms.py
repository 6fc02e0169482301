"""The step's executables fetched from the persistent cache and loaded (or
compiled, on a miss, which `cache_miss_count` also shows): the `compile`
spans on the training thread inside the first `update` span
(telemetry/profiler.py's compile funnel; benchmark/startup.py)."""
LAYER, UNIT, SOURCE = "compile-once", "ms", "program_span"
MOVES = "setup_s"


def read(run, ctx):
    from benchmark import startup

    return startup.in_first_update_ms(run, ("compile",))
