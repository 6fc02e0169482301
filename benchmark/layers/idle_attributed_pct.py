"""Share of the device's idle time in the trace that the program's spans
cover: the union of the `ac:<span>` annotations `telemetry/session.py` mirrors
into the profiler's trace (and the harness's `bench:*`), over all idle time
(benchmark/phases.py; `breakdown.idle_gaps` labels single gaps by
`trace_reduce.label_gap`'s half-a-gap rule, this is the whole)."""
LAYER, UNIT, SOURCE = "CLI / drivers", "%", "program_span"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    from benchmark import phases

    trace = phases.trace_of(run)
    return None if trace is None else phases.idle_attributed_pct(trace)
