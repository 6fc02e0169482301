"""Mosaic (Pallas) calls inside the step / update program, from its compile
record: 1 or more means the advantage kernel is engaged, 0 that the program
fell back to `lax.scan`. The kernel's time needs a kernel name in the trace
(the `tracing` issue)."""
LAYER, UNIT, SOURCE = "advantage kernels", "count", "program_counter"
MOVES = "the cell's rate metric"


def read(run, ctx):
    name = ctx.param("step_module")
    calls = [r.get("mosaic_calls", 0) for r in run.get("compile_records", [])
             if r.get("name") == name]
    return max(calls) if calls else None
