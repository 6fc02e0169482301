"""Device time of the step program per iteration: median duration of the
trace's XLA-module events whose name is the traffic file's `step_module`
(`jit_train_step`; `jit_full` when chunked, then divided by the chunk)."""
LAYER, UNIT, SOURCE = "fused trainers", "ms", "device_trace"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    trace = run.get("trace")
    name = ctx.param("step_module")
    if trace is None or name is None or name not in trace["modules"]:
        return None
    per_dispatch = trace["modules"][name]["median_s"] * 1e3
    return per_dispatch / float(ctx.param("iterations_per_dispatch", 1))
