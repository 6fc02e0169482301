"""Share of the window the collecting thread spent inside `env_step` spans
(environment stepping and the acting forward pass of one collection block)."""
LAYER, UNIT, SOURCE = "host-env path", "%", "program_span"
MOVES = "host_steps_per_s"


def read(run, ctx):
    from benchmark import spans

    durs = spans.durations_ms(run, "env_step")
    window_ms = spans.window_ms(run)
    if not durs or not window_ms:
        return None
    return 100.0 * sum(durs) / window_ms
