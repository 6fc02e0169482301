"""Median `update` span per iteration on the host path. It is an ENQUEUE time
(`dispatch="async"` in ppo.train_host): the update runs while the next block
is collected, and the wait for it lands in the next iteration's
`jax.device_get(params)`."""
LAYER, UNIT, SOURCE = "host-env path", "ms", "program_span"
MOVES = "host_steps_per_s"


def read(run, ctx):
    from benchmark import harness, spans

    return harness.median(spans.durations_ms(run, "update"))
