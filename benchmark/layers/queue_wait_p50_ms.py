"""Median time a request waited in the batcher's queue: the program's
`serve_queue_wait` spans inside the window (the gateway's `/metrics` has one
latency histogram and none for the queue alone)."""
LAYER, UNIT, SOURCE = "serving", "ms", "program_span"
MOVES = "act_p99_ms"


def read(run, ctx):
    from benchmark import harness, spans

    return harness.median(spans.durations_ms(run, "serve_queue_wait"))
