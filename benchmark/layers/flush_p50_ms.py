"""Median duration of one flush (pad, dispatch, both crossings): the program's
`serve_dispatch` spans inside the window."""
LAYER, UNIT, SOURCE = "serving", "ms", "program_span"
MOVES = "act_per_s"


def read(run, ctx):
    from benchmark import harness, spans

    return harness.median(spans.durations_ms(run, "serve_dispatch"))
