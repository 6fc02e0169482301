"""Rows served over bucket rows dispatched: the gateway's own
`batch_occupancy` gauge (mean over its last 256 flushes), scraped from
`/metrics` right after the window."""
LAYER, UNIT, SOURCE = "serving", "%", "program_counter"
MOVES = "act_per_s"


def read(run, ctx):
    from benchmark import prom

    occ = prom.gauge(run.get("metrics_after"), "serving_batch_occupancy")
    return None if occ is None else 100.0 * occ
