"""Persistent-cache misses during set-up (`compile_cache.cache_stats()` at the
window's start). 0 in every run of a checkout but its first."""
LAYER, UNIT, SOURCE = "compile-once", "count", "program_counter"
MOVES = "setup_s"


def read(run, ctx):
    stats = run.get("cache_stats_setup") or {}
    return stats.get("misses")
