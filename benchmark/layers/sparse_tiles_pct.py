"""The tiles the sparse layers' causal pass computes: 100 x the mean of the
program's `sparse_tiles_frac` over the window's rows, the share of the
`q_chunk_size` x `kv_chunk_size` tiles of the T x T square that hold at least
one selected pair (mean over the update's layers and rows, read off the
selection the pass built; about 53.1 at rows of 8,192, 136 of 256 tiles, when
every causal tile keeps a key). A program without the counter reads nothing.

A file and NOT a manifest entry, like the token cells' other readers (PERF.md
section 7)."""
LAYER, UNIT, SOURCE = "sequence policy", "%", "program_counter"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    values = [row["sparse_tiles_frac"] for row in run.get("rows") or []
              if "sparse_tiles_frac" in row]
    if not values:
        return None
    return 100.0 * sum(values) / len(values)
