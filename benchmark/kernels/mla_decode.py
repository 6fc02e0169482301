"""What a rollout's decode attention over the latent (MLA) cache needs of the
chip, counted from the configuration's `network` group and the cell's
settings: the work needed, whatever implements it.

A decode step `t` of a layer scores one query a head and row against the
cached latents of positions `0..t` and sums the same latents under the
softmax (queries absorbed into the latent space, so every head of a row
reads the same `[t + 1, kv_lora_rank + qk_rope_head_dim]` block). Needed
bytes a layer and step: the filled prefix of both caches, once, at a grain of
`GRAIN` positions (a fetch of whole blocks of positions; the prefix of step
`t` is `ceil((t + 1) / GRAIN)` blocks), the queries in and the float32 output
out. Needed operations: two a multiply-accumulate over the same prefix, the
scores over `rank + rope` and the values over `rank`. An episode is exactly
one unroll, so an iteration's `T` steps fill the row once: `t = 0..T-1`.
"""

# Positions a block of the prefix: the chip's lane width, the position block
# of the program's kernel (`actor_critic_tpu/ops/mla_decode.py`). Kept here as
# a number so that the yardstick does not move with the program.
GRAIN = 128
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def needed(network: dict, settings: dict) -> tuple[float, float]:
    """(operations, bytes) of one iteration's decode attention: every layer,
    every one of the `T` decode steps, all `E` rows."""
    n = network
    T, E = int(settings["rollout_steps"]), int(settings["num_envs"])
    heads, rank, rope = (n["num_attention_heads"], n["kv_lora_rank"],
                         n["qk_rope_head_dim"])
    width = _BYTES[n["compute_dtype"]]
    ops = moved = 0.0
    for t in range(T):
        prefix = -(-(t + 1) // GRAIN) * GRAIN
        ops += 2.0 * E * heads * prefix * (rank + rope + rank)
        moved += E * (prefix * (rank + rope) * width          # both caches, once
                      + heads * (rank + rope) * width         # the queries
                      + heads * rank * 4)                     # the output, float32
    return n["num_hidden_layers"] * ops, n["num_hidden_layers"] * moved


def roofline_s(network: dict, settings: dict, peak_flops: float,
               peak_bytes_s: float) -> float:
    """The least time an iteration's decode attention can take on a chip of
    those peaks: the larger of operations over the peak and bytes over the
    bandwidth (the bandwidth at every shape here: every head reuses the
    block, but a row has 32 queries, not hundreds)."""
    ops, moved = needed(network, settings)
    return max(ops / peak_flops, moved / peak_bytes_s)
