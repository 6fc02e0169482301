"""Share of the held experts whose weights a decode step read: 100 x the mean
of the program's `decode_experts_read_frac` over the window's rows (each row
is one iteration's mean over its rollout's decode steps and expert layers of
the experts some token chose over the experts held; the actor's own routing,
counted as it decodes). 100 where the decode runs every held expert on every
token (the batched matmuls); under even routing of `E` tokens that pick `k`
of `n` experts, `1 - (1 - k / n) ** E` (65.6% at 8 tokens x 8 of 64). A
program without the counter (the parent) reads nothing.

A file and NOT a manifest entry, like the token cells' other readers (PERF.md
section 7: an entry that lists the token cells alone fails two harness
tests)."""
LAYER, UNIT, SOURCE = "sequence policy", "%", "program_counter"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    values = [row["decode_experts_read_frac"] for row in run.get("rows") or []
              if "decode_experts_read_frac" in row]
    if not values:
        return None
    return 100.0 * sum(values) / len(values)
