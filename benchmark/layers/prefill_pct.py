"""Share of an iteration's token positions that the rollout's one prefill pass
filled instead of a decode step each: 100 x the mean of the program's
`prefill_frac` (`prefill_len / rollout_steps`) over the window's rows. A
program without the counter reads nothing."""
LAYER, UNIT, SOURCE = "sequence policy", "%", "program_counter"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    values = [row["prefill_frac"] for row in run.get("rows") or [] if "prefill_frac" in row]
    if not values:
        return None
    return 100.0 * sum(values) / len(values)
