"""The busiest held expert's assignments over the held experts' mean, a mean over
the expert layers and over the window's rows (the program's
`expert_load_max_over_mean`): 1 is even, what a grouped matmul's longest group
pays for.
A program without the counter reads nothing."""
LAYER, UNIT, SOURCE = "sequence policy", "ratio", "program_counter"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    values = [row["expert_load_max_over_mean"] for row in run.get("rows") or [] if "expert_load_max_over_mean" in row]
    if not values:
        return None
    return 1.0 * sum(values) / len(values)
