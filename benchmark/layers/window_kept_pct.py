"""Key positions the layers' attention covers over what full causal layers
would: 100 x the mean of the program's `window_kept_frac` over the window's
rows (a constant of the traffic's row length, the window and the layers' kinds:
57.8 at rows of 4,096, a window of 1,024 and three window layers to one full).
A program without the counter reads nothing."""
LAYER, UNIT, SOURCE = "sequence policy", "%", "program_counter"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    values = [row["window_kept_frac"] for row in run.get("rows") or [] if "window_kept_frac" in row]
    if not values:
        return None
    return 100.0 * sum(values) / len(values)
