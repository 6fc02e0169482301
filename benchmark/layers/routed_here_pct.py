"""Share of the window's token-expert assignments that landed on experts this
chip holds: 100 x the mean of the program's `routed_here_frac` (a mean over the
expert layers of one iteration's causal pass) over the window's rows. The
expectation under even routing is `experts_held / n_routed_experts` (6.25%).
A program without the counter reads nothing."""
LAYER, UNIT, SOURCE = "sequence policy", "%", "program_counter"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    values = [row["routed_here_frac"] for row in run.get("rows") or [] if "routed_here_frac" in row]
    if not values:
        return None
    return 100.0 * sum(values) / len(values)
