"""Share of the window's token positions under the loss mask (the policy's own
tokens, not prompt or padding): 100 x the mean of the program's `response_frac`
over the window's rows. A step of `fused_steps_per_s` is a token position; this
says how many are decisions.
A program without the counter reads nothing."""
LAYER, UNIT, SOURCE = "sequence policy", "%", "program_counter"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    values = [row["response_frac"] for row in run.get("rows") or [] if "response_frac" in row]
    if not values:
        return None
    return 100.0 * sum(values) / len(values)
