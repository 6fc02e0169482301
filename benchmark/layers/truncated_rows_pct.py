"""Share of the window's decisions at which the time limit cut an episode,
the rows the truncation bootstrap runs the critic on: 100 x the mean of the
program's `truncated_frac` (`impala_loss`'s metric, a mean over an
iteration's T x E rows) over the window's rows of `metrics.jsonl`. It is the
traffic `final_obs_ms` met; a program without the counter reads nothing."""
LAYER, UNIT, SOURCE = "fused trainers", "%", "program_counter"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    fracs = [row["truncated_frac"] for row in run.get("rows") or []
             if "truncated_frac" in row]
    if not fracs:
        return None
    return 100.0 * sum(fracs) / len(fracs)
