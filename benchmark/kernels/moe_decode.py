"""What a rollout's decode steps need of the chip for the held experts some
token chose, counted from the configuration's `network` group, the cell's
settings and the program's own count of the experts it read: the work
needed, whatever implements it.

A decode step of an expert layer computes `sum_e w[n, e] E_e(x[n])` over the
held experts with a non-zero weight for some row, `E_e(x) = (silu(x W_gate) *
x W_up) W_down`. Needed bytes a layer and step: the three matrices of each
chosen expert, once (`read_frac x experts_held x 3 x hidden x width` in
`compute_dtype`), the rows in (`compute_dtype`), their routing weights and
the float32 result out. Needed operations: two a multiply-accumulate of every
row through each chosen expert's three matrices, which is what the sum costs
as one matmul an expert and an upper bound on what the assignments alone
need; at the few rows of a decode step (up to 128) it is a fraction of the
bytes' time, so the bandwidth bounds the step either way.

`read_frac` is the program's `decode_experts_read_frac`: the mean over the
rollout's decode steps and the expert layers of the chosen experts over the
held ones. The decode steps of an iteration are the row's positions less
those one prefill pass takes (`prefill_len`).
"""

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def needed(network: dict, settings: dict, prefill_len: int,
           read_frac: float) -> tuple[float, float]:
    """(operations, bytes) of one iteration's decode steps through the held
    experts: every expert layer, every decode step, all `E` rows."""
    n = network
    steps = int(settings["rollout_steps"]) - int(prefill_len)
    E = int(settings["num_envs"])
    layers = n["num_hidden_layers"] - n.get("first_k_dense_replace", 0)
    held, H, W = n["experts_held"], n["hidden_size"], n["moe_intermediate_size"]
    width = _BYTES[n["compute_dtype"]]
    chosen = read_frac * held
    ops = chosen * E * 3 * 2.0 * H * W
    moved = (chosen * 3 * H * W * width      # the chosen experts' weights, once
             + E * H * width                 # the rows
             + E * held * 4                  # their routing weights
             + E * H * 4)                    # the result, float32
    return layers * steps * ops, layers * steps * moved


def roofline_s(network: dict, settings: dict, prefill_len: int, read_frac: float,
               peak_flops: float, peak_bytes_s: float) -> float:
    """The least time an iteration's decode steps can take for the held
    experts on a chip of those peaks: the larger of operations over the peak
    and bytes over the bandwidth."""
    ops, moved = needed(network, settings, prefill_len, read_frac)
    return max(ops / peak_flops, moved / peak_bytes_s)
