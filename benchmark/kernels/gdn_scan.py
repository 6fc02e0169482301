"""What the linear layers' delta rule needs of the chip in an iteration,
counted from the configuration's `network` group and the cell's settings: the
work of the chunked algorithm at `chunk` positions a chunk
(`networks/gdn_gated_moe.py::delta_rule_macs`) and of the one-position step
the decode takes, whatever implements them (the program's XLA operations
under the scope `gdn_scan` today, a Pallas kernel later: the same count).

Bytes a position and value head: `q` and `k` (`d_k` each, `compute_dtype`),
`v` (`d_v`, `compute_dtype`), `g` and `beta` (float32) in, `o` (`d_v`,
float32) out; a decode step also reads and writes the float32 state (`d_k x
d_v`). Operations: the chunked form's multiply-accumulates a position in the
causal passes, `3 d_k d_v` a value head in a decode step (`S^T k`, `k u^T`,
`S^T q`).

The phases of an iteration, each bounded by the larger of its operations
over the peak and its bytes over the bandwidth: the decode (`rollout_steps -
prefill_len` steps of every row), the prefill (`prefill_len` positions of
every row), the update's forward, its recomputation in the backward pass
(the causal pass is rematerialized there, once as counted here) and its
backward (twice the forward's operations and bytes: the gradients of every
input from the output's).
"""

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _sizes(n: dict):
    from benchmark.networks import gdn_gated_moe as kind

    linear, _ = kind.layers_of(n)
    return kind, linear, n["linear_num_value_heads"], n["linear_key_head_dim"], \
        n["linear_value_head_dim"]


def position_bytes(n: dict) -> float:
    """Bytes of one position of one linear layer in and out, every head."""
    _, _, nv, dk, dv = _sizes(n)
    width = _BYTES[n["compute_dtype"]]
    nk = n["linear_num_key_heads"]
    return 2 * nk * dk * width + nv * dv * width + 2 * nv * 4 + nv * dv * 4


def phases(network: dict, settings: dict, prefill_len: int) -> dict:
    """{phase: (operations, bytes)} of one iteration, all linear layers and
    rows."""
    n = network
    kind, linear, nv, dk, dv = _sizes(n)
    T, E = int(settings["rollout_steps"]), int(settings["num_envs"])
    chunked = 2.0 * kind.delta_rule_macs(n)
    per = position_bytes(n)
    step_ops = 2.0 * nv * 3 * dk * dv
    step_bytes = per + 2 * nv * dk * dv * 4
    decode = (T - prefill_len) * E * linear
    forward = (T * E * linear * chunked, T * E * linear * per)
    return {
        "decode": (decode * step_ops, decode * step_bytes),
        "prefill": (prefill_len * E * linear * chunked, prefill_len * E * linear * per),
        "update_forward": forward,
        "update_recompute": forward,
        "update_backward": (2 * forward[0], 2 * forward[1]),
    }


def roofline_s(network: dict, settings: dict, prefill_len: int,
               peak_flops: float, peak_bytes_s: float) -> float:
    """The least time an iteration's delta rule can take on a chip of those
    peaks: per phase the larger of operations over the peak and bytes over
    the bandwidth, summed."""
    return sum(max(ops / peak_flops, moved / peak_bytes_s)
               for ops, moved in phases(network, settings, prefill_len).values())
