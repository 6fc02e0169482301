"""Operations of a decoder policy with latent attention (MLA) and a chip's
share of sigmoid-routed experts (`models/seq_policy.py`), counted from the
configuration's `network` group; one multiply-accumulate is two operations.

A forward pass of one token position: the five MLA projections, the causal
scores and values at the row's MEAN context ((T + 1) / 2 keys a query), the
dense MLP in the first `first_k_dense_replace` layers, in the others the
router, the shared expert and the routed experts at the EXPECTATION of the
assignments that land on this chip (`num_experts_per_tok x experts_held /
n_routed_experts` experts a token: 0.5), the lm_head over the vocabulary
slice and the value head. Needed work only: nothing the program
rematerializes is counted.

The generic rule a decision does not fit (there is no bootstrap pass, and a
decision is a token position of a row): `flops_per_decision` is 1 forward in
the rollout and 3 in the update (forward + backward). Decoding through the
cache with absorbed projections costs the rollout about what a position of
the causal pass costs at the mean context; the difference is not counted.

The held experts' grouped matmuls have their own count, for their share of
the roofline (`layers/moe_experts_roofline_pct.py`).
"""


def _mla_macs(n: dict) -> int:
    H, nh = n["hidden_size"], n["num_attention_heads"]
    qk = n["qk_nope_head_dim"] + n["qk_rope_head_dim"]
    return (H * n["q_lora_rank"] + n["q_lora_rank"] * nh * qk
            + H * (n["kv_lora_rank"] + n["qk_rope_head_dim"])
            + n["kv_lora_rank"] * nh * (n["qk_nope_head_dim"] + n["v_head_dim"])
            + nh * n["v_head_dim"] * H)


def _attention_macs(n: dict, context: float) -> float:
    qk = n["qk_nope_head_dim"] + n["qk_rope_head_dim"]
    return n["num_attention_heads"] * context * (qk + n["v_head_dim"])


def expert_macs(n: dict) -> int:
    """One routed (or the shared) expert on one token: gate, up, down."""
    return 3 * n["hidden_size"] * n["moe_intermediate_size"]


def routed_here(n: dict) -> float:
    """Expected share of the token-expert assignments that land here."""
    return n["experts_held"] / n["n_routed_experts"]


def forward_flops(network: dict, context: float = 256.5) -> float:
    """Operations of one forward pass of one token position at a mean
    context of `context` keys (256.5: a row of 512, causal)."""
    n = network
    dense = n["first_k_dense_replace"]
    sparse = n["num_hidden_layers"] - dense
    macs = n["num_hidden_layers"] * (_mla_macs(n) + _attention_macs(n, context))
    macs += dense * 3 * n["hidden_size"] * n["intermediate_size"]
    macs += sparse * (n["hidden_size"] * n["n_routed_experts"] + expert_macs(n)
                      + n["num_experts_per_tok"] * routed_here(n) * expert_macs(n))
    macs += n["hidden_size"] * (n["vocab_size"] + 1)
    return 2.0 * macs


def flops_per_decision(network: dict, settings: dict) -> float:
    """Operations one token position needs end to end: the rollout's forward
    and the update's forward and backward (2 x forward), at the mean context
    of the cell's own row length."""
    context = (int(settings["rollout_steps"]) + 1) / 2.0
    return 4.0 * forward_flops(network, context)


def held_experts_roofline_s(network: dict, settings: dict, routed_frac: float,
                            peak_flops: float, peak_bytes_s: float) -> float:
    """The least time one iteration's grouped matmuls of the held experts can
    take on a chip of those peaks, with `routed_frac` of the assignments
    landing here: per phase the larger of operations over the peak and bytes
    over the bandwidth, summed over the expert layers.

    Rollout, each of the T decode steps: `E x k x routed_frac` assignments
    and the held experts' weights read once (bf16 operands, 2 bytes): a few
    tokens an expert, bound by bandwidth. Update: the forward and the
    backward (2 forwards more) over `T x E x k x routed_frac` assignments,
    the weights read once a pass and the activations in and out: bound by
    compute at a thousand tokens an expert."""
    n = network
    T, E = int(settings["rollout_steps"]), int(settings["num_envs"])
    layers = n["num_hidden_layers"] - n["first_k_dense_replace"]
    weights = 2.0 * n["experts_held"] * expert_macs(n)            # bytes, bf16
    H, W = n["hidden_size"], n["moe_intermediate_size"]
    row_bytes = 2.0 * (2 * H + 3 * W)   # x in, y out, the three [W] intermediates

    def least(assignments: float, passes: float) -> float:
        ops = passes * 2.0 * assignments * expert_macs(n)
        moved = passes * (weights + assignments * row_bytes)
        return max(ops / peak_flops, moved / peak_bytes_s)

    per_step = E * n["num_experts_per_tok"] * routed_frac
    return layers * (T * least(per_step, 1.0) + least(T * per_step, 3.0))
