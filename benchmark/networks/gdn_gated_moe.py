"""Operations of a decoder policy whose layers are Gated DeltaNet (linear
attention) and gated full attention, `full_attention_interval` apart, with a
chip's share of softmax-routed experts and a gated shared expert
(`models/seq_policy.py` with `full_attention_interval`, `models/gated_delta.py`),
counted from the configuration's `network` group; one multiply-accumulate is
two operations.

A forward pass of one token position, as a mean over the positions of a row of
`horizon` tokens:

- a linear layer: its projections (`[q | k | v | z]`, `[b | a]`, the output),
  the causal convolution (`linear_conv_kernel_dim` taps a channel of `[q | k
  | v]`), and the chunked delta rule at `chunk` positions a chunk
  (`delta_rule_macs`): a query's and a key's row of the chunk's `q k^T` and
  `k k^T`, its row of the triangular solve for `W` and `U`, the three
  products with the state (`W S`, `q S`, `k^T v'`) and its row of the
  intra-chunk `attn v'`, every value head;
- a full layer: its projections (the query and its gate, keys, values, the
  output) and attention's scores and values over the `t + 1` causal keys;
- every layer: the router, the routed experts at the EXPECTATION of the
  assignments that land on this chip (`num_experts_per_tok x experts_held /
  n_routed_experts` experts a token), the shared expert and its gate;
- the lm_head over the vocabulary slice and the value head.

Needed work only: nothing the program rematerializes, and no score of the full
layer's dense blocks that lies after the query, is counted.

`flops_per_decision` is 1 forward in the rollout, whether a position was
prefilled or decoded, and 3 in the update (forward + backward); there is no
bootstrap pass, and a decision is a token position of a row.
"""


def is_full(n: dict, layer: int) -> bool:
    return (layer + 1) % n["full_attention_interval"] == 0


def layers_of(n: dict) -> tuple[int, int]:
    """(linear layers, full layers)."""
    full = sum(is_full(n, i) for i in range(n["num_hidden_layers"]))
    return n["num_hidden_layers"] - full, full


def conv_channels(n: dict) -> int:
    return (2 * n["linear_num_key_heads"] * n["linear_key_head_dim"]
            + n["linear_num_value_heads"] * n["linear_value_head_dim"])


def linear_projection_macs(n: dict) -> int:
    H, value = n["hidden_size"], n["linear_num_value_heads"] * n["linear_value_head_dim"]
    return H * (conv_channels(n) + value) + H * 2 * n["linear_num_value_heads"] + value * H


def conv_macs(n: dict) -> int:
    return n["linear_conv_kernel_dim"] * conv_channels(n)


def delta_rule_macs(n: dict) -> float:
    """The chunked delta rule's multiply-accumulates a position, all value
    heads (`chunk` = C): `q k^T` and `k k^T` (C d_k each), the triangular
    solve's row against `[beta k exp(G) | beta v]` (C (d_k + d_v) / 2),
    `W S`, `q S`, `k^T v'` (d_k d_v each), `attn v'` (C d_v)."""
    C, dk, dv = n["chunk"], n["linear_key_head_dim"], n["linear_value_head_dim"]
    return n["linear_num_value_heads"] * (
        2 * C * dk + C * (dk + dv) / 2 + 3 * dk * dv + C * dv)


def full_projection_macs(n: dict) -> int:
    H, d = n["hidden_size"], n["head_dim"]
    return (2 * H * n["num_attention_heads"] * d + 2 * H * n["num_key_value_heads"] * d
            + n["num_attention_heads"] * d * H)


def attention_macs(n: dict, horizon: int) -> float:
    """Scores and values of one query over its causal keys, all heads."""
    return n["num_attention_heads"] * (horizon + 1) / 2.0 * 2 * n["head_dim"]


def expert_macs(n: dict, width: int) -> int:
    """One expert of `width` on one token: gate, up, down."""
    return 3 * n["hidden_size"] * width


def ffn_macs(n: dict) -> float:
    routed = n["num_experts_per_tok"] * n["experts_held"] / n["n_routed_experts"]
    return (n["hidden_size"] * n["n_routed_experts"]
            + routed * expert_macs(n, n["moe_intermediate_size"])
            + expert_macs(n, n["shared_expert_intermediate_size"]) + n["hidden_size"])


def forward_flops(network: dict, horizon: int = 16384) -> float:
    """Operations of one forward pass of one token position of a row of
    `horizon` (16,384: the cell's)."""
    n = network
    linear, full = layers_of(n)
    macs = (linear * (linear_projection_macs(n) + conv_macs(n) + delta_rule_macs(n))
            + full * (full_projection_macs(n) + attention_macs(n, horizon))
            + n["num_hidden_layers"] * ffn_macs(n)
            + n["hidden_size"] * (n["vocab_size"] + 1))
    return 2.0 * macs


def flops_per_decision(network: dict, settings: dict) -> float:
    """Operations one token position needs end to end: the rollout's forward
    and the update's forward and backward (2 x forward), over the cell's own
    row length."""
    return 4.0 * forward_flops(network, int(settings["rollout_steps"]))
