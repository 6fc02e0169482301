"""Operations of a decoder policy with grouped-query attention in window and
full layers and a chip's share of softmax-routed experts
(`models/seq_policy.py` with `layer_types`), counted from the configuration's
`network` group; one multiply-accumulate is two operations.

A forward pass of one token position, as a mean over the positions of a row of
`horizon` tokens: the four attention projections; the scores and the values
over the keys the layer's kind allows, which for a window layer is its BAND
(`min(t + 1, sliding_window)` keys for the query at position t) and for a full
layer the causal prefix (`t + 1`); the router; the routed experts at the
EXPECTATION of the assignments that land on this chip (`num_experts_per_tok x
experts_held / n_routed_experts` experts a token: 2); the lm_head over the
vocabulary slice and the value head. Needed work only: nothing the program
rematerializes, and no score a block computes outside the band, is counted.

`flops_per_decision` is 1 forward in the rollout, whether a position was
prefilled or decoded, and 3 in the update (forward + backward); there is no
bootstrap pass, and a decision is a token position of a row.
"""


def projection_macs(n: dict) -> int:
    H, d = n["hidden_size"], n["head_dim"]
    return 2 * H * n["num_attention_heads"] * d + 2 * H * n["num_key_value_heads"] * d


def keys_a_query(n: dict, kind: str, horizon: int) -> float:
    """Mean number of keys a query of a layer of `kind` attends in a row of
    `horizon` positions."""
    window = n["sliding_window"] if kind == "sliding_attention" else horizon
    return sum(min(t + 1, window) for t in range(horizon)) / horizon


def attention_macs(n: dict, kind: str, horizon: int) -> float:
    """Scores and values of one query over its keys, all heads."""
    return n["num_attention_heads"] * keys_a_query(n, kind, horizon) * 2 * n["head_dim"]


def expert_macs(n: dict) -> int:
    """One routed expert on one token: gate, up, down."""
    return 3 * n["hidden_size"] * n["moe_intermediate_size"]


def routed_here(n: dict) -> float:
    """Expected share of the token-expert assignments that land here."""
    return n["experts_held"] / n["n_routed_experts"]


def window_kept(n: dict, horizon: int) -> float:
    """Key positions the layers attend over what full causal layers would."""
    kinds = n["layer_types"][:n["num_hidden_layers"]]
    full = (horizon + 1) / 2.0
    return sum(keys_a_query(n, k, horizon) for k in kinds) / (len(kinds) * full)


def forward_flops(network: dict, horizon: int = 4096) -> float:
    """Operations of one forward pass of one token position of a row of
    `horizon` (4,096: the cell's)."""
    n = network
    macs = 0.0
    for kind in n["layer_types"][:n["num_hidden_layers"]]:
        macs += projection_macs(n) + attention_macs(n, kind, horizon)
        macs += n["hidden_size"] * n["n_routed_experts"]
        macs += n["num_experts_per_tok"] * routed_here(n) * expert_macs(n)
    macs += n["hidden_size"] * (n["vocab_size"] + 1)
    return 2.0 * macs


def flops_per_decision(network: dict, settings: dict) -> float:
    """Operations one token position needs end to end: the rollout's forward
    and the update's forward and backward (2 x forward), over the cell's own
    row length."""
    return 4.0 * forward_flops(network, int(settings["rollout_steps"]))
