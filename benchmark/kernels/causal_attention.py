"""What the grouped-query layers' causal pass needs of the chip in an
iteration, counted from the configuration's `network` group and the cell's
settings: the window and full layers' attention (`layer_types`, or the full
layers of `full_attention_interval`), whatever implements it (on a TPU one
splash-attention kernel a row and layer under a static band,
`ops/sparse_attention.py::attend_band`; the same count for dense blocks).

The work is that of the tiles a flash-style kernel visits: a row of `L`
positions cut into `TILE` x `TILE` tiles, of which those that hold a pair
inside the band (key `s` of query `t` where `0 <= t - s < window`; a full
layer's window is the row) are computed whole, every query head. A visited
tile of a forward pass is two matmuls (scores, values), `4 x TILE^2 x d`
operations a head; the backward pass is five (the scores again, since none
is kept, then dP, dV, dQ and dK): 2.5 times the forward's.

Bytes a row: q and o (`heads x d`), k and v (`kv heads x d`) in
`compute_dtype` and the float32 log-sum-exp a query head, each position
once; the backward pass also reads dO and writes dq, dk and dv.

The phases of an iteration, each bounded by the larger of its operations
over the peak and its bytes over the bandwidth: the prefill (`prefill_len`
positions of every row), the update's forward and its rematerialization
(the attention half of a layer is rematerialized in the backward pass, the
kernel's forward once more), and the backward.
"""

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
# The kernel's tile, queries and keys (`seq_policy.ATTN_QUERIES`).
TILE = 512


def windows(network: dict, length: int) -> list[int]:
    """The band of each grouped-query layer in a row of `length`: a window
    layer's `sliding_window` (at most the row), a full layer's the row."""
    n = network
    if n.get("layer_types"):
        kinds = n["layer_types"][:n["num_hidden_layers"]]
        return [min(n["sliding_window"], length) if kind == "sliding_attention" else length
                for kind in kinds if kind in ("sliding_attention", "full_attention")]
    interval = n.get("full_attention_interval") or 0
    if interval:
        return [length for i in range(n["num_hidden_layers"]) if (i + 1) % interval == 0]
    return []


def tiles_visited(length: int, window: int, tile: int = TILE) -> int:
    """Tiles of a `length` row's square that hold a pair of the band."""
    n = -(-length // tile)
    count = 0
    for i in range(n):
        q0, q1 = i * tile, min((i + 1) * tile, length) - 1
        for j in range(i + 1):
            k0, k1 = j * tile, min((j + 1) * tile, length) - 1
            # The largest key in the tile at or before the last query, and
            # inside the band of some query of the tile.
            count += k0 <= q1 and k1 >= q0 - window + 1
    return count


def _pass(n: dict, length: int, rows: int, backward: bool) -> tuple[float, float]:
    """(operations, bytes) of one pass over `rows` rows of `length`, every
    grouped-query layer."""
    nh, nkv, d = n["num_attention_heads"], n["num_key_value_heads"], n["head_dim"]
    width = _BYTES[n["compute_dtype"]]
    ops = moved = 0.0
    for window in windows(n, length):
        forward = tiles_visited(length, window) * 4.0 * TILE * TILE * d * nh
        ops += rows * (2.5 * forward if backward else forward)
        a_row = length * (2 * nh * d * width + 2 * nkv * d * width + nh * 4)
        if backward:  # dO in; dq, dk, dv out
            a_row += length * (2 * nh * d * width + 2 * nkv * d * width)
        moved += rows * a_row
    return ops, moved


def phases(network: dict, settings: dict, prefill_len: int) -> dict:
    """{phase: (operations, bytes)} of one iteration, all rows."""
    T, E = int(settings["rollout_steps"]), int(settings["num_envs"])
    forward = _pass(network, T, E, False)
    return {
        "prefill": _pass(network, int(prefill_len), E, False) if prefill_len else (0.0, 0.0),
        "update_forward": forward,
        "update_recompute": forward,
        "update_backward": _pass(network, T, E, True),
    }


def roofline_s(network: dict, settings: dict, prefill_len: int,
               peak_flops: float, peak_bytes_s: float) -> float:
    """The least time an iteration's causal attention can take on a chip of
    those peaks: per phase the larger of operations over the peak and bytes
    over the bandwidth, summed."""
    return sum(max(ops / peak_flops, moved / peak_bytes_s)
               for ops, moved in phases(network, settings, prefill_len).values())
