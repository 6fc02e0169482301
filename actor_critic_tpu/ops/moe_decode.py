"""A decode step's held experts, as one Pallas TPU kernel that reads the
weights of the experts some token chose and of no others.

A pass of a few tokens through an expert layer of `models/seq_policy.py`
computes `y[n] = sum_e weights_here[n, e] E_e(x[n])` over the held experts,
`E_e(x) = (silu(x W_gate[e]) * x W_up[e]) W_down[e]`. As batched matmuls
over every held expert (`reference`) a step streams all the held experts'
weights, and that stream is what the step's time is: at 8 tokens that pick 8
of 64 experts a third of the held experts is chosen by no token, and its
weights are multiplied by a weight of exactly zero. The kernel walks the
chosen experts only: a scalar-prefetched list of their ids picks each grid
step's weight blocks, and the steps past the last chosen expert name the
block the pipeline already holds, so nothing is fetched for them and their
body does not run. The terms left out are `0 x E_e(x)`: the same sum.

Numerics are `reference`'s: operands in `compute_dtype`, float32 sums, the
activation rounded to `compute_dtype` before the down-projection; only the
order of the float32 sum over experts (and tiles of the expert width)
differs, and the routing weight multiplies in float32 on the VPU. An
unchosen expert's weights are not read, so whatever they hold (NaN too)
reaches no output.

Forward only: the decode runs in the rollout, where nothing is
differentiated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from actor_critic_tpu.ops import pallas_scan

# Blocking of the kernel (time, never results). A grid step holds one tile of
# an expert's three matrices, `[hidden, tile]` of gate and up and `[tile,
# hidden]` of down, and the pipeline keeps two steps' tiles in flight. The
# tile is the whole expert width where two whole experts fit this budget (the
# shipped presets' 12.4 MB and 9.4 MB experts in bfloat16 do: contiguous
# fetches, one grid step an expert), else the widest multiple of 128 lanes
# that divides the width and fits.
VMEM_BLOCK_BYTES = 40 * 2**20
# What the kernel may take of VMEM beside the weight tiles: the rows, the
# float32 output and the tile's intermediates (the chip has 128 MiB).
VMEM_REST_BYTES = 16 * 2**20


def tiles(hidden: int, width: int) -> bool:
    """Whether the kernel's blocks tile an expert `[hidden, width]`: both a
    multiple of the 128 lanes."""
    return hidden % 128 == 0 and width % 128 == 0


def block_width(hidden: int, width: int, itemsize: int) -> int:
    """The tile of the expert width a grid step takes (see above)."""
    fits = [w for w in range(128, width + 1, 128)
            if width % w == 0 and 6 * hidden * w * itemsize <= VMEM_BLOCK_BYTES]
    return max(fits, default=128)


def reference(experts, h, weights_here, cd):
    """`sum_e weights_here[n, e] E_e(h[n])` with every held expert on every
    token, as batched matmuls: `y [N, H]` float32."""
    def einsum(spec, a, b):
        return jnp.einsum(spec, a.astype(cd), b.astype(cd),
                          preferred_element_type=jnp.float32)

    with jax.named_scope("moe_experts"):
        act = jax.nn.silu(einsum("nh,ehw->enw", h, experts["w_gate"])) \
            * einsum("nh,ehw->enw", h, experts["w_up"])
        out = einsum("enw,ewh->enh", act, experts["w_down"])
    with jax.named_scope("moe_route"):
        return jnp.einsum("enh,ne->nh", out, weights_here)


def chosen(sizes):
    """What the kernel prefetches, from `sizes [held]` (assignments an
    expert): (`ids [held]` int32, the experts with at least one assignment
    first, in order, the last of them repeated after them (0 where there is
    none); `n_active` int32, how many those are)."""
    held = sizes.shape[0]
    expert = jnp.arange(held, dtype=jnp.int32)
    picked = sizes > 0
    rank = jnp.cumsum(picked.astype(jnp.int32)) - 1      # among the chosen
    n_active = rank[-1] + 1
    at = picked[None, :] & (rank[None, :] == expert[:, None])
    ids = jnp.sum(jnp.where(at, expert[None, :], 0), axis=1)
    last = jnp.max(jnp.where(picked, expert, 0))
    return jnp.where(expert < n_active, ids, last).astype(jnp.int32), n_active


def _kernel(ids_ref, n_ref, x_ref, w_ref, gate_ref, up_ref, down_ref, y_ref):
    s, j = pl.program_id(0), pl.program_id(1)

    # The chip leaves unwritten what nothing writes: the output starts at
    # zero here, whatever the routing (no expert chosen: zeros).
    @pl.when((s == 0) & (j == 0))
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)

    @pl.when(s < n_ref[0])
    def _():
        x = x_ref[...]
        dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
        act = jax.nn.silu(dot(x, gate_ref[...])) * dot(x, up_ref[...])
        out = dot(act.astype(x.dtype), down_ref[...])
        # Column `ids[s]` of the routing weights, picked under a mask (a
        # dynamic lane index is no vector operation).
        w = w_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        w = jnp.sum(jnp.where(lane == ids_ref[s], w, 0.0), axis=1, keepdims=True)
        y_ref[...] += w * out


def moe_decode(experts, h, weights_here, sizes, cd):
    """`reference` as one kernel (`moe_decode` in the HLO text and in a
    trace) over the experts with `sizes[e] > 0`: `experts` the held experts'
    `w_gate`, `w_up` `[held, H, W]` and `w_down [held, W, H]`, `h [N, H]`,
    `weights_here [N, held]` float32 (zero where token `n` did not choose
    `e`, so zero in every column with `sizes[e] == 0`), `sizes [held]`.
    Needs `tiles(H, W)`; the rows are padded to the sublane multiple of
    `cd`. Off a TPU it runs the Pallas interpreter (tests); the program asks
    `engages` first."""
    cd = jnp.dtype(cd)
    N, H = h.shape
    held, _, W = experts["w_gate"].shape
    if not tiles(H, W):
        raise ValueError(
            f"experts of hidden={H}, width={W} are not whole tiles of 128 lanes")
    tw = block_width(H, W, cd.itemsize)
    n_tiles = W // tw
    rows = -N % (32 // cd.itemsize)          # 8 rows a float32 tile, 16 a bfloat16
    x = jnp.pad(h.astype(cd), ((0, rows), (0, 0)))
    w = jnp.pad(weights_here.astype(jnp.float32), ((0, rows), (0, 0)))
    ids, n_active = chosen(sizes)

    def whole(s, j, ids_ref, n_ref):
        return (0, 0)

    # Past the last chosen expert a step names the block of the step before
    # it (the last chosen expert's last tile): nothing to fetch.
    def tile(s, j, n_ref):
        return jnp.where(s < n_ref[0], j, n_tiles - 1)

    def gate_up(s, j, ids_ref, n_ref):
        return (ids_ref[s], 0, tile(s, j, n_ref))

    def down(s, j, ids_ref, n_ref):
        return (ids_ref[s], tile(s, j, n_ref), 0)

    with jax.named_scope("moe_experts"):
        y = pl.pallas_call(
            _kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(held, n_tiles),
                in_specs=[
                    pl.BlockSpec(x.shape, whole),
                    pl.BlockSpec(w.shape, whole),
                    pl.BlockSpec((None, H, tw), gate_up),
                    pl.BlockSpec((None, H, tw), gate_up),
                    pl.BlockSpec((None, tw, H), down),
                ],
                out_specs=pl.BlockSpec(x.shape, whole),
            ),
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=6 * H * tw * cd.itemsize + VMEM_REST_BYTES),
            # What the call costs where every held expert is chosen (each
            # operand read once). The compiler plans its own prefetches of
            # the step's other weights round the call by this: with no
            # estimate it moved the attention cache and the `lm_head` through
            # VMEM every step and two thirds of the kernel's gain went into
            # waits for those copies (PERF.md, Findings, PR 35).
            cost_estimate=pl.CostEstimate(
                flops=held * 3 * 2 * x.shape[0] * H * W,
                transcendentals=held * x.shape[0] * W,
                bytes_accessed=held * 3 * H * W * cd.itemsize
                + x.size * cd.itemsize + w.size * 4 + x.size * 4),
            interpret=not pallas_scan.on_tpu(),
            name="moe_decode",
        )(ids, jnp.reshape(n_active, (1,)), x, w, experts["w_gate"].astype(cd),
          experts["w_up"].astype(cd), experts["w_down"].astype(cd))
    return y[:N]


def engages(hidden: int, width: int) -> bool:
    """Whether the program takes the kernel for experts `[hidden, width]`: on
    a TPU, where they tile; the batched matmuls everywhere else (the rule of
    `pallas_scan.*_auto`; the interpreter inside a rollout's scan would
    crawl). `models/seq_policy.reads_chosen_only` is the program's entry."""
    return pallas_scan.on_tpu() and tiles(hidden, width)
