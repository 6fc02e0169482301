"""A decode step's attention over the latent (MLA) cache, as one Pallas TPU
kernel that reads the cache once and only as far as it is filled.

With the queries absorbed into the latent space, a decode step of
`models/seq_policy.py` scores one query a head against every cached latent
(`q_lat . c_kv + q_rope . k_r`), takes the softmax over the filled prefix
and sums the same latents under it: every head of a row reads the same
`[T, rank]` block twice. As two einsums XLA streams the layer's cache twice
(or keeps every layer's cache in VMEM and evicts it to HBM again each step:
PERF.md, PR 32). The kernel walks a row block's cache a block of positions
at a time, uses each block for the scores and for the values while it is in
VMEM (an online softmax carries the running maximum and sum across the
blocks), and never fetches a block that lies wholly past `slot`: its index
is clamped to the last filled block, and the pipeline does not fetch the
block it already holds.

The cache is the whole model's stacked pair (`c_kv [layers, E, T, rank]`,
`k_r [layers, E, T, rope]`), at home in HBM; `layer` is static and picks
the block's first index.

Numerics are the einsum path's (`reference`): operands in the cache's dtype,
float32 sums, the probabilities rounded to the cache's dtype before the
value product. The one difference is where the softmax divides: the kernel
rounds `exp(s - running max)` and divides the float32 sum at the end, the
einsums divide first; both are within the operands' rounding. Positions
past `slot` carry weight zero in `reference` and are not read here, so
whatever the cache holds there (NaN too) reaches no output.

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

# Blocking of the kernel (time, never results): rows of the batch and cache
# positions a grid step. 8 x 128 x 512 bf16 latents are 1 MB a block, so a
# row block's pipeline (two blocks in flight, queries, the float32 output)
# takes about 4 MB of VMEM; at 128 positions the mean read over a rollout
# is 62.5% of the cache.
BLOCK_ROWS = 8
BLOCK_POSITIONS = 128


def tiles(E: int, T: int, rank: int) -> bool:
    """Whether the kernel's blocks tile a cache `[.., E, T, rank]`: whole
    row and position blocks, latents a multiple of the 128 lanes."""
    return E % BLOCK_ROWS == 0 and T % BLOCK_POSITIONS == 0 and rank % 128 == 0


def reference(q_lat, q_rope, c_kv, k_r, layer: int, slot, scale: float):
    """The same attention as two einsums over the layer's whole cache:
    `o_lat [E, heads, rank]` float32."""
    cd = c_kv.dtype
    c_all, r_all = c_kv[layer], k_r[layer]

    def einsum(spec, a, b):
        return jnp.einsum(spec, a.astype(cd), b, preferred_element_type=jnp.float32)

    s = einsum("ehc,etc->eht", q_lat, c_all) + einsum("ehr,etr->eht", q_rope, r_all)
    s = jnp.where(jnp.arange(c_all.shape[1]) <= slot, s * scale, -jnp.inf)
    return einsum("eht,etc->ehc", jax.nn.softmax(s, axis=-1), c_all)


def _kernel(scale, slot_ref, q_lat_ref, q_rope_ref, c_ref, r_ref, o_ref, m_ref, l_ref):
    j = pl.program_id(1)
    slot = slot_ref[0]
    first = j * BLOCK_POSITIONS          # the block's first position

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    def block(partly_filled: bool):
        c = c_ref[...]
        batched = lambda a, b, contract: jax.lax.dot_general(  # noqa: E731
            a, b, (contract, ((0,), (0,))), preferred_element_type=jnp.float32)
        s = batched(q_lat_ref[...], c, ((2,), (2,))) \
            + batched(q_rope_ref[...], r_ref[...], ((2,), (2,)))
        s = s * scale
        if partly_filled:
            # The block that holds `slot`: the positions past it get weight
            # zero, and their latents are zeroed too (0 x NaN is NaN).
            live = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) <= slot
            s = jnp.where(live, s, -jnp.inf)
            live = first + jax.lax.broadcasted_iota(jnp.int32, c.shape, 1) <= slot
            c = jnp.where(live, c.astype(jnp.float32), 0.0).astype(c.dtype)
        m_prev = m_ref[...]
        m = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        shrink = jnp.exp(m_prev - m)
        p = jnp.exp(s - m)
        m_ref[...] = m
        l_ref[...] = shrink * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        o_ref[...] = shrink * o_ref[...] + batched(p.astype(c.dtype), c, ((2,), (1,)))

    # Block 0 always holds position 0 <= slot, so the running maximum is
    # finite from the first block on. Blocks wholly past `slot` do nothing
    # (and were not fetched: `positions` clamps their index).
    pl.when(first + BLOCK_POSITIONS - 1 <= slot)(lambda: block(False))
    pl.when((first <= slot) & (slot < first + BLOCK_POSITIONS - 1))(lambda: block(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = o_ref[...] / l_ref[...]


def mla_decode(q_lat, q_rope, c_kv, k_r, layer: int, slot, scale: float):
    """`reference` as one kernel (`mla_decode` in the HLO text and in a
    trace): `q_lat [E, heads, rank]`, `q_rope [E, heads, rope]`, the stacked
    cache pair, the layer (static) and `slot` (a traced scalar: positions
    `0..slot` are filled). Needs `tiles(E, T, rank)`. Off a TPU it runs the
    Pallas interpreter (tests); `mla_decode_auto` is the program's entry."""
    E, heads, rank = q_lat.shape
    rope = q_rope.shape[-1]
    T = c_kv.shape[2]
    if not tiles(E, T, rank):
        raise ValueError(
            f"a cache of E={E}, T={T}, rank={rank} is not whole blocks of "
            f"{BLOCK_ROWS} rows x {BLOCK_POSITIONS} positions x 128 lanes")
    cd = c_kv.dtype

    def rows(i, j, slot_ref):
        return (i, 0, 0)

    def positions(i, j, slot_ref):
        return (layer, i, jnp.minimum(j, slot_ref[0] // BLOCK_POSITIONS), 0)

    return pl.pallas_call(
        functools.partial(_kernel, float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(E // BLOCK_ROWS, T // BLOCK_POSITIONS),
            in_specs=[
                pl.BlockSpec((BLOCK_ROWS, heads, rank), rows),
                pl.BlockSpec((BLOCK_ROWS, heads, rope), rows),
                pl.BlockSpec((None, BLOCK_ROWS, BLOCK_POSITIONS, rank), positions),
                pl.BlockSpec((None, BLOCK_ROWS, BLOCK_POSITIONS, rope), positions),
            ],
            out_specs=pl.BlockSpec((BLOCK_ROWS, heads, rank), rows),
            scratch_shapes=[pltpu.VMEM((BLOCK_ROWS, heads, 1), jnp.float32)] * 2,
        ),
        out_shape=jax.ShapeDtypeStruct((E, heads, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=not pallas_scan.on_tpu(),
        name="mla_decode",
    )(jnp.reshape(slot, (1,)).astype(jnp.int32), q_lat.astype(cd), q_rope.astype(cd),
      c_kv, k_r)


def mla_decode_auto(q_lat, q_rope, c_kv, k_r, layer: int, slot, scale: float):
    """The kernel where the program is on a TPU and the cache tiles, the
    einsums everywhere else (the rule of `pallas_scan.*_auto`; the
    interpreter inside a rollout's scan would crawl)."""
    E, _, rank = q_lat.shape
    if pallas_scan.on_tpu() and tiles(E, c_kv.shape[2], rank):
        return mla_decode(q_lat, q_rope, c_kv, k_r, layer, slot, scale)
    return reference(q_lat, q_rope, c_kv, k_r, layer, slot, scale)
