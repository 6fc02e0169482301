"""The sparse layers' exact top-k selection, as one Pallas TPU kernel that
holds a block of rows of scores in VMEM and finds each row's k-th largest
score one bit a pass.

A sparse layer of `models/seq_policy.py` lets each query keep the `topk`
keys of the largest indexer score among a prefix of its row, the set
`lax.top_k` picks, ties to the lower position (`seq_policy.top_k_mask`: the
reference, and the program's path off a TPU). As XLA operations that is a
radix select four bits a pass, each pass comparing every score with fifteen
candidate digits in fusions of its own, so the row goes through HBM on every
pass, and a cumsum over the row for the ties. The kernel reads a block of
`ROWS` rows once, keeps their order keys in VMEM, and writes the mask once:

- the order key is `top_k_mask`'s (the float's bits as an integer that
  sorts like the float, the positions past the row's valid length at
  `-inf`), held as int32 with its sign bit flipped, so that the signed
  compares of the vector unit order it;
- the k-th largest key is found from the top bit down: a candidate is kept
  where at least `k` keys of the row lie at or above it, one compare and
  one count a key and bit, 32 passes over VMEM;
- every key above it is kept; of the keys equal to it the first `room = k -
  above` in position order, by the same search over the positions' bits,
  run only where some row of the block has more such keys than room (ties
  at the threshold, or a row of fewer valid keys than `k`).

The mask is `top_k_mask`'s bit for bit: the same keys, the same threshold,
the same ties. Forward only: the selection is discrete and carries no
gradient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from actor_critic_tpu.ops import pallas_scan

# Blocking of the kernel (time, never results): rows of scores a grid step,
# `[ROWS, S]` float32 in VMEM with their order keys beside them, and the
# columns a step of the (unrolled) counting loop takes.
ROWS = 32
LANES = 512
_SIGN = -(2**31)


def engages(S: int, k: int) -> bool:
    """Whether the program takes the kernel for rows of `S` scores and a
    selection of `k`: on a TPU, where a row is whole tiles of 128 lanes and
    `k` drops some key; `seq_policy.top_k_mask` everywhere else. Read from
    the input; no flag."""
    return pallas_scan.on_tpu() and S % 128 == 0 and k < S


def _kernel(n_ref, s_ref, o_ref, key_ref, pos_ref, *, k: int, width: int):
    R, S = s_ref.shape
    chunks = S // width
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, width), 1)
    n = jnp.broadcast_to(n_ref[...], (R, width))

    def cols(c):
        return pl.ds(pl.multiple_of(c * width, width), width)

    def keys(c, carry):
        x = jnp.where(lane + c * width < n, s_ref[:, cols(c)], -jnp.inf)
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        key_ref[:, cols(c)] = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
        return carry

    jax.lax.fori_loop(0, chunks, keys, 0)

    def count(test):
        """[R, 1]: the columns of each row where `test(key, column)`."""
        def body(c, acc):
            return acc + test(key_ref[:, cols(c)], lane + c * width).astype(jnp.int32)
        acc = jax.lax.fori_loop(0, chunks, body, jnp.zeros((R, width), jnp.int32),
                                unroll=True)
        return jnp.sum(acc, axis=1, keepdims=True)

    # The k-th largest key, unsigned, from the top bit down: the largest
    # value with at least k keys at or above it.
    def bit(i, found):
        at_least = found | jnp.left_shift(jnp.int32(1), 31 - i)
        bound = jnp.broadcast_to(at_least ^ _SIGN, (R, width))
        return jnp.where(count(lambda key, _: key >= bound) >= k, at_least, found)

    kth = jnp.broadcast_to(
        jax.lax.fori_loop(0, 32, bit, jnp.zeros((R, 1), jnp.int32)) ^ _SIGN, (R, width))
    room = k - count(lambda key, _: key > kth)
    ties = count(lambda key, _: key == kth)

    # Of the keys equal to the k-th, those before the position below which
    # `room` of them lie: every one where they fit.
    pos_ref[...] = jnp.full((R, 1), S, jnp.int32)

    @pl.when(jnp.max(ties - room) > 0)
    def _():
        def position_bit(i, found):
            below = found | jnp.left_shift(jnp.int32(1), S.bit_length() - 1 - i)
            bound = jnp.broadcast_to(below, (R, width))
            fit = count(lambda key, col: (key == kth) & (col < bound)) <= room
            return jnp.where(fit, below, found)

        pos_ref[...] = jax.lax.fori_loop(
            0, S.bit_length(), position_bit, jnp.zeros((R, 1), jnp.int32))

    first = jnp.broadcast_to(pos_ref[...], (R, width))

    def mask(c, carry):
        key, col = key_ref[:, cols(c)], lane + c * width
        keep = (col < n) & ((key > kth) | ((key == kth) & (col < first)))
        o_ref[:, cols(c)] = keep.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunks, mask, 0)


def select(scores, lengths, k: int):
    """`top_k_mask(scores, arange(S) < lengths[:, None], k)` as the kernel
    (`topk_select` in the HLO text and in a trace): `scores [rows, S]`
    float32, `lengths [rows]` int32, each row's valid prefix; the mask as
    int8 `[rows, S]`. Needs `S % 128 == 0` and `k < S`; the rows are padded
    to the block. Off a TPU it runs the Pallas interpreter (tests); the
    program asks `engages` first."""
    rows, S = scores.shape
    if S % 128 or k >= S:
        raise ValueError(f"rows of {S} scores and k={k}: no kernel (engages)")
    R = min(ROWS, -(-rows // 8) * 8)
    pad = -rows % R
    s = jnp.pad(scores.astype(jnp.float32), ((0, pad), (0, 0)))
    n = jnp.pad(lengths.astype(jnp.int32), (0, pad))[:, None]
    width = max(w for w in (LANES, 256, 128) if S % w == 0)
    out = pl.pallas_call(
        functools.partial(_kernel, k=k, width=width),
        grid=(s.shape[0] // R,),
        in_specs=[pl.BlockSpec((R, 1), lambda i: (i, 0)),
                  pl.BlockSpec((R, S), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((R, S), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(s.shape, jnp.int8),
        scratch_shapes=[pltpu.VMEM((R, S), jnp.int32), pltpu.VMEM((R, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0, bytes_accessed=s.size * 5 + n.size * 4),
        interpret=not pallas_scan.on_tpu(),
        name="topk_select",
    )(n, s)
    return out[:rows]
