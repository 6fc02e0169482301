"""Causal attention of the grouped-query layers as one flash-style Pallas TPU
kernel a row, which never writes the scores to HBM: JAX's splash attention,
with a dynamic mask (the sparse layers) or a static one (the window and full
layers).

As dense masked einsums (`reference`, or `seq_policy.gqa_unroll`'s blocks) a
block of queries scores the keys of the row and writes `[heads, queries,
keys]` float32 scores and probabilities to HBM. The kernel cuts the row's
square into the caller's tiles (`block_q` x `block_kv`); a tile with no kept
pair is neither computed nor fetched, a tile whose pairs are all kept takes
no mask, and the scores live in VMEM only. The forward, dq and dkv kernels
walk the same tiles, so the backward pass skips what the forward skipped.
The group of query heads is laid out as the kernel's heads over the
key/value heads (grouped-query attention), and one mask serves every head.

- `attend`, a sparse layer: the row's `keep [T, T]`, the keys its indexer
  selects, a subset of the causal ones. Each head fetches a partial tile's
  mask again, which Pallas hands to Mosaic as int32 (1 MB a 512 x 512 tile,
  four times a tile of keys and values together).
- `attend_band`, a window or full layer: the keys `[t - window + 1, t]` of
  query `t` (`window` >= T: the causal prefix), a mask built at trace time
  (splash's `LocalMask` / `CausalMask`). The kernel computes a partial
  tile's mask from the positions; no `[T, T]` array and no mask tile exists
  in the program.

Numerics: the operands are `compute_dtype`, the scale folded into q in
float32 before its one cast (by the caller), the scores and the softmax
float32. The kernel multiplies the float32 probabilities by the values where
`reference` rounds them to `compute_dtype` first, and it divides by the sum
at the end; both are within the operands' rounding. Every query keeps at
least one key (`top_k_mask` keeps `min(topk, causal)`; a band holds the
query's own), so no row of the softmax is empty.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as masks

from actor_critic_tpu.ops import pallas_scan

def tiles(T: int, head_dim: int, block_q: int, block_kv: int) -> bool:
    """Whether tiles of `block_q` queries x `block_kv` keys cover a row of `T`
    positions whole, and the tiles and the head are multiples of the 128
    lanes."""
    return (T % block_q == 0 and T % block_kv == 0
            and block_q % 128 == 0 and block_kv % 128 == 0 and head_dim % 128 == 0)


def takes_kernel(T: int, head_dim: int, block_q: int, block_kv: int) -> bool:
    """Whether a causal pass takes the kernel: on a TPU, where the shapes
    tile. Read from the input; no flag."""
    return pallas_scan.on_tpu() and tiles(T, head_dim, block_q, block_kv)


def reference(q, k, v, keep, scale: float):
    """Dense masked attention: `q [E, Q, kv heads, group, d]` against `k`,
    `v [E, S, kv heads, d]` under `keep [E, Q, S]`, float32 `[E, Q, kv
    heads, group, d]`. Operands in `k`'s dtype, float32 sums, the
    probabilities rounded to that dtype before the value product."""
    cd = k.dtype

    def einsum(spec, a, b):
        return jnp.einsum(spec, a.astype(cd), b.astype(cd),
                          preferred_element_type=jnp.float32)

    s = einsum("eqkgd,eskd->ekgqs", q, k) * scale
    s = jnp.where(keep[:, None, None], s, -jnp.inf)
    return einsum("ekgqs,eskd->eqkgd", jax.nn.softmax(s, axis=-1), v)


# The name of the kernel's residuals (its output and the softmax's
# log-sum-exp), for a caller's `jax.checkpoint` policy.
RESIDUALS = "sparse_attention_residuals"


def _rows(make, q, k, v, *mask):
    """The kernel `make(mask of a row)` gives, one row at a time: `q [E, T,
    kv heads, group, d]`, `k`, `v [E, T, kv heads, d]`, the heads laid out
    as splash's, the group of query heads over their key/value head."""
    E, T, nkv, group, d = q.shape

    def row(args):
        q, k, v, *mask = args
        kernel = make(*mask)
        heads = lambda x: jnp.moveaxis(x, 0, -2).reshape(-1, T, d)  # noqa: E731
        out = kernel(heads(q), heads(k), heads(v))
        return jnp.moveaxis(out.reshape(nkv, group, T, d), 2, 0)

    return jax.lax.map(row, (q, k, v, *mask))


def _kernel(mask, block_q: int, block_kv: int):
    """Splash's forward, dq and dkv kernels over `mask [1, T, T]` (one mask
    for every head), tiles of `block_q` x `block_kv`, the residuals named
    `RESIDUALS`. Off a TPU, the Pallas interpreter (tests)."""
    sizes = splash.BlockSizes(
        block_q=block_q, block_kv=block_kv, block_kv_compute=block_kv,
        block_q_dkv=block_q, block_kv_dkv=block_kv, block_kv_dkv_compute=block_kv,
        block_q_dq=block_q, block_kv_dq=block_kv)
    return splash.make_splash_mha_single_device(
        mask, block_sizes=sizes, residual_checkpoint_name=RESIDUALS,
        interpret=not pallas_scan.on_tpu())


def attend(q, k, v, keep, *, block_q: int, block_kv: int):
    """`reference` as the kernel, the scale already in `q`: `q [E, T, kv
    heads, group, d]`, `k`, `v [E, T, kv heads, d]` in one dtype, `keep [E,
    T, T]` bool; `[E, T, kv heads, group, d]` in that dtype. Tiles of
    `block_q` x `block_kv` (`tiles`) for the forward, dq and dkv kernels."""
    return _rows(lambda keep: _kernel(keep[None], block_q, block_kv), q, k, v, keep)


def attend_band(q, k, v, *, window: int, block_q: int, block_kv: int):
    """`attend` under the band of `window` keys a query, its own counted
    (`window` >= T: causal), the same for every row, built at trace time."""
    T = q.shape[1]
    band = masks.CausalMask((T, T)) if window >= T else masks.LocalMask(
        (T, T), window_size=(window - 1, 0), offset=0)
    kernel = _kernel(masks.MultiHeadMask([band]), block_q, block_kv)
    return _rows(lambda: kernel, q, k, v)
