"""The matmuls of the token-level models (`seq_policy`, `gated_delta`):
`compute_dtype` operands, float32 sums."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mm(x, w, cd, out=jnp.float32):
    """`x @ w` over the last axis: `compute_dtype` operands, float32 sums.
    `out=cd` hands the result on rounded, where it is a matmul operand only."""
    return jax.lax.dot_general(
        x.astype(cd), w.astype(cd), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(out)


def einsum(spec, a, b, cd):
    return jnp.einsum(spec, a.astype(cd), b.astype(cd),
                      preferred_element_type=jnp.float32)
