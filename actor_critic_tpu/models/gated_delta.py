"""Gated DeltaNet (arXiv:2412.06464), the linear-attention kind of layer of
`models/seq_policy.py` (`"linear_attention"` in its `kinds`): a delta-rule
recurrence over a matrix state a value head, with a data-dependent decay, in
place of attention over keys. Its cache is that state and the tail of a
causal convolution, whose size does not grow with the row.

The layer, under the source's `linear_*` keys (key heads `n_k` x `d_k`, value
heads `n_v` x `d_v`, conv kernel `K`), on `h`, the layer's normed input:

- `[q | k | v | z] = h W_qkvz` (`n_k d_k`, `n_k d_k`, `n_v d_v`, `n_v d_v`) and
  `[b | a] = h W_ba` (`n_v` each): one matrix each, its columns in that order;
- a causal depthwise convolution of kernel `K`, no bias, over the channels of
  `[q | k | v]`, positions before 0 zero, then SiLU;
- `q <- l2norm(q) d_k^-1/2`, `k <- l2norm(k)` per key head (eps 1e-6); key
  head `j // (n_v / n_k)` serves value head `j`;
- `beta = sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)` per value head;
- with `S_-1 = 0` (`[d_k, d_v]` a value head): `S_t = exp(g_t) S_{t-1} + k_t
  (beta_t (v_t - S_{t-1}^T k_t exp(g_t)))^T`, `o_t = S_t^T q_t`;
- `o <- RMSNorm(o) w_norm SiLU(z)` per value head, `out = o W_out`.

The causal pass (`unroll`: the update and the prefill) runs the recurrence
in chunks of `CHUNK` positions (the chunked form of the paper). Within a
chunk, with `G` the cumulative sum of `g`, the unit lower system `(I +
beta_i k_i.k_j exp(G_i - G_j))` (strictly lower part) is inverted once, by
block forward substitution, and gives `W` (of `beta k exp(G)`) and `U` (of
`beta v`), in parallel over every chunk; then one `lax.scan` carries `S`
across the chunks:

    v' = U - W S;  o = (q exp(G)) S + causal(q k^T exp(G_i - G_j)) v'
    S <- exp(G_C) S + (k exp(G_C - G))^T v'

Every exponent is a difference on the lower triangle or a cumulative sum
from the chunk's start, so none is above 0: `exp(-G_j)` alone, which a decay
of hundreds over a chunk would overflow, is never formed. The decode step
(`step`) applies the recurrence to one position. The matmuls of the chunked
pass take `compute_dtype` operands with float32 sums; the decay, `beta`, the
solve and the state are float32, and the decode's state update is float32
throughout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from actor_critic_tpu.models.matmul import einsum as _einsum, mm as _mm

LINEAR = "linear_attention"
# Positions a chunk of the causal pass: tiling of the computation, never of
# the result (the source's kernels use 64).
CHUNK = 64
# Chunks a step of the causal pass's outer scan (memory, never results): a
# block's solve is alive at once, in the backward pass too.
CHUNKS_A_BLOCK = 16
_EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


def conv_channels(cfg) -> int:
    """Channels of the convolution, `[q | k | v]`: what the conv tail keeps."""
    return 2 * cfg.linear_num_key_heads * cfg.linear_key_head_dim + \
        cfg.linear_num_value_heads * cfg.linear_value_head_dim


def init_params(mat, uniform, cfg) -> dict:
    """A linear layer's mixer: `mat(*shape)` gives seeded `normal /
    sqrt(fan_in)` matrices, `uniform(shape, lo, hi)` seeded uniforms. `A_log
    = log U(0, 16)` (floored at `log 1e-6`) and `dt_bias = 1`, as the source
    initialises them."""
    H, nv = cfg.hidden_size, cfg.linear_num_value_heads
    value = nv * cfg.linear_value_head_dim
    return {
        "w_qkvz": mat(H, conv_channels(cfg) + value),
        "w_ba": mat(H, 2 * nv),
        "conv": mat(cfg.linear_conv_kernel_dim, 1, conv_channels(cfg))[:, 0],
        "A_log": jnp.log(jnp.maximum(uniform((nv,), 0.0, 16.0), _EPS)),
        "dt_bias": jnp.ones((nv,), jnp.float32),
        "norm": jnp.ones((cfg.linear_value_head_dim,), jnp.float32),
        "w_out": mat(value, H),
    }


def _project(p, h, cfg):
    """(`[q | k | v]` before the convolution in `compute_dtype`, z [.., n_v,
    d_v], beta, g [.., n_v] float32)."""
    cd = jnp.dtype(cfg.compute_dtype)
    C = conv_channels(cfg)
    mixed = _mm(h, p["w_qkvz"][:, :C], cd, out=cd)
    z = _mm(h, p["w_qkvz"][:, C:], cd).reshape(
        *h.shape[:-1], cfg.linear_num_value_heads, -1)
    b, a = jnp.split(_mm(h, p["w_ba"], cd), 2, axis=-1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    return mixed, z, beta, g


def _heads(mixed, cfg):
    """q, k [.., n_k, d_k] (normed, q scaled) and v [.., n_v, d_v] of the
    convolution's output `mixed [.., channels]` float32."""
    nk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    q, k, v = jnp.split(mixed, [nk * dk, 2 * nk * dk], axis=-1)
    lead = mixed.shape[:-1]

    def l2(x):
        x = x.reshape(*lead, nk, dk)
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _EPS)

    return l2(q) * dk ** -0.5, l2(k), v.reshape(*lead, cfg.linear_num_value_heads, -1)


def _out(p, o, z, cfg):
    """The gated norm and the output projection of `o [.., n_v, d_v]`."""
    cd = jnp.dtype(cfg.compute_dtype)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    o = (o * p["norm"] * jax.nn.silu(z)).reshape(*o.shape[:-2], -1)
    return _mm(o, p["w_out"], cd)


def conv(x, w, tail=None):
    """The causal depthwise convolution of `x [E, T, channels]` by `w [K,
    channels]`, float32, before SiLU: output `t` is `sum_j w[j] x[t - K + 1 +
    j]`, the positions before the row's first taken from `tail [E, K - 1,
    channels]` (zeros where None)."""
    K = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1).astype(jnp.float32)
    T = x.shape[1]
    return sum(full[:, j:j + T] * w[j] for j in range(K))


def _divisor(n: int, most: int) -> int:
    """The largest divisor of `n` that is at most `most`."""
    most = min(most, n)
    while n % most:
        most -= 1
    return most


def _unit_lower_inverse(A):
    """`(I + A)^-1` of a strictly lower triangular `A [.., C, C]`, float32
    at highest precision, by doubling the diagonal blocks: from the inverses
    of the two halves of a block, the block's is `[[X, 0], [-Y A_21 X,
    Y]]`, six levels for a chunk of 64 (a `C` that is no power of two is
    padded with identity rows). This is block forward substitution, stable
    wherever the inverse is bounded: the series `sum (-A)^n` is not, since
    its terms grow like binomials where keys repeat (a row padded with one
    token: `k_i . k_j = 1`) and cancel to the inverse's small entries, which
    float32 cannot do. XLA's triangular solve expands into a loop over the
    rows."""
    C = A.shape[-1]
    n = 1 << (C - 1).bit_length()
    A = jnp.pad(A, [(0, 0)] * (A.ndim - 2) + [(0, n - C)] * 2)
    mm = functools.partial(jnp.matmul, precision=_HI)
    inv = jnp.ones((*A.shape[:-2], n, 1, 1), jnp.float32)
    size = 1
    while size < n:
        m = n // (2 * size)
        blocks = A.reshape(*A.shape[:-2], m, 2 * size, m, 2 * size)
        a21 = jnp.einsum("...iaib->...iab", blocks)[..., size:, :size]
        upper, lower = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        below = -mm(mm(lower, a21), upper)
        inv = jnp.concatenate([
            jnp.concatenate([upper, jnp.zeros_like(upper)], axis=-1),
            jnp.concatenate([below, lower], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :C, :C]


def chunked(q, k, v, g, beta, cd, chunk: int = CHUNK):
    """The recurrence over `q`, `k [E, T, n_v, d_k]`, `v [E, T, n_v, d_v]`,
    `g`, `beta [E, T, n_v]` from `S = 0`, in chunks of `chunk` (a last short
    one padded with positions that leave `S` as it is): (o [E, T, n_v, d_v]
    float32, the final S [E, n_v, d_k, d_v] float32, each row's smallest sum
    of `g` over a chunk [E]). `q`, `k`, `v` are matmul operands only, so
    they may come in `cd`; so are the chunks' `W`, decayed `q` and `k` and
    causal scores, which are held in `cd` (`U`, the system's inverse and the
    state are float32).

    The chunks go `CHUNKS_A_BLOCK` at a time through an outer `lax.scan`
    whose step is rematerialized in the backward pass: a block's chunks are
    solved in parallel and an inner scan carries `S` across them, so what
    the backward pass keeps is `S` at each block's start, and the solve's
    intermediates are alive a block at a time."""
    E, T, nv, dk = k.shape
    pad = -T % chunk
    n = (T + pad) // chunk
    b = _divisor(n, CHUNKS_A_BLOCK)

    def blocks(x):  # [E, T, nv, ...] -> [n / b, b, E, nv, chunk, ...]
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(E, n, chunk, *x.shape[2:])
        x = jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)
        return x.reshape(n // b, b, *x.shape[1:])

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    @jax.checkpoint
    def across(S, c):
        W, U, attn, qd, kd, last = c
        vn = U - _einsum("...cd,...dv->...cv", W, S, cd)
        o = _einsum("...cd,...dv->...cv", qd, S, cd) + _einsum(
            "...ij,...jv->...iv", attn, vn, cd)
        return last[..., None, None] * S + _einsum("...cd,...cv->...dv", kd, vn, cd), o

    @jax.checkpoint
    def block(S, c):
        q, k, v, g, beta = c                                      # [b, E, nv, C, ..]
        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
        G = jnp.cumsum(g, axis=-1)
        decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :], -jnp.inf))
        kk = _einsum("...id,...jd->...ij", k, k, cd)
        inv = _unit_lower_inverse(jnp.where(strict, beta[..., :, None] * kk * decay, 0.0))
        W = _einsum("...ij,...jd->...id", inv, (beta * jnp.exp(G))[..., None] * k32,
                    cd).astype(cd)
        U = _einsum("...ij,...jd->...id", inv, beta[..., None] * v32, cd)
        attn = jnp.where(lower, _einsum("...id,...jd->...ij", q, k, cd) * decay,
                         0.0).astype(cd)
        q_decayed = (q32 * jnp.exp(G)[..., None]).astype(cd)
        k_decayed = (k32 * jnp.exp(G[..., -1:] - G)[..., None]).astype(cd)
        S, o = jax.lax.scan(across, S, (W, U, attn, q_decayed, k_decayed,
                                        jnp.exp(G[..., -1])))
        return S, (o, jnp.min(G[..., -1], axis=(0, 2)))

    S = jnp.zeros((E, nv, dk, v.shape[-1]), jnp.float32)
    S, (o, lowest) = jax.lax.scan(block, S, tuple(map(blocks, (q, k, v, g, beta))))
    o = jnp.moveaxis(jnp.moveaxis(o.reshape(n, *o.shape[2:]), 3, 2), 0, 1)
    return o.reshape(E, n * chunk, nv, -1)[:, :T], S, jnp.min(lowest, axis=0)


def recurrent_step(q, k, v, g, beta, S):
    """One position: `q`, `k [E, n_v, d_k]`, `v [E, n_v, d_v]`, `g`, `beta
    [E, n_v]`, `S [E, n_v, d_k, d_v]`, all float32: (o [E, n_v, d_v], S)."""
    S = jnp.exp(g)[..., None, None] * S
    u = beta[..., None] * (v - jnp.einsum("ehdv,ehd->ehv", S, k, precision=_HI))
    S = S + k[..., :, None] * u[..., None, :]
    return jnp.einsum("ehdv,ehd->ehv", S, q, precision=_HI), S


def _spread(x, cfg):
    """Key heads to value heads: value head `j` reads key head `j // r`."""
    return jnp.repeat(x, cfg.linear_num_value_heads // cfg.linear_num_key_heads, axis=-2)


def unroll(p, h, cfg):
    """The causal pass over `h [E, T, H]`: (out [E, T, H], (the final state
    [E, n_v, d_k, d_v] float32, the conv tail [E, K - 1, channels] in
    `compute_dtype`): what the cache keeps after position T - 1, each row's
    smallest sum of `g` over a chunk [E])."""
    cd = jnp.dtype(cfg.compute_dtype)
    mixed, z, beta, g = _project(p, h, cfg)
    K = cfg.linear_conv_kernel_dim
    tail = jnp.pad(mixed, [(0, 0), (max(0, K - 1 - h.shape[1]), 0), (0, 0)])[:, -(K - 1):]
    q, k, v = (x.astype(cd) for x in _heads(jax.nn.silu(conv(mixed, p["conv"])), cfg))
    with jax.named_scope("gdn_scan"):
        o, S, lowest = chunked(_spread(q, cfg), _spread(k, cfg), v, g, beta, cd)
    return _out(p, o, z, cfg), (S, tail), lowest


def step(p, h, cache, index: int, cfg):
    """One token a row through the linear layer whose state and conv tail
    are layer `index` of `cache = (S [layers, E, n_v, d_k, d_v] float32,
    tail [layers, E, K - 1, channels])`: the token's `[q | k | v]` goes
    through the convolution after the tail and becomes its last entry, and
    the state takes one step. Returns (out [E, H], cache)."""
    states, tails = cache
    mixed, z, beta, g = _project(p, h, cfg)
    tail = tails[index]
    q, k, v = _heads(jax.nn.silu(conv(mixed[:, None], p["conv"], tail)[:, 0]), cfg)
    with jax.named_scope("gdn_scan"):
        o, S = recurrent_step(_spread(q, cfg), _spread(k, cfg), v, g, beta, states[index])
    tail = jnp.concatenate([tail[:, 1:], mixed[:, None].astype(tail.dtype)], axis=1)
    return _out(p, o, z, cfg), fill(cache, index, S, tail)


def fill(cache, index: int, state, tail):
    """Layer `index` of `cache = (states, tails)` set to `state`, `tail`, in
    place."""
    put = lambda buf, new: jax.lax.dynamic_update_index_in_dim(  # noqa: E731
        buf, new.astype(buf.dtype), index, 0)
    return put(cache[0], state), put(cache[1], tail)
