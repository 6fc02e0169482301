"""Plain reference for the `impala_qwen3_next` configuration.

The decoder block of Qwen3-Next-80B-A3B-Instruct as its config.json publishes
it (https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json;
`model_type` qwen3_next: Gated DeltaNet layers and gated full-attention layers
3:1, `full_attention_interval` 4; 512 softmax-routed experts, top-10,
renormalised, and one shared expert with a sigmoid gate in every layer), then
IMPALA's V-trace loss over token positions. Float32 `jax.numpy` at `highest`
matmul precision; no cache, no kernels, no batching: the full causal pass of
one row (one episode of T tokens) at a time. The delta rule is the plain
recurrence, one position a step of a `lax.scan` (not the chunked form the
program runs); full attention is blocked over queries only (`QUERIES` a
block, all keys each, through `lax.map`), so that a row of 16,384 fits;
V-trace is a `lax.scan`: loops the compiler meets once.

    RMSNorm0(y) = y / sqrt(mean(y^2) + eps) * (1 + w)     (w starts at 0)
    x += Mixer_l(RMSNorm0(x)); x += MoE(RMSNorm0(x)); h = RMSNorm0(x)
    logits = h W_lm; value = h w_v + b_v
    Mixer, layer l with (l + 1) % 4 != 0, Gated DeltaNet (16 key heads and
          32 value heads of 128):
          [q | k | v | z] = h W_qkvz (2,048, 2,048, 4,096, 4,096); [b | a] = h W_ba
          [q | k | v] <- SiLU(causal depthwise conv, kernel 4, no bias), zero
          before position 0
          q <- q / sqrt(|q|^2 + 1e-6) / sqrt(128); k <- k / sqrt(|k|^2 + 1e-6)
          per key head; value head j reads key head j // 2
          beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias)
          S_t = exp(g_t) S_{t-1} + k_t (beta_t (v_t - S_{t-1}^T k_t exp(g_t)))^T,
          S_-1 = 0, o_t = S_t^T q_t            ([128, 128] a value head)
          o <- o / sqrt(mean(o^2) + eps) * w_o * SiLU(z) per value head; out = o W_out
    Mixer, layer l with (l + 1) % 4 == 0, gated attention (16 query heads over
          2 key/value heads of 256):
          q = h W_q, gate = h W_gate -> [16, 256]; k = h W_k, v = h W_v -> [2, 256]
          q <- RMSNorm0(q), k <- RMSNorm0(k) per head
          RoPE on the first 64 dimensions, pairs (j, j + 32), f_j = theta^(-2j/64),
          theta 1e7; the other 192 pass through
          query head i reads key/value head i // 8; scores q.k / 16, causal
          out = (softmax(scores) v * sigmoid(gate)) W_o
    MoE:  p = softmax(x W_r) over all 512; idx = top-10 of p;
          w = p[idx] / sum(p[idx]); y = sum_{i in idx, held here} w_i E_i(x)
          + sigmoid(x w_sg) E_shared(x); E(x) = down(silu(gate x) * up x)

`q_proj` of the source gives each head's query and gate side by side; the
tree keeps its two halves as `w_q` and `w_gate`, and `in_proj_qkvz` /
`in_proj_ba` as one matrix each with their columns in the order above: a
layout of the same numbers, the same function of seeded weights.

Departures from the published model, each because the configuration states it
(`reduced` / `assumed` of benchmark/configs/impala_qwen3_next.json): the chip's
share of the experts (what absent experts would add is left out, here as in
the program, and the partial result goes on; the shared expert is whole), the
vocabulary slice, one period (4) of the 48 layers, no multi-token-prediction
module, a scalar value head on the final norm's output.

Every width is read from the parameters' shapes; `network` gives the counts
and constants a shape cannot (heads, `full_attention_interval`,
`partial_rotary_factor`, the RoPE theta, experts per token, eps, the expert
offset).

The loss and the two keys no router decides (`logits_attn`, `decode_logp`, on
the tree with every routed expert's down-projection set to zero) are
`reference/impala_mellum2.py`'s, over this file's forward pass; so are
V-trace (a reverse `lax.scan`) and the matmul at `highest`.

TOLERANCE: see the configuration file's `tolerance.why`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import impala_mellum2 as mellum

HI = jax.lax.Precision.HIGHEST
QUERIES = 512  # queries a block of the full layer's scores, against all T keys
_mm, _mlp, vtrace = mellum._mm, mellum._mlp, mellum.vtrace


def norm0(x, w, eps):
    """The zero-centred RMSNorm: the weight `w` applied as `1 + w`."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def conv(x, w, tail=None):
    """`sum_j w[j] x[t - K + 1 + j]` over `x [T, channels]`, `w [K, channels]`,
    with the K - 1 positions before 0 taken from `tail` (zeros where None)."""
    K = w.shape[0]
    if tail is None:
        tail = jnp.zeros((K - 1, x.shape[1]), jnp.float32)
    full = jnp.concatenate([tail, x])
    return sum(full[j:j + x.shape[0]] * w[j] for j in range(K))


def delta_rule(q, k, v, g, beta, state=None):
    """The gated delta rule one position at a time: `q`, `k [T, heads, d_k]`,
    `v [T, heads, d_v]`, `g`, `beta [T, heads]` -> (o [T, heads, d_v], the
    final state [heads, d_k, d_v])."""
    if state is None:
        state = jnp.zeros((k.shape[1], k.shape[2], v.shape[2]), jnp.float32)

    def one(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, None, None] * S
        u = beta[:, None] * (v - jnp.einsum("hdv,hd->hv", S, k, precision=HI))
        S = S + k[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hdv,hd->hv", S, q, precision=HI)

    state, o = jax.lax.scan(one, state, (q, k, v, g, beta))
    return o, state


def linear_attention(p, x, net, tail=None, state=None):
    """A Gated DeltaNet mixer over one row `x [T, H]` (after the tail and
    state of earlier positions, where given): (out [T, H], (the final state,
    the last K - 1 inputs of the convolution))."""
    nk, nv = net["linear_num_key_heads"], net["linear_num_value_heads"]
    dk, dv = net["linear_key_head_dim"], net["linear_value_head_dim"]
    T = x.shape[0]
    qkvz = _mm(x, p["w_qkvz"])
    mixed = qkvz[:, :2 * nk * dk + nv * dv]
    z = qkvz[:, 2 * nk * dk + nv * dv:].reshape(T, nv, dv)
    b, a = jnp.split(_mm(x, p["w_ba"]), 2, axis=-1)
    K = p["conv"].shape[0]
    history = mixed if tail is None else jnp.concatenate([tail, mixed])
    new_tail = jnp.pad(history, [(max(0, K - 1 - history.shape[0]), 0), (0, 0)])[-(K - 1):]
    out = jax.nn.silu(conv(mixed, p["conv"], tail))
    q, k, v = out[:, :nk * dk], out[:, nk * dk:2 * nk * dk], out[:, 2 * nk * dk:]
    unit = lambda y: y / jnp.sqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)  # noqa: E731
    q = jnp.repeat(unit(q.reshape(T, nk, dk)) / math.sqrt(dk), nv // nk, axis=1)
    k = jnp.repeat(unit(k.reshape(T, nk, dk)), nv // nk, axis=1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o, state = delta_rule(q, k, v.reshape(T, nv, dv), g, beta, state)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + net["rms_norm_eps"])
    o = o * p["norm"] * jax.nn.silu(z)
    return _mm(o.reshape(T, nv * dv), p["w_out"]), (state, new_tail)


def partial_rope(x, pos, rotary: int, theta: float):
    """RoPE of the first `rotary` dimensions of `x [T, heads, d]`, pairs
    (j, j + rotary/2); the rest pass through."""
    inv = theta ** (-np.arange(0, rotary, 2, dtype=np.float64) / rotary)
    ang = pos.astype(jnp.float32)[:, None, None] * inv.astype(np.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b, rest = x[..., :rotary // 2], x[..., rotary // 2:rotary], x[..., rotary:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def full_attention(p, x, pos, net):
    T = x.shape[0]
    heads, kv_heads = net["num_attention_heads"], net["num_key_value_heads"]
    eps, theta = net["rms_norm_eps"], float(net["rope_theta"])
    d = p["w_q"].shape[1] // heads
    rotary = int(d * net["partial_rotary_factor"])
    q = partial_rope(norm0(_mm(x, p["w_q"]).reshape(T, heads, d), p["q_norm"], eps),
                     pos, rotary, theta)
    k = partial_rope(norm0(_mm(x, p["w_k"]).reshape(T, kv_heads, d), p["k_norm"], eps),
                     pos, rotary, theta)
    v = _mm(x, p["w_v"]).reshape(T, kv_heads, d)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))  # head i <- i // 8
    block = math.gcd(T, QUERIES)

    def attend(q0):
        causal = (q0 + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        s = jnp.einsum("thd,shd->hts", jax.lax.dynamic_slice_in_dim(q, q0, block), k,
                       precision=HI) / math.sqrt(d)
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v, precision=HI)

    out = jax.lax.map(attend, jnp.arange(0, T, block)).reshape(T, heads * d)
    return _mm(out * jax.nn.sigmoid(_mm(x, p["w_gate"])), p["w_o"])


def moe(p, x, net, offset=None, routed=1.0):
    """The part of the expert layer that the experts `[offset, offset +
    held)` give, times `routed`, plus the gated shared expert (whole on every
    chip). `routed` 0 is the tree with every routed expert's down-projection
    set to zero (`zero_down`): those experts then give exactly zero."""
    y = routed * mellum.moe(p, x, net, offset)
    return y + jax.nn.sigmoid(_mm(x, p["shared_gate"])) * _mlp(p["shared"], x)


def is_full(layer: int, net: dict) -> bool:
    return (layer + 1) % net["full_attention_interval"] == 0


def forward_row(params, tokens, positions, network: dict, routed=1.0):
    """(logits [T, V], values [T]) for one row of T tokens; `routed` as in
    `moe`."""
    p = params["params"]
    eps = network["rms_norm_eps"]
    x = p["embed"][tokens]
    i = 0
    while f"layer_{i}" in p:
        layer = p[f"layer_{i}"]
        h = norm0(x, layer["attn_norm"], eps)
        if is_full(i, network):
            x = x + full_attention(layer["attn"], h, positions, network)
        else:
            x = x + linear_attention(layer["linear"], h, network)[0]
        x = x + moe(layer["moe"], norm0(x, layer["ffn_norm"], eps), network,
                    routed=routed)
        i += 1
    h = norm0(x, p["final_norm"], eps)
    value = _mm(h, p["value_head"]["kernel"])[:, 0] + p["value_head"]["bias"][0]
    return _mm(h, p["lm_head"]), value


def forward(params, obs, network: dict, routed=None):
    """(logits [T, E, V], values [T, E]) for observations [T, E, 3], a row
    (an episode) at a time; `routed [E]` as in `moe` (1 where None)."""
    rows = jnp.swapaxes(obs, 0, 1)
    if routed is None:
        routed = jnp.ones(rows.shape[0], jnp.float32)
    logits, values = jax.lax.map(
        lambda a: forward_row(params, a[0][:, 0], a[0][:, 1], network, a[1]),
        (rows, routed))
    return jnp.swapaxes(logits, 0, 1), values.T


def loss_and_targets(params, traj: dict, bootstrap_obs, hp: dict,
                     network: dict) -> dict:
    """The loss (as its three terms), the advantage targets and the logits
    for one [T, E] trajectory of the token env, and for the seam's second
    rollout (`decode_obs`, `decode_action`, made with `zero_down(params)` as
    the actor) `logits_attn` and `decode_logp` of that same tree:
    `reference/impala_mellum2.py::loss_and_targets` line for line, over this
    file's forward pass. `bootstrap_obs` is not evaluated: every episode
    terminated inside its row."""
    with jax.default_matmul_precision("highest"):
        # Both trees' rows through one forward pass (one compiled body):
        # the trajectory's with every expert, the second rollout's with
        # the routed experts zeroed.
        E = traj["obs"].shape[1]
        both, values = forward(
            params, jnp.concatenate([traj["obs"], traj["decode_obs"]], axis=1),
            network, jnp.repeat(jnp.array([1.0, 0.0], jnp.float32), E))
        logits, attn, values = both[:, :E], both[:, E:], values[:, :E]
        logp_all = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        target_lp = jnp.take_along_axis(
            logp_all, traj["action"].astype(jnp.int32)[..., None], axis=-1)[..., 0]
        mask = 1.0 - traj["obs"][..., 2].astype(jnp.float32)
        count = jnp.maximum(jnp.sum(mask), 1.0)
        entropy = jnp.sum(-jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1) * mask) / count
        vs, pg = vtrace(
            jnp.where(mask > 0, target_lp, traj["log_prob"]), traj["log_prob"],
            traj["reward"], values, traj["done"], jnp.zeros_like(values[0]),
            hp["gamma"], hp["rho_bar"], hp["c_bar"], hp["lam"])
        pg_loss = -jnp.sum(pg * target_lp * mask) / count
        v_loss = 0.5 * jnp.sum((values - vs) ** 2 * mask) / count
        loss = jnp.stack([pg_loss, hp["value_coef"] * v_loss,
                          -hp["entropy_coef"] * entropy])
        attn_lp = attn - jax.scipy.special.logsumexp(attn, axis=-1, keepdims=True)
        decode_lp = jnp.take_along_axis(
            attn_lp, traj["decode_action"].astype(jnp.int32)[..., None], axis=-1)[..., 0]
        kept = 1.0 - traj["decode_obs"][..., 2].astype(jnp.float32)
        return {"loss": loss, "pg_advantages": pg, "value_targets": vs,
                "logits": logits, "logits_attn": attn,
                "decode_logp": (decode_lp + math.log(attn.shape[-1])) * kept}
