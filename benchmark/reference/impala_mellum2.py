"""Plain reference for the `impala_mellum2` configuration.

The decoder block of Mellum2-12B-A2.5B-Instruct as its config.json publishes it
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json;
`model_type` mellum: grouped-query attention, window and full layers 3:1 with a
RoPE each, 64 softmax-routed experts, no shared expert, no dense layer), then
IMPALA's V-trace loss over token positions. Float32 `jax.numpy` at `highest`
matmul precision; no cache, no kernels, no batching: the full causal pass of
one row (one episode of T tokens) at a time with the window as a MASK over all
T keys, every held expert on every token with its routing weight. The only
blocking is over queries (`QUERIES` a block, all keys each), so that a row of
4,096 fits: `[32, 4096, 4096]` float32 scores would be 2.1 GB a copy. The
blocks, the held experts and V-trace's steps are loops the compiler meets once
(`lax.map`, `lax.scan`), not Python loops: unrolled, V-trace's 4,096 steps
alone took the chip's compiler a quarter of an hour, and the check the whole
time limit of a run.

    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g
    x += Attn_l(RMSNorm(x)); x += MoE(RMSNorm(x)); h = RMSNorm(x)
    logits = h W_lm; value = h w_v + b_v
    Attn: q = x W_q -> [32, 128]; k = x W_k, v = x W_v -> [4, 128]; no bias
          RoPE on all 128 dimensions of q and k, pairs (j, j + 64):
          (a, b) -> (a cos - b sin, b cos + a sin) * factor, angle = pos * f_j
          query head i reads key/value head i // 8; scores q.k / sqrt(128)
          out = concat_h softmax(scores over the keys the layer allows) v  W_o
      layer_types[l] == "sliding_attention": f_j = theta^(-2j/128); keys s with
          0 <= t - s < sliding_window; factor 1
      "full_attention": keys s <= t; YaRN (the arithmetic of HF's
          `_compute_yarn_parameters`, written out in `yarn_frequencies`);
          factor = attention_factor on cos and sin
    MoE:  p = softmax(x W_r) over all num_experts; idx = top-k of p;
          w = p[idx] / sum(p[idx]); y = sum_{i in idx, held here} w_i E_i(x);
          E(x) = down(silu(gate x) * up x)

Departures from the published model, each because the configuration states it
(`reduced` / `assumed` of benchmark/configs/impala_mellum2.json): the chip's
share of the experts (`experts_held` from `expert_offset`; what absent experts
would add is left out, here as in the program, and the partial result goes
on), the vocabulary slice, one period (4) of the 28 layers, no per-head norm on
q and k, no multi-token-prediction head, a scalar value head on the final
norm's output.

Every width is read from the parameters' shapes; `network` gives the counts
and constants a shape cannot (heads, the window, the RoPE keys, experts per
token, eps, the expert offset, the layers' kinds).

The loss is V-trace over the row's steps (`vtrace`: the recursion of
`reference/impala_pong.py::vtrace`, which a test holds it to) with the token
env's reading: steps whose observation says `is_prompt` (the env ignored
the action) leave every mean and take importance ratio 1; every episode
terminates inside its row, so the bootstrap value is zero.

Two more keys hold what no router decides, on the tree with every expert's
down-projection set to zero (`zero_down`; the expert layer then adds exactly
nothing whatever the routing): `logits_attn`, this file's causal pass over the
tokens of a second rollout, and `decode_logp`, the log-probability of that
rollout's own actions relative to a uniform policy (`+ log V`: the seeded head
is near uniform, so the raw log-probabilities are all about -10.1 and their
relative error would be blind), at the positions the loss mask keeps.

TOLERANCE: see the configuration file's `tolerance.why`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QUERIES = 512  # queries a block of the scores, against all T keys


def _mm(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32), precision=HI)


def _norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def yarn_frequencies(dim: int, rope: dict):
    """(inv_freq [dim / 2], attention_factor) of `rope_parameters.<kind>`.

    `rope_type` default: `theta^(-2j/dim)`, factor 1. `yarn`: pair j turns
    `original_max_position_embeddings * theta^(-2j/dim) / (2 pi)` times over
    the original context; solving for the pair that turns n times gives
    `dim * ln(L / (2 pi n)) / (2 ln theta)`. `low` = floor of that for
    `beta_fast`, `high` = ceil for `beta_slow`, both clamped to [0, dim - 1];
    `ramp_j = clip((j - low) / (high - low), 0, 1)`; the frequency is
    `inv_freq_j / factor` weighted by the ramp plus `inv_freq_j` weighted by
    `1 - ramp`, at every position."""
    theta = float(rope["rope_theta"])
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") != "yarn":
        return inv.astype(np.float32), 1.0
    L = rope["original_max_position_embeddings"]

    def pair(turns):
        return dim * math.log(L / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair(rope["beta_fast"])), 0)
    high = min(math.ceil(pair(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv = inv / rope["factor"] * ramp + inv * (1 - ramp)
    return inv.astype(np.float32), float(rope["attention_factor"])


def _rope(x, pos, inv, factor):
    """x [T, heads, d], pos [T]: rotate pairs (j, j + d/2)."""
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, x, pos, kind: str, net):
    T = x.shape[0]
    heads, kv_heads = net["num_attention_heads"], net["num_key_value_heads"]
    d = p["w_q"].shape[1] // heads
    inv, factor = yarn_frequencies(d, net["rope_parameters"][kind])
    q = _rope(_mm(x, p["w_q"]).reshape(T, heads, d), pos, inv, factor)
    k = _rope(_mm(x, p["w_k"]).reshape(T, kv_heads, d), pos, inv, factor)
    v = _mm(x, p["w_v"]).reshape(T, kv_heads, d)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))  # head i <- i // 8
    window = net["sliding_window"] if kind == "sliding_attention" else T
    block = math.gcd(T, QUERIES)

    def attend(q0):
        ahead = (q0 + jnp.arange(block))[:, None] - jnp.arange(T)[None, :]
        s = jnp.einsum("thd,shd->hts", jax.lax.dynamic_slice_in_dim(q, q0, block), k,
                       precision=HI) / math.sqrt(d)
        s = jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v, precision=HI)

    out = jax.lax.map(attend, jnp.arange(0, T, block))
    return _mm(out.reshape(T, heads * d), p["w_o"])


def _mlp(p, x):
    return _mm(jax.nn.silu(_mm(x, p["w_gate"])) * _mm(x, p["w_up"]), p["w_down"])


def route(router, x, k: int):
    """(idx [T, k] over all experts, weights [T, k]): softmax over all the
    logits, the top k by probability, renormalised over the chosen."""
    prob = jax.nn.softmax(_mm(x, router), axis=-1)
    chosen, idx = jax.lax.top_k(prob, k)
    return idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def moe(p, x, net, offset=None):
    """The part of the expert layer that the experts `[offset, offset + held)`
    give; `held` is read from the parameters' shapes."""
    offset = net["expert_offset"] if offset is None else offset
    idx, w = route(p["router"], x, net["num_experts_per_tok"])

    def add(y, held):
        e, expert = held
        w_e = jnp.sum(jnp.where(idx == offset + e, w, 0.0), axis=-1)  # [T]
        return y + w_e[:, None] * _mlp(expert, x), None

    experts = p["experts"]
    y, _ = jax.lax.scan(add, jnp.zeros_like(x), (jnp.arange(experts["w_gate"].shape[0]), experts))
    return y


def forward_row(params, tokens, positions, network: dict):
    """(logits [T, V], values [T]) for one row of T tokens."""
    p = params["params"]
    eps = network["rms_norm_eps"]
    x = p["embed"][tokens]
    i = 0
    while f"layer_{i}" in p:
        layer = p[f"layer_{i}"]
        x = x + _attention(layer["attn"], _norm(x, layer["attn_norm"], eps),
                           positions, network["layer_types"][i], network)
        x = x + moe(layer["moe"], _norm(x, layer["ffn_norm"], eps), network)
        i += 1
    h = _norm(x, p["final_norm"], eps)
    value = _mm(h, p["value_head"]["kernel"])[:, 0] + p["value_head"]["bias"][0]
    return _mm(h, p["lm_head"]), value


def forward(params, obs, network: dict):
    """(logits [T, E, V], values [T, E]) for observations [T, E, 3], a row
    (an episode) at a time."""
    rows = jnp.swapaxes(obs, 0, 1)
    logits, values = jax.lax.map(
        lambda row: forward_row(params, row[:, 0], row[:, 1], network), rows)
    return jnp.swapaxes(logits, 0, 1), values.T


def zero_down(params) -> dict:
    """The tree with every expert's down-projection set to zero: each expert
    then gives exactly zero, so nothing in the logits hangs on which of two
    near-equal router probabilities is the larger."""
    p = dict(params["params"])
    for name, layer in p.items():
        if name.startswith("layer_"):
            experts = {**layer["moe"]["experts"],
                       "w_down": jnp.zeros_like(layer["moe"]["experts"]["w_down"])}
            p[name] = {**layer, "moe": {**layer["moe"], "experts": experts}}
    return {"params": p}


def vtrace(target_lp, behaviour_lp, rewards, values, dones, bootstrap,
           gamma, rho_bar, c_bar, lam):
    """V-trace targets (arXiv:1802.01561, eq. 1), the recursion of
    `reference/impala_pong.py::vtrace` step for step, as a reverse `lax.scan`
    over time where that file unrolls a Python loop: 4,096 unrolled steps
    take the compiler a quarter of an hour. Returns (vs [T, E],
    pg_advantages [T, E])."""
    ratio = jnp.exp(target_lp - behaviour_lp)
    rho = jnp.minimum(rho_bar, ratio)
    c = lam * jnp.minimum(c_bar, ratio)
    disc = gamma * (1.0 - dones)
    v_next = jnp.concatenate([values[1:], bootstrap[None]])
    delta = rho * (rewards + disc * v_next - values)

    def back(acc, step):
        delta_t, carry_t = step
        acc = delta_t + carry_t * acc
        return acc, acc

    _, acc = jax.lax.scan(back, jnp.zeros_like(bootstrap), (delta, disc * c), reverse=True)
    vs = values + acc
    vs_next = jnp.concatenate([vs[1:], bootstrap[None]])
    return vs, rho * (rewards + disc * vs_next - values)


def loss_and_targets(params, traj: dict, bootstrap_obs, hp: dict,
                     network: dict) -> dict:
    """The loss (as its three terms, see `reference/impala_joyai_flash.py`),
    the advantage targets and the logits for one [T, E] trajectory of the
    token env, and for the second rollout the seam put beside it
    (`decode_obs`, `decode_action`, made with `zero_down(params)` as the actor)
    `logits_attn` and `decode_logp` of that same tree. `bootstrap_obs` is not
    evaluated: every episode terminated inside its row."""
    with jax.default_matmul_precision("highest"):
        logits, values = forward(params, traj["obs"], network)
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
        attn, _ = forward(zero_down(params), traj["decode_obs"], network)
        attn_lp = attn - jax.scipy.special.logsumexp(attn, axis=-1, keepdims=True)
        decode_lp = jnp.take_along_axis(
            attn_lp, traj["decode_action"].astype(jnp.int32)[..., None], axis=-1)[..., 0]
        kept = 1.0 - traj["decode_obs"][..., 2].astype(jnp.float32)
        return {"loss": loss, "pg_advantages": pg, "value_targets": vs,
                "logits": logits, "logits_attn": attn,
                "decode_logp": (decode_lp + math.log(attn.shape[-1])) * kept}
