"""Plain reference for the `impala_joyai_flash` configuration.

The decoder block of JoyAI-LLM-Flash as its config.json publishes it
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json;
the DeepSeek-V3 layer family: latent attention, sigmoid-routed experts with a
score-correction bias, one shared expert), then IMPALA's V-trace loss over
token positions. Float32 `jax.numpy` at `highest` matmul precision; no cache,
no kernels, no batching: the full forward pass of one row (one episode of T
tokens) at a time, every held expert on every token with its routing weight.

    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g
    x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x)); h = RMSNorm(x)
    logits = h W_lm; value = h w_v + b_v
    MLA: c_q = RMSNorm(x W_qa); q = c_q W_qb -> heads x (nope + rope)
         [c_kv, k_r] = x W_kva; c_kv = RMSNorm(c_kv)
         [k_nope, v] = c_kv W_kvb -> heads x (nope + v)
         RoPE on q_rope and on k_r (one for all heads): pairs (2i, 2i+1)
         rotated by position * theta^(-2i/d)
         out = concat_h softmax(q.k / sqrt(nope + rope), causal) v  W_o
    FFN, first layer: down(silu(gate x) * up x)
    FFN, expert layers: s = sigmoid(x W_g); idx = top-k of (s + b);
         w = s[idx] / (sum(s[idx]) + 1e-20) * routed_scaling_factor;
         y = sum_{i in idx, held here} w_i E_i(x) + E_shared(x)

Departures from the published model, each because the configuration states it
(`reduced` / `assumed` of benchmark/configs/impala_joyai_flash.json): the
chip's share of the experts (`experts_held` from `expert_offset`; what absent
experts would add is left out, here as in the program, and the partial result
goes on), the vocabulary slice, 5 of 40 layers, no multi-token-prediction
module, a frozen bias `b`, a scalar value head on the final norm's output.

Every width is read from the parameters' shapes; `network` gives the counts
and constants a shape cannot (heads, experts per token, scaling factor, eps,
theta, expert offset).

The loss is `reference/impala_pong.py`'s V-trace over the row's steps with the
token env's reading: steps whose observation says `is_prompt` (the env ignored
the action) leave every mean and take importance ratio 1; every episode
terminates inside its row, so the bootstrap value is zero.

TOLERANCE: see the configuration file's `tolerance.why`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import harness

HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32), precision=HI)


def _norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x [T, ..., d], pos [T]: rotate pairs (2i, 2i+1)."""
    d = x.shape[-1]
    freq = theta ** (-(2.0 * jnp.arange(d // 2)) / d)
    ang = pos.astype(jnp.float32).reshape(-1, *([1] * (x.ndim - 1))) * freq
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(even * jnp.cos(ang) - odd * jnp.sin(ang))
    return out.at[..., 1::2].set(even * jnp.sin(ang) + odd * jnp.cos(ang))


def _mla(p, x, pos, net):
    T = x.shape[0]
    heads, eps, theta = net["num_attention_heads"], net["rms_norm_eps"], net["rope_theta"]
    rank = p["kv_norm"].shape[0]
    rope = p["w_kva"].shape[1] - rank
    nope = p["w_qb"].shape[1] // heads - rope
    q = _mm(_norm(_mm(x, p["w_qa"]), p["q_norm"], eps), p["w_qb"])
    q = q.reshape(T, heads, nope + rope)
    kv = _mm(x, p["w_kva"])
    c_kv = _norm(kv[:, :rank], p["kv_norm"], eps)
    k_r = _rope(kv[:, rank:], pos, theta)                       # [T, rope]
    kvb = _mm(c_kv, p["w_kvb"]).reshape(T, heads, -1)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, None, :], (T, heads, rope))], axis=-1)
    scores = jnp.einsum("thd,shd->hts", q, k, precision=HI) / jnp.sqrt(
        float(nope + rope))
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -jnp.inf)
    out = jnp.einsum("hts,shv->thv", jax.nn.softmax(scores, axis=-1), v, precision=HI)
    return _mm(out.reshape(T, -1), p["w_o"])


def _mlp(p, x):
    return _mm(jax.nn.silu(_mm(x, p["w_gate"])) * _mm(x, p["w_up"]), p["w_down"])


def _moe(p, x, net):
    k, offset = net["num_experts_per_tok"], net["expert_offset"]
    held = p["experts"]["w_gate"].shape[0]
    s = jax.nn.sigmoid(_mm(x, p["router"]))                      # [T, all]
    _, idx = jax.lax.top_k(s + p["bias"], k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    w = w * net["routed_scaling_factor"]
    y = _mlp(p["shared"], x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(idx == offset + e, w, 0.0), axis=-1)  # [T]
        y = y + w_e[:, None] * _mlp(jax.tree.map(lambda a: a[e], p["experts"]), x)
    return y


def forward_row(params, tokens, positions, network: dict):
    """(logits [T, V], values [T]) for one row of T tokens."""
    p = params["params"]
    eps = network["rms_norm_eps"]
    x = p["embed"][tokens]
    i = 0
    while f"layer_{i}" in p:
        layer = p[f"layer_{i}"]
        x = x + _mla(layer["mla"], _norm(x, layer["attn_norm"], eps), positions, network)
        h = _norm(x, layer["ffn_norm"], eps)
        x = x + (_moe(layer["moe"], h, network) if "moe" in layer
                 else _mlp(layer["mlp"], h))
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


def dense_prefix(params) -> dict:
    """The model cut before its first expert layer: embedding, the dense
    layers, final norm, heads. No router, so nothing in its logits hangs on
    which of two near-equal scores is the larger."""
    p = params["params"]
    keep, i = {k: v for k, v in p.items() if not k.startswith("layer_")}, 0
    while f"layer_{i}" in p and "moe" not in p[f"layer_{i}"]:
        keep[f"layer_{i}"] = p[f"layer_{i}"]
        i += 1
    return {"params": keep}


def loss_and_targets(params, traj: dict, bootstrap_obs, hp: dict,
                     network: dict) -> dict:
    """The loss, the advantage targets and the logits (of the whole model,
    and of `dense_prefix`) for one [T, E] trajectory of the token env.
    `bootstrap_obs` is not evaluated: every episode terminated inside its row.

    `loss` is the scalar loss as its three terms `[pg_loss, value_coef x
    v_loss, -entropy_coef x entropy]`, which sum to it: the policy-gradient
    term takes either sign, so on some seeds the sum is a hundredth of its
    terms and an error relative to the SUM says nothing (a seed read 2.9 for
    terms that agreed to 1e-2: my chip run, PR 29). `harness.compare` takes
    the largest difference over the largest reference value, so the terms are
    compared against the largest of them."""
    vtrace = harness.load_module("reference", "impala_pong").vtrace
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
        dense, _ = forward(dense_prefix(params), traj["obs"], network)
        return {"loss": loss, "pg_advantages": pg, "value_targets": vs,
                "logits": logits, "logits_dense": dense}
