"""Plain reference for the `ppo_halfcheetah` configuration.

PPO-clip as published for MuJoCo (Schulman et al. 2017, arXiv:1707.06347,
Table 3): a tanh MLP 2x64 for the policy mean and a separate one for the
value, a state-independent log-std, GAE(gamma, lambda), the clipped surrogate.

Departures from the paper, each because the shipped configuration has it:
- the value loss is clipped round the old value (`vf_clip`), as in the
  authors' released code and not in the paper;
- advantages are normalized over the batch;
- time-limit truncations bootstrap: reward += gamma * V(final_obs) there.

TOLERANCE: the numbers and their origin are in
benchmark/configs/ppo_halfcheetah.json; see impala_pong.py's note on default
against `highest` precision.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_LOG_2PI = math.log(2.0 * math.pi)


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _torso(p, x):
    i = 0
    while f"dense_{i}" in p:
        x = jnp.tanh(_mm(x, p[f"dense_{i}"]["kernel"]) + p[f"dense_{i}"]["bias"])
        i += 1
    return x


def forward(params, obs):
    """(mean [B, A], log_std [A], value [B]) for observations [B, D]."""
    p = params["params"]
    x = obs.astype(jnp.float32)
    za = _torso(p["pi_torso"], x)
    zc = _torso(p["vf_torso"], x)
    mean = _mm(za, p["policy"]["kernel"]) + p["policy"]["bias"]
    value = (_mm(zc, p["value"]["kernel"]) + p["value"]["bias"])[:, 0]
    return mean, p["log_std"], value


def greedy_action(params, obs):
    """What the gateway serves by default: the policy's mode (the mean)."""
    with jax.default_matmul_precision("highest"):
        return forward(params, obs)[0]


def gae(rewards, values, dones, bootstrap, gamma, lam, use_dones: bool = True):
    """GAE by a reverse Python loop over time: (advantages, returns)."""
    T = rewards.shape[0]
    nonterm = 1.0 - dones if use_dones else jnp.ones_like(rewards)
    adv = [None] * T
    acc = jnp.zeros_like(bootstrap)
    for t in reversed(range(T)):
        v_next = values[t + 1] if t + 1 < T else bootstrap
        delta = rewards[t] + gamma * v_next * nonterm[t] - values[t]
        acc = delta + gamma * lam * nonterm[t] * acc
        adv[t] = acc
    adv = jnp.stack(adv)
    return adv, adv + values


def loss_and_targets(params, traj: dict, bootstrap_obs, hp: dict,
                     network: dict, use_dones: bool = True) -> dict:
    """The first minibatch-free PPO loss (whole batch, parameters unchanged,
    so ratio = 1 up to rounding) and the GAE targets for a [T, E] block."""
    del network
    with jax.default_matmul_precision("highest"):
        T, E = traj["reward"].shape
        flat = lambda x: x.reshape(T * E, *x.shape[2:])  # noqa: E731
        _, _, bootstrap = forward(params, bootstrap_obs)
        _, _, final_v = forward(params, flat(traj["final_obs"]))
        truncated = traj["done"] * (1.0 - traj["terminated"])
        rewards = traj["reward"] + hp["gamma"] * final_v.reshape(T, E) * truncated
        adv, ret = gae(rewards, traj["value"], traj["done"], bootstrap,
                       hp["gamma"], hp["gae_lambda"], use_dones=use_dones)
        mean, log_std, value = forward(params, flat(traj["obs"]))
        z = (flat(traj["action"]) - mean) / jnp.exp(log_std)
        log_prob = jnp.sum(-0.5 * (z * z + _LOG_2PI) - log_std, axis=-1)
        entropy = jnp.sum(log_std + 0.5 * (_LOG_2PI + 1.0))
        a = adv.reshape(-1)
        a = (a - jnp.mean(a)) / (jnp.sqrt(jnp.maximum(
            jnp.mean(a * a) - jnp.mean(a) ** 2, 0.0)) + 1e-8)
        ratio = jnp.exp(log_prob - flat(traj["log_prob"]))
        eps = hp["clip_eps"]
        pg_loss = -jnp.mean(jnp.minimum(
            ratio * a, jnp.clip(ratio, 1.0 - eps, 1.0 + eps) * a))
        v_old, r = flat(traj["value"]), ret.reshape(-1)
        v_clip = v_old + jnp.clip(value - v_old, -hp["vf_clip"], hp["vf_clip"])
        v_loss = 0.5 * jnp.mean(jnp.maximum((value - r) ** 2, (v_clip - r) ** 2))
        loss = pg_loss + hp["value_coef"] * v_loss - hp["entropy_coef"] * entropy
        return {"loss": loss, "pg_advantages": adv, "value_targets": ret}
