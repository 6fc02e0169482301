"""Numpy host-side actor mirrors (SURVEY.md §7.2 item 2).

The host-env trainers' wall-clock path is: step the (1-core) host pool,
round-trip to the device for every batched `act`, then block on the
device update before the next rollout can start. These mirrors remove
both device dependencies from the collection loop:

- acting is a few small numpy matmuls on the host (the policies are
  2-layer MLPs — the premise is that a device round-trip costs more
  than the forward pass; ROADMAP S3/D5 re-measure it on the chip),
- the jitted update is dispatched asynchronously and computes on-device
  WHILE the host collects the next rollout, using acting params that are
  one update stale (fetched from the previous iteration's output, which
  is concrete by then — no wait). PPO's clipped importance ratio and the
  off-policy algorithms' replay make 1-update staleness semantically
  clean; IMPALA formalizes the same idea (algos/impala.py).

Mirrors cover the MLP-torso networks (the host-env configs:
BASELINE.json:8-10). CNN torsos are not mirrored — pixel pools keep the
device acting path (`supports_mirror` returns False).

Parity with the flax modules is tested in tests/test_host_actor.py
(logits/means/values allclose against `Module.apply`).
"""

from __future__ import annotations

import math
from typing import Any

import numpy as np

_LOG_2PI = math.log(2.0 * math.pi)
# Keep in sync with models/distributions.py (TanhGaussian.create clips).
_LOG_STD_MIN, _LOG_STD_MAX = -20.0, 2.0


def _dense(p: dict, x: np.ndarray) -> np.ndarray:
    return x @ np.asarray(p["kernel"]) + np.asarray(p["bias"])


def _mlp(torso: dict, x: np.ndarray, activation) -> np.ndarray:
    for i in range(len(torso)):
        x = activation(_dense(torso[f"dense_{i}"], x))
    return x


def _tanh(x):
    return np.tanh(x)


def _relu(x):
    return np.maximum(x, 0.0)


def _log_softmax(logits: np.ndarray) -> np.ndarray:
    z = logits - logits.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))


def supports_mirror(params: Any) -> bool:
    """True if the param tree is an MLP-torso net this module can mirror
    (conv torsos — pixel obs — keep the device acting path)."""
    p = params.get("params", params)
    torsos = [v for k, v in p.items() if k.endswith("torso")]
    return bool(torsos) and all(
        all(k.startswith("dense_") for k in t) for t in torsos
    )


# -- PPO (models/networks.py ActorCriticDiscrete / ActorCriticGaussian) --


def make_ppo_host_policy(env_spec, cfg):
    """(np_params, obs, rng) → (action, log_prob, value), matching
    ppo.make_policy_step's sampling semantics in host numpy."""
    if env_spec.discrete:

        def policy(params, obs, rng: np.random.Generator):
            p = params["params"]
            z = _mlp(p["torso"], np.asarray(obs, np.float32), _tanh)
            logits = _dense(p["policy"], z)
            value = _dense(p["value"], z)[..., 0]
            # Gumbel-max sampling == jax.random.categorical semantics.
            g = rng.gumbel(size=logits.shape).astype(np.float32)
            action = np.argmax(logits + g, axis=-1)
            logp = np.take_along_axis(
                _log_softmax(logits), action[..., None], axis=-1
            )[..., 0]
            return action, logp.astype(np.float32), value.astype(np.float32)

        return policy

    def policy(params, obs, rng: np.random.Generator):
        p = params["params"]
        obs = np.asarray(obs, np.float32)
        za = _mlp(p["pi_torso"], obs, _tanh)
        zc = _mlp(p["vf_torso"], obs, _tanh)
        mean = _dense(p["policy"], za)
        value = _dense(p["value"], zc)[..., 0]
        log_std = np.broadcast_to(np.asarray(p["log_std"]), mean.shape)
        std = np.exp(log_std)
        action = mean + std * rng.standard_normal(mean.shape).astype(np.float32)
        zscore = (action - mean) / std
        logp = np.sum(-0.5 * (zscore * zscore + _LOG_2PI) - log_std, axis=-1)
        return (
            action.astype(np.float32),
            logp.astype(np.float32),
            value.astype(np.float32),
        )

    return policy


def make_ppo_host_value(env_spec, cfg):
    """(np_params, obs) → value: the critic head alone, for computing
    truncation-bootstrap values of final_obs and the rollout bootstrap
    with the SAME (stale) params that produced the recorded per-step
    values — overlap mode must not mix value baselines across parameter
    versions (GAE deltas and the value-clip anchor stay consistent)."""
    if env_spec.discrete:

        def value_fn(params, obs):
            p = params["params"]
            z = _mlp(p["torso"], np.asarray(obs, np.float32), _tanh)
            return _dense(p["value"], z)[..., 0].astype(np.float32)

        return value_fn

    def value_fn(params, obs):
        p = params["params"]
        zc = _mlp(p["vf_torso"], np.asarray(obs, np.float32), _tanh)
        return _dense(p["value"], zc)[..., 0].astype(np.float32)

    return value_fn


def make_ppo_host_greedy(env_spec, cfg):
    """(np_params, obs) → mode action; host mirror of the eval policy
    (`ppo.make_greedy_act`). Greedy host eval otherwise pays one device
    round-trip per eval step, up to 1000 per eval sweep."""
    if env_spec.discrete:

        def act(params, obs):
            p = params["params"]
            z = _mlp(p["torso"], np.asarray(obs, np.float32), _tanh)
            return np.argmax(_dense(p["policy"], z), axis=-1)

        return act

    def act(params, obs):
        p = params["params"]
        za = _mlp(p["pi_torso"], np.asarray(obs, np.float32), _tanh)
        return _dense(p["policy"], za).astype(np.float32)

    return act


# -- DDPG/TD3 (models/networks.py DeterministicActor) --------------------


def _ddpg_actor_fwd(p: dict, obs) -> np.ndarray:
    """Deterministic tanh actor forward — the ONE copy both the explore
    and greedy mirrors share (divergence here would split collection and
    eval policies)."""
    z = _mlp(p["torso"], np.asarray(obs, np.float32), _relu)
    return _tanh(_dense(p["action"], z))


def make_ddpg_host_explore(env_spec, cfg):
    """(np_params, obs, rng, env_steps) → action; mirrors
    ddpg.make_explore_fn (tanh actor + clipped Gaussian noise, uniform
    random during warmup)."""

    def act(params, obs, rng: np.random.Generator, env_steps: int):
        shape = (np.asarray(obs).shape[0], env_spec.action_dim)
        if env_steps < cfg.warmup_steps:
            return rng.uniform(-1.0, 1.0, shape).astype(np.float32)
        a = _ddpg_actor_fwd(params["params"], obs)
        a = a + cfg.exploration_noise * rng.standard_normal(shape).astype(
            np.float32
        )
        return np.clip(a, -1.0, 1.0).astype(np.float32)

    return act


def make_ddpg_host_greedy(env_spec, cfg):
    """(np_params, obs) → deterministic actor action (no noise); host
    mirror of ddpg.make_greedy_act."""

    def act(params, obs):
        return _ddpg_actor_fwd(params["params"], obs).astype(np.float32)

    return act


# -- SAC (models/networks.py SquashedGaussianActor) ----------------------


def _sac_mean_logstd(p: dict, obs) -> tuple[np.ndarray, np.ndarray]:
    """Squashed-Gaussian actor heads — shared by explore and greedy."""
    z = _mlp(p["torso"], np.asarray(obs, np.float32), _relu)
    mean = _dense(p["mean"], z)
    log_std = np.clip(_dense(p["log_std"], z), _LOG_STD_MIN, _LOG_STD_MAX)
    return mean, log_std


def make_sac_host_explore(env_spec, cfg):
    """(np_params, obs, rng, env_steps) → action; mirrors
    sac.make_explore_fn (tanh-Gaussian sample, uniform during warmup)."""

    def act(params, obs, rng: np.random.Generator, env_steps: int):
        shape = (np.asarray(obs).shape[0], env_spec.action_dim)
        if env_steps < cfg.warmup_steps:
            return rng.uniform(-1.0, 1.0, shape).astype(np.float32)
        mean, log_std = _sac_mean_logstd(params["params"], obs)
        pre = mean + np.exp(log_std) * rng.standard_normal(shape).astype(
            np.float32
        )
        return _tanh(pre).astype(np.float32)

    return act


def make_sac_host_greedy(env_spec, cfg):
    """(np_params, obs) → tanh(mean) action; host mirror of
    sac.make_greedy_act."""

    def act(params, obs):
        mean, _ = _sac_mean_logstd(params["params"], obs)
        return _tanh(mean).astype(np.float32)

    return act


# -- serving dispatch (ISSUE 10) -----------------------------------------

_GREEDY_MIRRORS = {
    "ppo": make_ppo_host_greedy,
    "ddpg": make_ddpg_host_greedy,
    "td3": make_ddpg_host_greedy,
    "sac": make_sac_host_greedy,
}


def greedy_mirror_for(env_spec, cfg, algo: str):
    """The greedy host mirror `(np_params, obs) -> action` for `algo`'s
    policy params, or ValueError when no mirror exists — the serving
    engine's `backend="mirror"` acting path (serving/engine.py): on a
    CPU-only serving host these few numpy matmuls beat a batch-1 XLA
    dispatch, exactly the trade the training loops already make.
    Callers must still gate on `supports_mirror(params)` (conv torsos
    keep the device path)."""
    try:
        maker = _GREEDY_MIRRORS[algo]
    except KeyError:
        raise ValueError(
            f"no greedy host mirror for algo {algo!r}; "
            f"mirrored: {sorted(_GREEDY_MIRRORS)}"
        ) from None
    return maker(env_spec, cfg)
