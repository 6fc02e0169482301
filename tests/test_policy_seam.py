"""The policy seam of the fused trainers leaves feed-forward policies as they
were: `rollout_scan` and `impala_loss` through `common.feedforward_policy`
lower to the program the parent commit lowered to, and a train step gives
the parent's outputs bit for bit (A2C, PPO, IMPALA).

The parent's two bodies are kept here, verbatim from commit 301b7e8, and
swapped in for the comparison."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from actor_critic_tpu.algos import a2c, common, impala, ppo  # noqa: E402
from actor_critic_tpu.algos.common import (  # noqa: E402
    RolloutState, Transition, corrected_advantages, truncation_bootstrap)
from actor_critic_tpu.envs import make_cartpole, make_pong, make_two_state_mdp  # noqa: E402


def parent_rollout_scan(env, apply_fn, params, rstate, key, num_steps):
    def step_fn(carry, step_key):
        dist, value = apply_fn(params, carry.obs)
        n_envs = carry.obs.shape[0]
        akeys = jax.random.split(step_key, n_envs)
        action = jax.vmap(lambda d, k: d.sample(k), in_axes=(0, 0))(dist, akeys)
        log_prob = jax.vmap(lambda d, a: d.log_prob(a))(dist, action)
        out = jax.vmap(env.step)(carry.env_state, action)
        trans = Transition(
            obs=carry.obs,
            action=action,
            log_prob=log_prob,
            value=value,
            reward=out.reward,
            done=out.done,
            terminated=out.info["terminated"],
            final_obs=out.info["final_obs"],
        )
        return RolloutState(env_state=out.state, obs=out.obs), trans

    with jax.named_scope("rollout"):
        step_keys = jax.random.split(key, num_steps)
        return jax.lax.scan(step_fn, rstate, step_keys)


def parent_impala_loss(params, apply_fn, traj, bootstrap_obs, cfg,
                       can_truncate=True, time_axis_name=None):
    if isinstance(apply_fn, common.Policy):   # the new train step hands a Policy
        apply_fn = parent_impala_loss.apply_fn
    T, E = traj.reward.shape
    with jax.named_scope("forward"):
        obs = traj.obs.reshape(T * E, *traj.obs.shape[2:])
        actions = traj.action.reshape(T * E, *traj.action.shape[2:])
        dist, values = apply_fn(params, obs)
        target_log_probs = dist.log_prob(actions).reshape(T, E)
        values = values.reshape(T, E)
        entropy = jnp.mean(dist.entropy(), dtype=jnp.float32)
    with jax.named_scope("bootstrap"):
        _, bootstrap_value = apply_fn(params, bootstrap_obs)

    if can_truncate:
        with jax.named_scope("final_obs"):
            rewards = truncation_bootstrap(
                apply_fn, jax.lax.stop_gradient(params), traj, cfg.gamma
            )
            truncated_frac = jnp.mean(
                traj.done * (1.0 - traj.terminated), dtype=jnp.float32
            )
    else:
        rewards = traj.reward
        truncated_frac = jnp.zeros((), jnp.float32)

    pg_advantages, value_targets, mean_rho = corrected_advantages(
        jax.lax.stop_gradient(target_log_probs),
        traj.log_prob,
        rewards,
        jax.lax.stop_gradient(values),
        traj.done,
        jax.lax.stop_gradient(bootstrap_value),
        cfg.gamma,
        cfg.lam,
        rho_bar=cfg.rho_bar,
        c_bar=cfg.c_bar,
        correction=cfg.correction,
        time_axis_name=time_axis_name,
    )

    with jax.named_scope("loss"):
        pg_loss = -jnp.mean(
            jax.lax.stop_gradient(pg_advantages) * target_log_probs,
            dtype=jnp.float32,
        )
        v_loss = 0.5 * jnp.mean(
            (values - jax.lax.stop_gradient(value_targets)) ** 2,
            dtype=jnp.float32,
        )
        loss = pg_loss + cfg.value_coef * v_loss - cfg.entropy_coef * entropy
    return loss, {
        "loss": loss,
        "pg_loss": pg_loss,
        "v_loss": v_loss,
        "entropy": entropy,
        "mean_rho": mean_rho,
        "truncated_frac": truncated_frac,
    }


def _as_parent(mod, env, cfg, monkeypatch):
    """`mod.make_train_step` with the parent's bodies swapped in."""
    def rollout(env_, policy, *rest, policy_metrics=False):
        apply_fn = parent_impala_loss.apply_fn \
            if isinstance(policy, common.Policy) else policy
        out = parent_rollout_scan(env_, apply_fn, *rest)
        # A feed-forward policy counts nothing over a rollout.
        return (*out, {}) if policy_metrics else out

    monkeypatch.setattr(mod, "rollout_scan", rollout)
    if mod is impala:
        parent_impala_loss.apply_fn = impala.make_network(env, cfg).apply
        monkeypatch.setattr(impala, "impala_loss", parent_impala_loss)
    return mod.make_train_step(env, cfg)


CASES = {
    "a2c": (a2c, lambda: make_cartpole(),
            lambda: a2c.A2CConfig(num_envs=8, rollout_steps=8)),
    "ppo": (ppo, lambda: make_cartpole(),
            lambda: ppo.PPOConfig(num_envs=8, rollout_steps=8, epochs=2,
                                  num_minibatches=2)),
    "impala": (impala, lambda: make_two_state_mdp(),
               lambda: impala.ImpalaConfig(num_envs=8, rollout_steps=8,
                                           actor_refresh_every=2)),
    "impala_pixels": (impala, lambda: make_pong(size=36),
                      lambda: impala.ImpalaConfig(num_envs=4, rollout_steps=5,
                                                  actor_refresh_every=2)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_feedforward_train_step_is_the_parents_bit_for_bit(case, monkeypatch):
    mod, make_env, make_cfg = CASES[case]
    env, cfg = make_env(), make_cfg()
    state = mod.init_state(env, cfg, jax.random.key(0))
    new = jax.jit(mod.make_train_step(env, cfg))
    new_text = new.lower(state).as_text()
    old = jax.jit(_as_parent(mod, env, cfg, monkeypatch))
    # The same program: not one operation gained (the pixel step's too).
    assert new_text == old.lower(state).as_text()
    a, b = state, state
    for _ in range(3):
        a, ma = new(a)
        b, mb = old(b)
    for x, y in zip(jax.tree.leaves((a.params, ma)), jax.tree.leaves((b.params, mb))):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_a_bare_apply_fn_still_serves_where_a_policy_does():
    """`rollout_scan` and `impala_loss` take the network's `apply` as before
    (the benchmark's accepted seam calls them so)."""
    env = make_two_state_mdp()
    cfg = impala.ImpalaConfig(num_envs=4, rollout_steps=6)
    net = impala.make_network(env, cfg)
    state = impala.init_state(env, cfg, jax.random.key(1))
    args = (state.params, state.rollout, jax.random.key(2), cfg.rollout_steps)
    r1, t1 = common.rollout_scan(env, net.apply, *args)
    r2, t2 = common.rollout_scan(env, impala.make_policy(env, cfg), *args)
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)))
    l1, _ = impala.impala_loss(state.params, net.apply, t1, r1.obs, cfg, True)
    l2, m = impala.impala_loss(state.params, impala.make_policy(env, cfg), t1, r1.obs, cfg, True)
    assert float(l1) == float(l2) and "routed_here_frac" not in m
