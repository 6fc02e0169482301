"""The program side of the check for PPO on host environments."""

from __future__ import annotations

import itertools


def sample(preset, seed: int, burn_in: int) -> dict:
    """One [T, E] block collected from the program's host pool with the
    program's jitted policy step, and the program's GAE seam and PPO loss
    (whole batch, unchanged parameters) on it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import train
    from actor_critic_tpu.algos import common, host_loop, ppo

    cfg = preset.config
    pool, fused = train.build_env(
        preset.env, preset.algo, cfg, seed, env_kwargs=preset.env_kwargs)
    assert not fused, f"{preset.env} is not a host env"
    try:
        spec = pool.spec
        params, _ = ppo.init_host_params(spec, cfg, jax.random.key(seed))
        policy_step = ppo.make_policy_step(spec, cfg)
        act_key = jax.random.key(seed + 1)
        step = itertools.count()

        def act(obs):
            action, logp, value = policy_step(
                params, jnp.asarray(obs), jax.random.fold_in(act_key, next(step)))
            return np.asarray(action), {
                "log_prob": np.asarray(logp), "value": np.asarray(value)}

        tracker = host_loop.EpisodeTracker(pool.num_envs)
        obs = pool.reset()
        for _ in range(burn_in + 1):
            obs, block = host_loop.host_collect(
                pool, obs, cfg.rollout_steps, act, tracker)
        traj = {k: jnp.asarray(v) for k, v in block.items()}
        last_obs = jnp.asarray(obs)
    finally:
        pool.close()
    net = ppo.make_network(spec, cfg)
    T, E = traj["reward"].shape
    flat = lambda x: x.reshape(T * E, *x.shape[2:])  # noqa: E731

    def program(params, traj, last_obs):
        _, bootstrap = net.apply(params, last_obs)
        _, final_v = net.apply(params, flat(traj["final_obs"]))
        truncated = traj["done"] * (1.0 - traj["terminated"])
        rewards = traj["reward"] + cfg.gamma * final_v.reshape(T, E) * truncated
        adv, ret = common.gae_targets(
            rewards, traj["value"], traj["done"], bootstrap,
            cfg.gamma, cfg.gae_lambda)
        batch = ppo.PPOBatch(
            obs=flat(traj["obs"]), action=flat(traj["action"]),
            log_prob_old=flat(traj["log_prob"]), value_old=flat(traj["value"]),
            advantage=adv.reshape(-1), ret=ret.reshape(-1))
        loss, _ = ppo.ppo_loss(params, net.apply, batch, cfg)
        return {"loss": loss, "pg_advantages": adv, "value_targets": ret}

    return {
        # Loss and gradient on that block, traced and not run: the harness
        # reads the types of its matrix multiplications.
        "update_jaxpr": jax.make_jaxpr(jax.grad(
            lambda p: program(p, traj, last_obs)["loss"]))(params),
        "params": params,
        "traj": traj,
        "bootstrap_obs": last_obs,
        "program": jax.jit(program)(params, traj, last_obs),
        "dones": float(jnp.sum(traj["done"])),
        "shape": (T, E),
    }
