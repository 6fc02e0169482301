"""The program side of the check for IMPALA / A3C (fused, on-device env)."""

from __future__ import annotations


def sample(preset, seed: int, burn_in: int) -> dict:
    """One [T, E] rollout from `seed` and the program's outputs on it.

    The behaviour policy is a second seeded initialization, so the importance
    ratios differ from 1 and both clips of V-trace are exercised; `burn_in`
    rollouts run first so that episodes end inside the sampled one."""
    import jax
    import jax.numpy as jnp

    import train
    from actor_critic_tpu.algos import common

    cfg = preset.config
    env, fused = train.build_env(
        preset.env, preset.algo, cfg, seed, env_kwargs=preset.env_kwargs)
    assert fused, f"{preset.env} is not an on-device env"
    mod = train.fused_module(preset.algo)
    net = mod.make_network(env, cfg)
    state = mod.init_state(env, cfg, jax.random.key(seed))
    behaviour = mod.init_state(env, cfg, jax.random.key(seed + 1)).params
    T = cfg.rollout_steps

    @jax.jit
    def rollout(actor_params, rstate, key):
        return common.rollout_scan(env, net.apply, actor_params, rstate, key, T)

    rstate = state.rollout
    for i in range(burn_in + 1):
        rstate, traj = rollout(
            behaviour, rstate, jax.random.fold_in(jax.random.key(seed), i))

    @jax.jit
    def program(params, traj, bootstrap_obs):
        loss, _ = mod.impala_loss(
            params, net.apply, traj, bootstrap_obs, cfg, env.spec.can_truncate)
        # The same inputs the loss gives the seam, to read its targets.
        E = traj.reward.shape[1]
        flat = lambda x: x.reshape(T * E, *x.shape[2:])  # noqa: E731
        dist, values = net.apply(params, flat(traj.obs))
        target_lp = dist.log_prob(flat(traj.action)).reshape(T, E)
        _, bootstrap = net.apply(params, bootstrap_obs)
        rewards = traj.reward
        if env.spec.can_truncate:
            _, final_v = net.apply(params, flat(traj.final_obs))
            rewards = common.truncation_bootstrap_rewards(
                traj, final_v.reshape(T, E), cfg.gamma)
        pg, vs, _ = common.corrected_advantages(
            target_lp, traj.log_prob, rewards, values.reshape(T, E),
            traj.done, bootstrap, cfg.gamma, cfg.lam, rho_bar=cfg.rho_bar,
            c_bar=cfg.c_bar, correction=cfg.correction,
        )
        return {"loss": loss, "pg_advantages": pg, "value_targets": vs}

    out = program(state.params, traj, rstate.obs)
    return {
        # The step program train.py dispatches, traced and not run: the
        # harness reads the types of its convolutions and multiplications.
        "update_jaxpr": jax.make_jaxpr(mod.make_train_step(env, cfg))(state),
        "params": state.params,
        "traj": traj._asdict(),
        "bootstrap_obs": rstate.obs,
        "program": out,
        "dones": float(jnp.sum(traj.done)),
        "shape": tuple(traj.reward.shape),
    }
