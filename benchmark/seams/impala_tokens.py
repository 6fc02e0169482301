"""The program side of the check for IMPALA over a sequence policy (a token
env, on the device). `seams/impala.py` cannot serve: it applies the network
to flattened rows, and a sequence policy acts through its cache and is
re-evaluated as one causal pass a row (`common.Policy`)."""

from __future__ import annotations


def dense_prefix(params: dict) -> dict:
    """The parameter tree cut before its first expert layer: embedding, the
    leading dense layers, final norm and heads."""
    p = params["params"]
    keep, i = {k: v for k, v in p.items() if not k.startswith("layer_")}, 0
    while f"layer_{i}" in p and "moe" not in p[f"layer_{i}"]:
        keep[f"layer_{i}"] = p[f"layer_{i}"]
        i += 1
    return {"params": keep}


def sample(preset, seed: int, burn_in: int) -> dict:
    """One [T, E] rollout from `seed`, decoded through the policy's cache by
    the program's own rollout code, and the program's outputs on it: the
    loss (as its three terms), the targets its advantage seam gives, and the
    logits of its causal pass (whole; the check runs a few rows), of the
    whole model and of the model cut after its dense layers.

    The behaviour policy is a second seeded initialization, so the importance
    ratios differ from 1 and both clips of V-trace are exercised. An episode
    is exactly one unroll, so there is nothing to burn in (`burn_in` rollouts
    still run first, where a traffic file asks for them)."""
    import jax
    import jax.numpy as jnp

    import train
    from actor_critic_tpu.algos import common
    from actor_critic_tpu.models import seq_policy

    cfg = preset.config
    env, fused = train.build_env(
        preset.env, preset.algo, cfg, seed, env_kwargs=preset.env_kwargs)
    assert fused, f"{preset.env} is not an on-device env"
    mod = train.fused_module(preset.algo)
    policy = mod.make_policy(env, cfg)
    # Parameters only: a whole train state of each would hold the chip's
    # memory three times over.
    params = mod.init_params(env, cfg, jax.random.key(seed))
    behaviour = mod.init_params(env, cfg, jax.random.key(seed + 1))
    T = cfg.rollout_steps

    @jax.jit
    def rollout(actor_params, rstate, key):
        return common.rollout_scan(env, policy, actor_params, rstate, key, T)

    rstate = common.init_rollout(env, jax.random.key(seed + 2), cfg.num_envs)
    for i in range(burn_in + 1):
        rstate, traj = rollout(
            behaviour, rstate, jax.random.fold_in(jax.random.key(seed), i))

    @jax.jit
    def program(params, traj, bootstrap_obs):
        _, m = mod.impala_loss(
            params, policy, traj, bootstrap_obs, cfg, env.spec.can_truncate)
        # The loss as its three terms (they sum to it): compared against the
        # largest of them, not against a sum in which they may cancel.
        loss = jnp.stack([m["pg_loss"], cfg.value_coef * m["v_loss"],
                          -cfg.entropy_coef * m["entropy"]])
        # The same inputs the loss gives the seam, to read its targets.
        out = policy.unroll(params, traj)
        pg, vs, _ = common.corrected_advantages(
            jnp.where(out.mask > 0, out.log_prob, traj.log_prob), traj.log_prob,
            traj.reward, out.value, traj.done,
            policy.bootstrap(params, bootstrap_obs), cfg.gamma, cfg.lam,
            rho_bar=cfg.rho_bar, c_bar=cfg.c_bar, correction=cfg.correction,
        )
        obs = jnp.swapaxes(traj.obs, 0, 1)
        logits, _ = seq_policy.logits_and_values(params, obs, cfg.seq)
        # The same pass over the model cut after its dense layers: no router
        # in it, so no token changes experts on a rounding and the limit can
        # sit at the rounding itself (the configuration's `tolerance.why`).
        dense, _ = seq_policy.logits_and_values(dense_prefix(params), obs, cfg.seq)
        return {"loss": loss, "pg_advantages": pg, "value_targets": vs,
                "logits": jnp.swapaxes(logits, 0, 1),
                "logits_dense": jnp.swapaxes(dense, 0, 1)}

    out = program(params, traj, rstate.obs)
    state = jax.eval_shape(lambda: mod.init_state(env, cfg, jax.random.key(seed)))
    return {
        # The step program train.py dispatches, traced and not run: the
        # harness reads the types of its matrix multiplications.
        "update_jaxpr": jax.make_jaxpr(mod.make_train_step(env, cfg))(state),
        "params": params,
        "traj": traj._asdict(),
        "bootstrap_obs": rstate.obs,
        "program": out,
        "dones": float(jnp.sum(traj.done)),
        "shape": tuple(traj.reward.shape),
    }
