"""The program side of the check for IMPALA over a sequence policy whose
layers are all sparse and whose rollout prefills the prompt and then decodes
through caches (`impala_mellum2`). What `seams/impala_tokens.py` compares, less
`logits_dense` (no layer is dense, so there is no tree to cut), plus two keys
that no router decides, on the tree with every expert's down-projection set to
zero, from a second rollout of the program's own `rollout_scan` with that tree
as the actor: the prompt's first `prefill_len` positions in one pass, the rest
decoded through the caches:

- `logits_attn`: the program's causal pass over that rollout's tokens (both
  kinds of attention, both RoPEs, the band, the head);
- `decode_logp`: the behaviour log-probabilities that rollout recorded, relative
  to a uniform policy (`+ log V`), at the positions the loss mask keeps: what
  prefill and then decoding through the cache gave, against the reference's
  full causal pass over the same tokens.

The second rollout's observations and actions ride in the returned `traj` dict
(`decode_obs`, `decode_action`) so that the reference is given them."""

from __future__ import annotations

import math


def zero_down(params: dict) -> dict:
    """The parameter tree with every expert's down-projection set to zero."""
    import jax.numpy as jnp

    p = dict(params["params"])
    for name, layer in p.items():
        if name.startswith("layer_"):
            experts = {**layer["moe"]["experts"],
                       "w_down": jnp.zeros_like(layer["moe"]["experts"]["w_down"])}
            p[name] = {**layer, "moe": {**layer["moe"], "experts": experts}}
    return {"params": p}


def sample(preset, seed: int, burn_in: int) -> dict:
    """One [T, E] rollout from `seed` by the program's own rollout code, with
    a second seeded initialization as the behaviour policy (so both clips of
    V-trace are exercised), and the program's outputs on it: the loss as its
    three terms, the targets of its advantage seam, the logits of its causal
    pass; then the second rollout described above."""
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
    del behaviour
    attn_params = zero_down(params)
    _, decoded = rollout(attn_params, rstate, jax.random.key(seed + 3))

    @jax.jit
    def update(params, traj, bootstrap_obs):
        _, m = mod.impala_loss(
            params, policy, traj, bootstrap_obs, cfg, env.spec.can_truncate)
        loss = jnp.stack([m["pg_loss"], cfg.value_coef * m["v_loss"],
                          -cfg.entropy_coef * m["entropy"]])
        out = policy.unroll(params, traj)
        pg, vs, _ = common.corrected_advantages(
            jnp.where(out.mask > 0, out.log_prob, traj.log_prob), traj.log_prob,
            traj.reward, out.value, traj.done,
            policy.bootstrap(params, bootstrap_obs), cfg.gamma, cfg.lam,
            rho_bar=cfg.rho_bar, c_bar=cfg.c_bar, correction=cfg.correction,
        )
        return {"loss": loss, "pg_advantages": pg, "value_targets": vs}

    # One program for both trees: the check compiles the causal pass once.
    @jax.jit
    def causal(params, obs):
        logits, _ = seq_policy.logits_and_values(
            params, jnp.swapaxes(obs, 0, 1), cfg.seq)
        return jnp.swapaxes(logits, 0, 1)

    kept = 1.0 - decoded.obs[..., 2].astype(jnp.float32)
    out = {**update(params, traj, rstate.obs),
           "logits": causal(params, traj.obs),
           "logits_attn": causal(attn_params, decoded.obs),
           "decode_logp": (decoded.log_prob + math.log(env.spec.action_dim)) * kept}
    state = jax.eval_shape(lambda: mod.init_state(env, cfg, jax.random.key(seed)))
    return {
        # The step program train.py dispatches, traced and not run: the
        # harness reads the types of its matrix multiplications.
        "update_jaxpr": jax.make_jaxpr(mod.make_train_step(env, cfg))(state),
        "params": params,
        "traj": {**traj._asdict(), "decode_obs": decoded.obs,
                 "decode_action": decoded.action},
        "bootstrap_obs": rstate.obs,
        "program": out,
        "dones": float(jnp.sum(traj.done)),
        "shape": tuple(traj.reward.shape),
    }
