"""A2C — synchronous advantage actor-critic, fully fused on-device.

Capability parity with the reference's A2C CartPole config
(BASELINE.json:7; reference mount empty at survey, SURVEY.md §0), built
the TPU way: one jitted program per train step containing

    lax.scan over T: [policy fwd → vmapped env.step]   (rollout)
    → GAE reverse scan                                  (targets)
    → policy-gradient + value-MSE + entropy loss        (update)
    → optax update (grads pmean-ed over the dp mesh axis)

so the host is touched once per iteration, not once per env step — the
design that makes the ≥1M env-steps/sec north star (BASELINE.json:5)
reachable where the reference's host-stepped loop cannot.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax

from actor_critic_tpu.algos.common import (
    TrainState,
    Transition,
    anneal_fraction,
    episode_metrics_update,
    gae_targets as gae,
    init_rollout,
    linear_anneal,
    rollout_scan,
    truncation_bootstrap,
)
from actor_critic_tpu.algos.metrics import aggregate_metrics
from actor_critic_tpu.envs.jax_env import JaxEnv
from actor_critic_tpu.models.networks import ActorCriticDiscrete, ActorCriticGaussian
from actor_critic_tpu.ops.returns import normalize_advantages
from actor_critic_tpu.parallel import mesh as pmesh


@dataclasses.dataclass(frozen=True)
class A2CConfig:
    num_envs: int = 64
    rollout_steps: int = 16  # T
    gamma: float = 0.99
    gae_lambda: float = 0.95
    lr: float = 3e-4
    value_coef: float = 0.5
    entropy_coef: float = 0.01
    max_grad_norm: float = 0.5
    hidden: tuple[int, ...] = (64, 64)
    normalize_adv: bool = False
    # Huber value loss with this delta (<=0 keeps plain MSE). A2C takes
    # ONE gradient step per rollout, so PPO's value-clip-vs-old would be
    # a mathematical no-op here (value ≡ value_old at the differentiation
    # point); Huber is the stabilizer that DOES engage — it clips each
    # sample's value-step gradient to ±delta without touching the
    # policy-gradient estimator. Round-5 measurement on the flagship
    # preset (results/a2c_s{0,2}_huber{5,10}.json): delta=5 certifies
    # seed 2 but BREAKS seed 0; delta=10 certifies seed 0 and lifts
    # seed 2's oscillation band to 299–499 without certifying it — the
    # knob relocates A2C's seed sensitivity, it does not remove it
    # (consistent with the round-4 sweep rejecting normalize_adv /
    # lower lr / tighter grad clip). Left off in the preset; available
    # per-run via --set value_huber_delta=N.
    value_huber_delta: float = 0.0
    # bfloat16 activations for MXU throughput; params/optimizer stay fp32.
    bf16_compute: bool = False
    # Linear annealing over the first `anneal_iters` train steps (0 = off):
    # lr → lr_final and entropy_coef → entropy_coef_final, both optional.
    # The flat-coefficient flagship preset never converged to a solve
    # (round-2 verdict); annealing is the standard fix.
    anneal_iters: int = 0
    lr_final: Optional[float] = None
    entropy_coef_final: Optional[float] = None


def make_network(env: JaxEnv, cfg: A2CConfig):
    dtype = jnp.bfloat16 if cfg.bf16_compute else jnp.float32
    if env.spec.discrete:
        return ActorCriticDiscrete(
            num_actions=env.spec.action_dim, hidden=cfg.hidden,
            pixel_obs=env.spec.pixel_obs, compute_dtype=dtype,
        )
    return ActorCriticGaussian(
        action_dim=env.spec.action_dim, hidden=cfg.hidden, compute_dtype=dtype
    )


def make_eval_fn(env: JaxEnv, cfg: "A2CConfig"):
    """Greedy (mode-action) eval program (SURVEY.md §3.4)."""
    from actor_critic_tpu.algos.common import make_mode_eval

    return make_mode_eval(env, make_network(env, cfg))


def make_optimizer(cfg: A2CConfig) -> optax.GradientTransformation:
    lr = cfg.lr
    if cfg.anneal_iters > 0 and cfg.lr_final is not None:
        # One optimizer step per train iteration, so the schedule's step
        # count IS the iteration count.
        lr = optax.linear_schedule(cfg.lr, cfg.lr_final, cfg.anneal_iters)
    return optax.chain(
        optax.clip_by_global_norm(cfg.max_grad_norm),
        optax.adam(lr),
    )


def entropy_coef_at(cfg: A2CConfig, update_step: jax.Array) -> jax.Array:
    """Current entropy coefficient under the linear anneal (constant when
    annealing is off)."""
    return linear_anneal(
        cfg.entropy_coef,
        cfg.entropy_coef_final,
        anneal_fraction(update_step, cfg.anneal_iters),
    )


def init_state(env: JaxEnv, cfg: A2CConfig, key: jax.Array) -> TrainState:
    net = make_network(env, cfg)
    opt = make_optimizer(cfg)
    key, pkey, rkey = jax.random.split(key, 3)
    dummy = jnp.zeros((1, *env.spec.obs_shape), env.spec.obs_dtype)
    params = net.init(pkey, dummy)
    rstate = init_rollout(env, rkey, cfg.num_envs)
    E = cfg.num_envs
    return TrainState(
        params=params,
        opt_state=opt.init(params),
        rollout=rstate,
        key=key,
        update_step=jnp.zeros((), jnp.int32),
        ep_return=jnp.zeros((E,)),
        ep_length=jnp.zeros((E,)),
        avg_return=jnp.zeros(()),
    )


def a2c_loss(
    params: Any,
    apply_fn: Callable,
    traj: Transition,
    advantages: jax.Array,
    returns: jax.Array,
    cfg: A2CConfig,
    axis_name: Optional[str] = None,
    entropy_coef: Optional[jax.Array] = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Policy-gradient + value-MSE + entropy-bonus loss on a [T, E] batch.

    Re-evaluates the policy at the stored obs (same params as rollout, so
    ratio==1; the re-evaluation is what makes the loss differentiable).
    `axis_name` keeps advantage-normalization statistics global under dp.
    `entropy_coef` overrides cfg.entropy_coef (annealing threads the
    current value through here).
    """
    if entropy_coef is None:
        entropy_coef = jnp.asarray(cfg.entropy_coef)
    obs = traj.obs.reshape(-1, *traj.obs.shape[2:])
    actions = traj.action.reshape(-1, *traj.action.shape[2:])
    adv = advantages.reshape(-1)
    ret = returns.reshape(-1)
    if cfg.normalize_adv:
        adv = normalize_advantages(adv, axis_name)

    dist, value = apply_fn(params, obs)
    log_prob = dist.log_prob(actions)
    # Explicit fp32 accumulators on every reduction: bit-identical in
    # fp32 mode (the heads cast up), precision-discipline-required under
    # --update-dtype bf16 (bf16 compute, fp32 accumulation).
    entropy = jnp.mean(dist.entropy(), dtype=jnp.float32)

    pg_loss = -jnp.mean(
        jax.lax.stop_gradient(adv) * log_prob, dtype=jnp.float32
    )
    ret = jax.lax.stop_gradient(ret)
    if cfg.value_huber_delta > 0:
        # d/dv huber(v - ret) = clip(v - ret, ±delta): a per-sample bound
        # on the value step (see the config-field comment for why PPO's
        # clip-vs-old cannot work in A2C's single-step regime).
        v_loss = jnp.mean(
            optax.losses.huber_loss(value, ret, delta=cfg.value_huber_delta),
            dtype=jnp.float32,
        )
    else:
        v_loss = 0.5 * jnp.mean((value - ret) ** 2, dtype=jnp.float32)
    loss = pg_loss + cfg.value_coef * v_loss - entropy_coef * entropy
    return loss, {
        "loss": loss,
        "pg_loss": pg_loss,
        "v_loss": v_loss,
        "entropy": entropy,
    }


def make_train_step(
    env: JaxEnv,
    cfg: A2CConfig,
    axis_name: Optional[str] = None,
) -> Callable[[TrainState], tuple[TrainState, dict[str, jax.Array]]]:
    """Build the fused train step. `axis_name` names the dp mesh axis when
    running under shard_map (grads/metrics pmean-ed over it); None for
    single-device."""
    net = make_network(env, cfg)
    opt = make_optimizer(cfg)
    apply_fn = net.apply

    def train_step(state: TrainState) -> tuple[TrainState, dict[str, jax.Array]]:
        key, rkey = jax.random.split(state.key)

        # --- rollout (T steps, E envs, on-device) ---
        new_rollout, traj = rollout_scan(
            env, apply_fn, state.params, state.rollout, rkey, cfg.rollout_steps
        )

        # --- targets ---
        _, bootstrap_value = apply_fn(state.params, new_rollout.obs)
        if env.spec.can_truncate:
            # Value of pre-reset final obs, at the truncated rows only.
            rewards = truncation_bootstrap(
                apply_fn, state.params, traj, cfg.gamma
            )
        else:
            rewards = traj.reward
        advantages, returns = gae(
            rewards, traj.value, traj.done, bootstrap_value, cfg.gamma, cfg.gae_lambda
        )

        # --- update ---
        grad_fn = jax.value_and_grad(a2c_loss, has_aux=True)
        (_, metrics), grads = grad_fn(
            state.params, apply_fn, traj, advantages, returns, cfg, axis_name,
            entropy_coef_at(cfg, state.update_step),
        )
        grads = pmesh.pmean_tree(grads, axis_name)
        updates, new_opt_state = opt.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)

        # --- metrics / accounting ---
        ep_ret, ep_len, avg_ret, ep_metrics = episode_metrics_update(
            state.ep_return, state.ep_length, state.avg_return, traj
        )
        # Keep the EMA replicated across the dp axis (it is part of the
        # replicated state; per-device episode streams would diverge).
        avg_ret = pmesh.pmean(avg_ret, axis_name)
        ep_metrics["avg_return_ema"] = avg_ret
        metrics = aggregate_metrics(metrics, ep_metrics, axis_name)

        new_state = TrainState(
            params=new_params,
            opt_state=new_opt_state,
            rollout=new_rollout,
            key=key,
            update_step=state.update_step + 1,
            ep_return=ep_ret,
            ep_length=ep_len,
            avg_return=avg_ret,
        )
        return new_state, metrics

    return train_step


def train(
    env: JaxEnv,
    cfg: A2CConfig,
    num_iterations: int,
    seed: int = 0,
    state: Optional[TrainState] = None,
    log_every: int = 0,
    log_fn: Optional[Callable[[int, dict], None]] = None,
    state_hook: Optional[Callable] = None,
) -> tuple[TrainState, dict[str, jax.Array]]:
    """Simple host loop around the fused step (single device).

    For N iterations without host logging, the loop body is itself scanned
    on-device (`log_every=0`) so the host dispatches O(1) programs.
    `state_hook` is the between-dispatch state rewrite seam (curriculum
    weight installs on mixture fleets — host_loop.fused_train_loop).
    """
    from actor_critic_tpu.algos.host_loop import fused_train_loop

    return fused_train_loop(
        make_train_step, init_state, env, cfg, num_iterations,
        seed=seed, state=state, log_every=log_every, log_fn=log_fn,
        scan_when_silent=True, state_hook=state_hook,
    )


# -- AOT warmup registry (utils/compile_cache.py, ISSUE 4) ------------------
from actor_critic_tpu.utils import compile_cache as _compile_cache  # noqa: E402

_compile_cache.register_fused_warmups(
    "a2c", ("a2c",), init_state, make_train_step, make_eval_fn
)
