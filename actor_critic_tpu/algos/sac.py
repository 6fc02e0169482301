"""SAC — soft actor-critic with twin-Q and automatic entropy temperature.

Capability parity with the reference's SAC Humanoid config
(BASELINE.json:10: "twin-Q, entropy-temperature auto-tune"; reference
mount empty at survey, SURVEY.md §0). Same TPU-first shape as
algos/ddpg.py: the replay ring lives in HBM, and the fused path runs
collect → insert → J soft-policy-iteration updates as one jitted,
donated program (SURVEY §3.2 boundary fix).

Per update (Haarnoja et al. 2018, soft policy iteration):
  critic:  y = r + γ(1−term)·[min(Q̄₁,Q̄₂)(s', a') − α·log π(a'|s')],
           a' ~ π(·|s')  (fresh sample, tanh-Gaussian)
  actor:   min E[α·log π(a|s) − min(Q₁,Q₂)(s, a)]  (reparameterized)
  alpha:   min_α E[−α·(log π(a|s) + H_target)],  H_target = −action_dim
           (optimized in log α; the update uses the analytic gradient
           d/d(log α) = −α·E[log π + H_target])
  targets: Polyak on the twin critic only (no target actor in SAC).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from actor_critic_tpu import replay
from actor_critic_tpu.algos.common import (
    OffPolicyTransition,
    RolloutState,
    episode_metrics_update,
    init_rollout,
    offpolicy_rollout,
)
from actor_critic_tpu.algos.metrics import aggregate_metrics
from actor_critic_tpu.envs.jax_env import JaxEnv
from actor_critic_tpu.models.networks import SquashedGaussianActor, TwinQ
from actor_critic_tpu.ops.polyak import polyak_update
from actor_critic_tpu.parallel import mesh as pmesh


@dataclasses.dataclass(frozen=True)
class SACConfig:
    num_envs: int = 8
    steps_per_iter: int = 8
    updates_per_iter: int = 8
    buffer_capacity: int = 1_000_000
    batch_size: int = 256
    gamma: float = 0.99
    tau: float = 0.005
    actor_lr: float = 3e-4
    critic_lr: float = 3e-4
    alpha_lr: float = 3e-4
    hidden: tuple[int, ...] = (256, 256)
    warmup_steps: int = 1_000
    init_alpha: float = 1.0
    # None → auto-tune toward target_entropy (default −action_dim);
    # a float here freezes α at that value (no alpha optimizer step).
    fixed_alpha: Optional[float] = None
    target_entropy: Optional[float] = None
    bf16_compute: bool = False
    # Quantized replay storage (ISSUE 8, replay/quantize.py): "fp32" |
    # "mixed" (int8-standardized obs/rewards, fp32 actions — the tanh
    # actor's actions concentrate where int8 is coarsest) | "int8".
    replay_dtype: str = "fp32"

    def __post_init__(self):
        if self.init_alpha <= 0.0:
            raise ValueError("init_alpha must be > 0 (α is parameterized in log)")
        if self.fixed_alpha is not None and self.fixed_alpha <= 0.0:
            raise ValueError("fixed_alpha must be > 0 (α is parameterized in log)")


class SACLearnerState(NamedTuple):
    """Device-resident SAC learner (actor, twin critic, α, replay)."""

    actor_params: Any
    critic_params: Any
    target_critic: Any
    actor_opt: Any
    critic_opt: Any
    log_alpha: jax.Array
    alpha_opt: Any
    replay: replay.ReplayState
    key: jax.Array
    update_count: jax.Array


class SACState(NamedTuple):
    """Fused-trainer state: learner + env batch + accounting."""

    learner: SACLearnerState
    rollout: RolloutState
    env_steps: jax.Array
    update_step: jax.Array
    ep_return: jax.Array
    ep_length: jax.Array
    avg_return: jax.Array


def _modules(action_dim: int, cfg: SACConfig):
    dtype = jnp.bfloat16 if cfg.bf16_compute else jnp.float32
    actor = SquashedGaussianActor(action_dim, cfg.hidden, compute_dtype=dtype)
    critic = TwinQ(cfg.hidden, compute_dtype=dtype)
    return actor, critic


def _target_entropy(action_dim: int, cfg: SACConfig) -> float:
    return (
        cfg.target_entropy if cfg.target_entropy is not None else -float(action_dim)
    )


def init_learner(
    obs_shape: tuple[int, ...], action_dim: int, cfg: SACConfig, key: jax.Array
) -> SACLearnerState:
    actor, critic = _modules(action_dim, cfg)
    akey, ckey, lkey = jax.random.split(key, 3)
    dummy_obs = jnp.zeros((1, *obs_shape), jnp.float32)
    dummy_act = jnp.zeros((1, action_dim), jnp.float32)
    actor_params = actor.init(akey, dummy_obs)
    critic_params = critic.init(ckey, dummy_obs, dummy_act)
    log_alpha = jnp.log(
        jnp.asarray(
            cfg.init_alpha if cfg.fixed_alpha is None else cfg.fixed_alpha,
            jnp.float32,
        )
    )
    example = OffPolicyTransition(
        obs=jnp.zeros(obs_shape, jnp.float32),
        action=jnp.zeros((action_dim,), jnp.float32),
        reward=jnp.zeros((), jnp.float32),
        next_obs=jnp.zeros(obs_shape, jnp.float32),
        terminated=jnp.zeros((), jnp.float32),
        done=jnp.zeros((), jnp.float32),
    )
    return SACLearnerState(
        actor_params=actor_params,
        critic_params=critic_params,
        # Distinct buffer from the online critic: the fused trainer
        # donates its state and XLA rejects aliased donations.
        target_critic=jax.tree.map(jnp.copy, critic_params),
        actor_opt=optax.adam(cfg.actor_lr).init(actor_params),
        critic_opt=optax.adam(cfg.critic_lr).init(critic_params),
        log_alpha=log_alpha,
        alpha_opt=optax.adam(cfg.alpha_lr).init(log_alpha),
        replay=replay.init(
            example, cfg.buffer_capacity,
            replay.offpolicy_codecs(cfg.replay_dtype),
        ),
        key=lkey,
        update_count=jnp.zeros((), jnp.int32),
    )


def init_state(env: JaxEnv, cfg: SACConfig, key: jax.Array) -> SACState:
    key, lkey, rkey = jax.random.split(key, 3)
    learner = init_learner(env.spec.obs_shape, env.spec.action_dim, cfg, lkey)
    E = cfg.num_envs
    return SACState(
        learner=learner,
        rollout=init_rollout(env, rkey, E),
        env_steps=jnp.zeros((), jnp.int32),
        update_step=jnp.zeros((), jnp.int32),
        ep_return=jnp.zeros((E,)),
        ep_length=jnp.zeros((E,)),
        avg_return=jnp.zeros(()),
    )


def make_eval_fn(env: JaxEnv, cfg: "SACConfig"):
    """Greedy (tanh-mean) eval program (SURVEY.md §3.4); see
    common.make_greedy_eval for the shared contract."""
    from actor_critic_tpu.algos.common import make_greedy_eval

    actor, _ = _modules(env.spec.action_dim, cfg)
    return make_greedy_eval(
        env, lambda p, o: actor.apply(p, o).mode(),
        lambda s: s.learner.actor_params,
    )


def make_explore_fn(action_dim: int, cfg: SACConfig):
    """Behavior policy: sample the tanh-Gaussian; uniform during warmup."""
    actor, _ = _modules(action_dim, cfg)

    def act(params, obs, key, env_steps):
        skey, ukey = jax.random.split(key)
        dist = actor.apply(params, obs)
        a = dist.sample(skey)
        rand = jax.random.uniform(ukey, a.shape, minval=-1.0, maxval=1.0)
        return jnp.where(env_steps < cfg.warmup_steps, rand, a)

    return act


def make_update_loop(
    action_dim: int,
    cfg: SACConfig,
    axis_name: Optional[str] = None,
) -> Callable[[SACLearnerState, jax.Array], tuple[SACLearnerState, dict]]:
    """Build `(learner, do_update) → (learner, metrics)`: a scan of
    `cfg.updates_per_iter` soft-policy-iteration steps. Warmup gating is
    a branchless `where`-select, as in ddpg.make_update_loop."""
    actor, critic = _modules(action_dim, cfg)
    h_target = _target_entropy(action_dim, cfg)
    codecs = replay.offpolicy_codecs(cfg.replay_dtype)

    def critic_loss_fn(critic_params, target_q, batch: OffPolicyTransition):
        q1, q2 = critic.apply(critic_params, batch.obs, batch.action)
        return jnp.mean((q1 - target_q) ** 2) + jnp.mean((q2 - target_q) ** 2), (
            jnp.mean(q1)
        )

    def actor_loss_fn(actor_params, critic_params, alpha, obs, key):
        dist = actor.apply(actor_params, obs)
        a, logp = dist.sample_and_log_prob(key)
        q1, q2 = critic.apply(critic_params, obs, a)
        q = jnp.minimum(q1, q2)
        return jnp.mean(alpha * logp - q), logp

    def select(mask, new, old):
        return jax.tree.map(lambda n, o: jnp.where(mask, n, o), new, old)

    def one_update(ls: SACLearnerState, do_update: jax.Array):
        key, skey, tkey, akey = jax.random.split(ls.key, 4)
        batch: OffPolicyTransition = replay.sample(
            ls.replay, skey, cfg.batch_size, codecs
        )
        alpha = jnp.exp(ls.log_alpha)

        # --- soft TD target ---
        next_dist = actor.apply(ls.actor_params, batch.next_obs)
        next_a, next_logp = next_dist.sample_and_log_prob(tkey)
        tq1, tq2 = critic.apply(ls.target_critic, batch.next_obs, next_a)
        next_v = jnp.minimum(tq1, tq2) - alpha * next_logp
        target_q = jax.lax.stop_gradient(
            batch.reward + cfg.gamma * (1.0 - batch.terminated) * next_v
        )

        # --- critic step ---
        (closs, q_mean), cgrads = jax.value_and_grad(critic_loss_fn, has_aux=True)(
            ls.critic_params, target_q, batch
        )
        cgrads = pmesh.pmean_tree(cgrads, axis_name)
        cupd, critic_opt = optax.adam(cfg.critic_lr).update(cgrads, ls.critic_opt)
        critic_params = optax.apply_updates(ls.critic_params, cupd)
        critic_params = select(do_update, critic_params, ls.critic_params)
        critic_opt = select(do_update, critic_opt, ls.critic_opt)

        # --- actor step (fresh reparameterized sample, updated critic) ---
        (aloss, logp), agrads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            ls.actor_params, critic_params, alpha, batch.obs, akey
        )
        agrads = pmesh.pmean_tree(agrads, axis_name)
        aupd, actor_opt = optax.adam(cfg.actor_lr).update(agrads, ls.actor_opt)
        actor_params = optax.apply_updates(ls.actor_params, aupd)
        actor_params = select(do_update, actor_params, ls.actor_params)
        actor_opt = select(do_update, actor_opt, ls.actor_opt)

        # --- temperature step (skipped entirely with fixed_alpha) ---
        if cfg.fixed_alpha is None:
            entropy_gap = jax.lax.stop_gradient(logp + h_target)
            alpha_grad = jnp.mean(-entropy_gap) * jnp.exp(ls.log_alpha)
            # d/d(log α) of E[−exp(log α)·(log π + H_t)] — scalar, no AD
            # needed; pmean'd for identical α across the dp axis.
            alpha_grad = pmesh.pmean(alpha_grad, axis_name)
            alupd, alpha_opt = optax.adam(cfg.alpha_lr).update(
                alpha_grad, ls.alpha_opt
            )
            log_alpha = optax.apply_updates(ls.log_alpha, alupd)
            log_alpha = jnp.where(do_update, log_alpha, ls.log_alpha)
            alpha_opt = select(do_update, alpha_opt, ls.alpha_opt)
        else:
            log_alpha, alpha_opt = ls.log_alpha, ls.alpha_opt

        target_critic = select(
            do_update,
            polyak_update(critic_params, ls.target_critic, cfg.tau),
            ls.target_critic,
        )

        new_ls = SACLearnerState(
            actor_params=actor_params,
            critic_params=critic_params,
            target_critic=target_critic,
            actor_opt=actor_opt,
            critic_opt=critic_opt,
            log_alpha=log_alpha,
            alpha_opt=alpha_opt,
            replay=ls.replay,
            key=key,
            update_count=ls.update_count + do_update.astype(jnp.int32),
        )
        metrics = {
            "critic_loss": closs,
            "actor_loss": aloss,
            "q_mean": q_mean,
            "alpha": jnp.exp(log_alpha),
            "entropy_est": -jnp.mean(logp),
        }
        return new_ls, metrics

    def update_loop(ls: SACLearnerState, do_update: jax.Array):
        def body(carry, _):
            return one_update(carry, do_update)

        ls, metrics = jax.lax.scan(body, ls, None, length=cfg.updates_per_iter)
        return ls, jax.tree.map(lambda m: m[-1], metrics)

    return update_loop


def make_train_step(
    env: JaxEnv,
    cfg: SACConfig,
    axis_name: Optional[str] = None,
) -> Callable[[SACState], tuple[SACState, dict[str, jax.Array]]]:
    """The fused collect→insert→update program (one jit dispatch)."""
    explore = make_explore_fn(env.spec.action_dim, cfg)
    update_loop = make_update_loop(env.spec.action_dim, cfg, axis_name)
    codecs = replay.offpolicy_codecs(cfg.replay_dtype)

    def train_step(state: SACState):
        ls = state.learner
        key, rkey = jax.random.split(ls.key)

        rollout, env_steps, traj = offpolicy_rollout(
            env, explore, ls.actor_params, state.rollout, rkey,
            cfg.steps_per_iter, state.env_steps,
        )
        flat = jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), traj)
        # axis_name keeps the quantizer stats identical across dp (they
        # are replicated in parallel.dp.replay_specs).
        rbuf = replay.add_batch(ls.replay, flat, codecs, axis_name=axis_name)

        do_update = jnp.logical_and(
            env_steps >= cfg.warmup_steps, rbuf.size >= cfg.batch_size
        )
        ls, metrics = update_loop(ls._replace(replay=rbuf, key=key), do_update)

        ep_ret, ep_len, avg_ret, ep_metrics = episode_metrics_update(
            state.ep_return, state.ep_length, state.avg_return, traj
        )
        avg_ret = pmesh.pmean(avg_ret, axis_name)
        ep_metrics["avg_return_ema"] = avg_ret
        metrics = aggregate_metrics(metrics, ep_metrics, axis_name)

        new_state = SACState(
            learner=ls,
            rollout=rollout,
            env_steps=env_steps,
            update_step=state.update_step + 1,
            ep_return=ep_ret,
            ep_length=ep_len,
            avg_return=avg_ret,
        )
        return new_state, metrics

    return train_step


def train(
    env: JaxEnv,
    cfg: SACConfig,
    num_iterations: int,
    seed: int = 0,
    state: Optional[SACState] = None,
    log_every: int = 0,
    log_fn: Optional[Callable[[int, dict], None]] = None,
) -> tuple[SACState, dict[str, jax.Array]]:
    """Host loop around the fused step (single device)."""
    from actor_critic_tpu.algos.host_loop import fused_train_loop

    return fused_train_loop(
        make_train_step, init_state, env, cfg, num_iterations,
        seed=seed, state=state, log_every=log_every, log_fn=log_fn,
    )


# --------------------------------------------------------------------------
# Host-env path (MuJoCo Humanoid etc. — BASELINE.json:10)
# --------------------------------------------------------------------------

def make_host_act_fn(action_dim: int, cfg: SACConfig):
    return jax.jit(make_explore_fn(action_dim, cfg))


def make_host_ingest_update(action_dim: int, cfg: SACConfig):
    """Jitted (learner, [K,E] block, env_steps) → (learner, metrics)."""
    update_loop = make_update_loop(action_dim, cfg)
    codecs = replay.offpolicy_codecs(cfg.replay_dtype)

    @partial(jax.jit, donate_argnums=0)
    def ingest_update(ls: SACLearnerState, traj: OffPolicyTransition, env_steps):
        flat = jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), traj)
        rbuf = replay.add_batch(ls.replay, flat, codecs)
        do_update = jnp.logical_and(
            env_steps >= cfg.warmup_steps, rbuf.size >= cfg.batch_size
        )
        return update_loop(ls._replace(replay=rbuf), do_update)

    return ingest_update


def make_device_ingest_update(
    action_dim: int, cfg: SACConfig, ring_codecs: dict
):
    """Device-data-plane ingest (ISSUE 13): in-jit ring gather + decode
    ahead of the replay scatter and update loop — zero host→device
    transfers per consumed block (ddpg.make_device_ingest_update
    docstring; SAC's update gate is batch_size, it has no n-step
    window)."""
    from actor_critic_tpu.data_plane import device_replay

    return device_replay.make_device_ingest_update(
        make_update_loop, action_dim, cfg, ring_codecs,
        min_size=cfg.batch_size,
    )


def make_greedy_act(action_dim: int, cfg: SACConfig):
    """Tanh-mean actor for host eval (host_loop.host_evaluate)."""
    actor, _ = _modules(action_dim, cfg)
    return lambda params, obs: actor.apply(params, obs).mode()


def train_host(
    pool,
    cfg: SACConfig,
    num_iterations: int,
    seed: int = 0,
    log_every: int = 10,
    log_fn: Optional[Callable[[int, dict], None]] = None,
    eval_every: int = 0,
    eval_envs: int = 4,
    eval_steps: int = 1000,
    ckpt=None,
    save_every: int = 0,
    resume: bool = False,
    overlap: bool = True,
    save_replay: bool = True,
):
    """SAC on a HostEnvPool (host rollout, device learner). Use
    normalize_obs=False AND normalize_reward=False on the pool: running-
    stat obs normalization scales replayed transitions inconsistently as
    the stats drift, and the critic then bootstraps across mixed frames —
    observed in-session to send SAC Humanoid-v5 into a Q/alpha runaway
    (alpha 0.2 -> 18, Q ~17k) that raw observations eliminate; TD targets
    likewise want raw reward scale.
    `overlap` acts via the numpy host mirror with 1-update-stale params
    so device updates run during collection (host_loop docstring)."""
    from actor_critic_tpu.algos.host_loop import off_policy_train_host
    from actor_critic_tpu.models.host_actor import (
        make_sac_host_explore,
        make_sac_host_greedy,
    )

    return off_policy_train_host(
        pool, cfg, num_iterations,
        init_learner=init_learner,
        make_act_fn=make_host_act_fn,
        make_ingest_update=make_host_ingest_update,
        seed=seed, log_every=log_every, log_fn=log_fn,
        eval_every=eval_every, make_greedy_act=make_greedy_act,
        eval_envs=eval_envs, eval_steps=eval_steps,
        ckpt=ckpt, save_every=save_every, resume=resume,
        overlap=overlap, make_host_explore=make_sac_host_explore,
        make_host_greedy=make_sac_host_greedy,
        save_replay=save_replay,
    )


def train_host_async(
    pools,
    cfg: SACConfig,
    num_iterations: int,
    seed: int = 0,
    log_every: int = 10,
    log_fn: Optional[Callable[[int, dict], None]] = None,
    eval_every: int = 0,
    eval_envs: int = 4,
    eval_steps: int = 1000,
    queue_depth: int = 4,
    max_staleness: Optional[int] = None,
    data_plane: str = "host",
    plane_codec: str = "fp32",
    publish_hook: Optional[Callable[[int, object], None]] = None,
):
    """SAC with decoupled actor services (ISSUE 9 satellite; mirrors
    ddpg.train_host_async — replay absorbs behavior staleness, only the
    ingest hand-off is wired through the queue; `data_plane="device"`
    stages blocks encoded in HBM, ISSUE 13). Returns
    (learner, history)."""
    from actor_critic_tpu.algos.host_loop import off_policy_train_host_async
    from actor_critic_tpu.models.host_actor import (
        make_sac_host_explore,
        make_sac_host_greedy,
    )

    return off_policy_train_host_async(
        pools, cfg, num_iterations,
        init_learner=init_learner,
        make_ingest_update=make_host_ingest_update,
        make_host_explore=make_sac_host_explore,
        make_host_greedy=make_sac_host_greedy,
        seed=seed, log_every=log_every, log_fn=log_fn,
        eval_every=eval_every, eval_envs=eval_envs, eval_steps=eval_steps,
        queue_depth=queue_depth, max_staleness=max_staleness,
        data_plane=data_plane, plane_codec=plane_codec,
        make_device_ingest_update=make_device_ingest_update,
        publish_hook=publish_hook,
    )


# -- AOT warmup registry (utils/compile_cache.py, ISSUE 4) ------------------
from actor_critic_tpu.utils import compile_cache as _compile_cache  # noqa: E402

_compile_cache.register_offpolicy_warmups(
    "sac", ("sac",),
    init_learner=init_learner,
    make_host_act_fn=make_host_act_fn,
    make_host_ingest_update=make_host_ingest_update,
    make_greedy_act=make_greedy_act,
    init_state=init_state,
    make_train_step=make_train_step,
    make_eval_fn=make_eval_fn,
)
