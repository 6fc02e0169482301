"""DDPG / TD3 — off-policy deterministic actor-critic, replay in HBM.

Capability parity with the reference's DDPG/TD3 Walker2d config
(BASELINE.json:9: "off-policy, HBM replay buffer, target nets"; reference
mount empty at survey, SURVEY.md §0). TD3 is DDPG plus three flags
(`twin_q`, `policy_delay`, `target_noise`) — one implementation, two
configs, matching how the reference layers TD3 over DDPG (SURVEY §2.1).

TPU-first structure (SURVEY §3.2 boundary fix): one jitted train step =

    lax.scan over K env steps: [actor fwd + noise → vmapped env.step]
    → replay.add_batch (in-HBM scatter, donated)
    → lax.scan over J updates: [replay.sample → critic TD step
         → (delayed) actor step + Polyak targets]

so replay storage, sampling RNG, and both optimizers never leave the
device. The reference's per-update host→device `buffer.sample(B)` copy
does not exist here. Delayed actor/target updates are branchless
`where`-selects (no `cond` inside the vmapped/scanned update loop).

For MuJoCo (host-stepped, SURVEY §7.2 item 2) `train_host` keeps the
same learner program and feeds it one [K, E] transition block per
iteration — a single host→device transfer.
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
from actor_critic_tpu.models.networks import DeterministicActor, QFunction, TwinQ
from actor_critic_tpu.ops.polyak import polyak_update
from actor_critic_tpu.parallel import mesh as pmesh


@dataclasses.dataclass(frozen=True)
class DDPGConfig:
    num_envs: int = 8
    steps_per_iter: int = 8      # K env steps per train_step call
    updates_per_iter: int = 8    # J gradient updates per train_step call
    buffer_capacity: int = 1_000_000
    batch_size: int = 256
    gamma: float = 0.99
    tau: float = 0.005
    actor_lr: float = 3e-4
    critic_lr: float = 3e-4
    hidden: tuple[int, ...] = (256, 256)
    exploration_noise: float = 0.1  # behavior-policy Gaussian noise std
    warmup_steps: int = 1_000       # uniform-random action steps (per device)
    # --- TD3 extensions (BASELINE.json:9) ---
    twin_q: bool = False
    policy_delay: int = 1
    target_noise: float = 0.0       # target-policy smoothing std
    target_noise_clip: float = 0.5
    bf16_compute: bool = False
    # --- n-step returns (replay.sample_sequences consumer) ---
    # nstep > 1 samples length-n windows of consecutive inserts and
    # trains the critic on the n-step target
    #   G = Σ_{k<m} γ^k r_k  +  γ^m (1 − terminated_{m−1}) Q̄(s_m, π̄(s_m)),
    # where m is the window length up to the first episode end (done
    # cuts the sum; truncation bootstraps through, exactly like the
    # 1-step path). Requires num_envs == 1: the ring stores flattened
    # [K, E] rollouts, so consecutive inserts are one env's consecutive
    # timesteps only for a single env (replay.sample_sequences guards
    # the ring seam, not env interleaving).
    nstep: int = 1
    # --- quantized replay storage (ISSUE 8, replay/quantize.py) ---
    # "fp32" stores transitions as-is; "mixed" stores obs/rewards as
    # standardized int8 + done flags as int8 with actions kept fp32
    # (~3.1x transitions per HBM byte at Pendulum shape); "int8" also
    # quantizes the bounded actions (~4x, aggressive).
    replay_dtype: str = "fp32"


def td3_config(**overrides) -> DDPGConfig:
    """TD3 = DDPG + twin critics, delayed policy, target smoothing."""
    base = dict(twin_q=True, policy_delay=2, target_noise=0.2)
    base.update(overrides)
    return DDPGConfig(**base)


class LearnerState(NamedTuple):
    """Device-resident learner: params, targets, optimizers, replay ring."""

    actor_params: Any
    critic_params: Any
    target_actor: Any
    target_critic: Any
    actor_opt: Any
    critic_opt: Any
    replay: replay.ReplayState
    key: jax.Array
    update_count: jax.Array  # gradient updates so far (drives policy delay)


class OffPolicyState(NamedTuple):
    """Fused-trainer state: learner + on-device env batch + accounting."""

    learner: LearnerState
    rollout: RolloutState
    env_steps: jax.Array  # per-device env steps (warmup gating)
    update_step: jax.Array  # train_step calls
    ep_return: jax.Array
    ep_length: jax.Array
    avg_return: jax.Array


def _modules(action_dim: int, cfg: DDPGConfig):
    dtype = jnp.bfloat16 if cfg.bf16_compute else jnp.float32
    actor = DeterministicActor(action_dim, cfg.hidden, compute_dtype=dtype)
    critic = (
        TwinQ(cfg.hidden, compute_dtype=dtype)
        if cfg.twin_q
        else QFunction(cfg.hidden, compute_dtype=dtype)
    )
    return actor, critic


def _critic_q(critic, params, obs, action, cfg: DDPGConfig):
    """(q1, q2) from either critic flavor; q2 is None without twin-Q."""
    if cfg.twin_q:
        return critic.apply(params, obs, action)
    return critic.apply(params, obs, action), None


def init_learner(
    obs_shape: tuple[int, ...], action_dim: int, cfg: DDPGConfig, key: jax.Array
) -> LearnerState:
    actor, critic = _modules(action_dim, cfg)
    akey, ckey, lkey = jax.random.split(key, 3)
    dummy_obs = jnp.zeros((1, *obs_shape), jnp.float32)
    dummy_act = jnp.zeros((1, action_dim), jnp.float32)
    actor_params = actor.init(akey, dummy_obs)
    critic_params = critic.init(ckey, dummy_obs, dummy_act)
    example = OffPolicyTransition(
        obs=jnp.zeros(obs_shape, jnp.float32),
        action=jnp.zeros((action_dim,), jnp.float32),
        reward=jnp.zeros((), jnp.float32),
        next_obs=jnp.zeros(obs_shape, jnp.float32),
        terminated=jnp.zeros((), jnp.float32),
        done=jnp.zeros((), jnp.float32),
    )
    return LearnerState(
        actor_params=actor_params,
        critic_params=critic_params,
        # Targets start equal but must be distinct buffers: the fused
        # trainer donates its state, and XLA rejects aliased donations.
        target_actor=jax.tree.map(jnp.copy, actor_params),
        target_critic=jax.tree.map(jnp.copy, critic_params),
        actor_opt=optax.adam(cfg.actor_lr).init(actor_params),
        critic_opt=optax.adam(cfg.critic_lr).init(critic_params),
        replay=replay.init(
            example, cfg.buffer_capacity,
            replay.offpolicy_codecs(cfg.replay_dtype),
        ),
        key=lkey,
        update_count=jnp.zeros((), jnp.int32),
    )


def init_state(env: JaxEnv, cfg: DDPGConfig, key: jax.Array) -> OffPolicyState:
    key, lkey, rkey = jax.random.split(key, 3)
    learner = init_learner(env.spec.obs_shape, env.spec.action_dim, cfg, lkey)
    E = cfg.num_envs
    return OffPolicyState(
        learner=learner,
        rollout=init_rollout(env, rkey, E),
        env_steps=jnp.zeros((), jnp.int32),
        update_step=jnp.zeros((), jnp.int32),
        ep_return=jnp.zeros((E,)),
        ep_length=jnp.zeros((E,)),
        avg_return=jnp.zeros(()),
    )


def make_eval_fn(env: JaxEnv, cfg: "DDPGConfig"):
    """Greedy (noiseless actor) eval program (SURVEY.md §3.4); see
    common.make_greedy_eval for the shared contract."""
    from actor_critic_tpu.algos.common import make_greedy_eval

    actor, _ = _modules(env.spec.action_dim, cfg)
    return make_greedy_eval(
        env, lambda p, o: actor.apply(p, o), lambda s: s.learner.actor_params
    )


def make_explore_fn(action_dim: int, cfg: DDPGConfig):
    """Behavior policy: actor + clipped Gaussian noise; uniform actions
    during warmup (branchless `where` on the env-step counter)."""
    actor, _ = _modules(action_dim, cfg)

    def act(params, obs, key, env_steps):
        nkey, ukey = jax.random.split(key)
        a = actor.apply(params, obs)
        a = a + cfg.exploration_noise * jax.random.normal(nkey, a.shape)
        a = jnp.clip(a, -1.0, 1.0)
        rand = jax.random.uniform(ukey, a.shape, minval=-1.0, maxval=1.0)
        return jnp.where(env_steps < cfg.warmup_steps, rand, a)

    return act


def nstep_batch(
    seq: OffPolicyTransition, gamma: float
) -> tuple[OffPolicyTransition, jax.Array]:
    """[B, n] sequence windows → (1-step-shaped batch, bootstrap discount).

    The returned batch's `reward` carries the masked n-step return prefix
    G = Σ_{k<m} γ^k r_k (m = steps up to and including the first done;
    the done step's own reward counts — it is the terminal reward), and
    `next_obs`/`terminated` are the window-END transition's (first done
    step, else the last). The bootstrap discount is γ^m, so
    target = G + γ^m (1 − terminated_end) Q̄(next_obs_end, ·) matches the
    1-step TD shape exactly — truncations bootstrap through, terminations
    mask, episodes never splice (`replay.sample_sequences` consumer).
    """
    n = seq.reward.shape[1]
    d = seq.done.astype(jnp.float32)  # [B, n]
    alive_before = jnp.cumprod(
        jnp.concatenate([jnp.ones_like(d[:, :1]), 1.0 - d[:, :-1]], axis=1),
        axis=1,
    )
    gammas = gamma ** jnp.arange(n, dtype=jnp.float32)
    g = jnp.sum(seq.reward * alive_before * gammas, axis=1)
    any_done = jnp.max(d, axis=1) > 0
    end_idx = jnp.where(any_done, jnp.argmax(d, axis=1), n - 1)  # [B]

    def at_end(x):
        idx = end_idx.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.take_along_axis(x, idx, axis=1)[:, 0]

    batch = OffPolicyTransition(
        obs=seq.obs[:, 0],
        action=seq.action[:, 0],
        reward=g,
        next_obs=at_end(seq.next_obs),
        terminated=at_end(seq.terminated),
        done=seq.done[:, 0],
    )
    return batch, gamma ** (end_idx.astype(jnp.float32) + 1.0)


def make_update_loop(
    action_dim: int,
    cfg: DDPGConfig,
    axis_name: Optional[str] = None,
) -> Callable[[LearnerState, jax.Array], tuple[LearnerState, dict[str, jax.Array]]]:
    """Build `(learner, do_update) → (learner, metrics)` running
    `cfg.updates_per_iter` sample→TD→(delayed) actor steps in one scan.

    `do_update` gates learning during warmup: grads are still computed
    (static program) but params/targets/optimizer state are `where`-kept.
    """
    actor, critic = _modules(action_dim, cfg)
    codecs = replay.offpolicy_codecs(cfg.replay_dtype)
    if cfg.nstep < 1:
        raise ValueError(f"nstep must be >= 1, got {cfg.nstep}")
    if cfg.nstep > 1 and cfg.num_envs != 1:
        raise ValueError(
            "nstep > 1 requires num_envs == 1: the replay ring stores "
            "flattened [K, E] rollouts, so consecutive inserts interleave "
            "envs unless E == 1 (see DDPGConfig.nstep)"
        )

    def critic_loss_fn(critic_params, target_q, batch: OffPolicyTransition):
        q1, q2 = _critic_q(critic, critic_params, batch.obs, batch.action, cfg)
        loss = jnp.mean((q1 - target_q) ** 2)
        if q2 is not None:
            loss = loss + jnp.mean((q2 - target_q) ** 2)
        return loss, jnp.mean(q1)

    def actor_loss_fn(actor_params, critic_params, obs):
        a = actor.apply(actor_params, obs)
        q1, _ = _critic_q(critic, critic_params, obs, a, cfg)
        return -jnp.mean(q1)

    def select(mask, new, old):
        return jax.tree.map(lambda n, o: jnp.where(mask, n, o), new, old)

    def one_update(ls: LearnerState, do_update: jax.Array):
        key, skey, tkey = jax.random.split(ls.key, 3)
        if cfg.nstep > 1:
            seq = replay.sample_sequences(
                ls.replay, skey, cfg.batch_size, cfg.nstep, codecs
            )
            batch, boot_discount = nstep_batch(seq, cfg.gamma)
        else:
            batch = replay.sample(ls.replay, skey, cfg.batch_size, codecs)
            boot_discount = cfg.gamma

        # --- TD target from target nets (+TD3 smoothing) ---
        next_a = actor.apply(ls.target_actor, batch.next_obs)
        if cfg.target_noise > 0.0:
            noise = jnp.clip(
                cfg.target_noise * jax.random.normal(tkey, next_a.shape),
                -cfg.target_noise_clip,
                cfg.target_noise_clip,
            )
            next_a = jnp.clip(next_a + noise, -1.0, 1.0)
        tq1, tq2 = _critic_q(critic, ls.target_critic, batch.next_obs, next_a, cfg)
        next_q = tq1 if tq2 is None else jnp.minimum(tq1, tq2)
        target_q = jax.lax.stop_gradient(
            batch.reward + boot_discount * (1.0 - batch.terminated) * next_q
        )

        # --- critic step (every update) ---
        (closs, q_mean), cgrads = jax.value_and_grad(critic_loss_fn, has_aux=True)(
            ls.critic_params, target_q, batch
        )
        cgrads = pmesh.pmean_tree(cgrads, axis_name)
        cupd, critic_opt = optax.adam(cfg.critic_lr).update(cgrads, ls.critic_opt)
        critic_params = optax.apply_updates(ls.critic_params, cupd)
        critic_params = select(do_update, critic_params, ls.critic_params)
        critic_opt = select(do_update, critic_opt, ls.critic_opt)

        # --- actor step + Polyak (every policy_delay-th update) ---
        do_actor = jnp.logical_and(
            do_update, (ls.update_count % cfg.policy_delay) == 0
        )
        aloss, agrads = jax.value_and_grad(actor_loss_fn)(
            ls.actor_params, critic_params, batch.obs
        )
        agrads = pmesh.pmean_tree(agrads, axis_name)
        aupd, actor_opt = optax.adam(cfg.actor_lr).update(agrads, ls.actor_opt)
        actor_params = optax.apply_updates(ls.actor_params, aupd)
        actor_params = select(do_actor, actor_params, ls.actor_params)
        actor_opt = select(do_actor, actor_opt, ls.actor_opt)
        target_actor = select(
            do_actor,
            polyak_update(actor_params, ls.target_actor, cfg.tau),
            ls.target_actor,
        )
        target_critic = select(
            do_actor,
            polyak_update(critic_params, ls.target_critic, cfg.tau),
            ls.target_critic,
        )

        new_ls = LearnerState(
            actor_params=actor_params,
            critic_params=critic_params,
            target_actor=target_actor,
            target_critic=target_critic,
            actor_opt=actor_opt,
            critic_opt=critic_opt,
            replay=ls.replay,
            key=key,
            update_count=ls.update_count + do_update.astype(jnp.int32),
        )
        metrics = {
            "critic_loss": closs,
            "actor_loss": aloss,
            "q_mean": q_mean,
        }
        return new_ls, metrics

    def update_loop(ls: LearnerState, do_update: jax.Array):
        def body(carry, _):
            return one_update(carry, do_update)

        ls, metrics = jax.lax.scan(body, ls, None, length=cfg.updates_per_iter)
        return ls, jax.tree.map(lambda m: m[-1], metrics)

    return update_loop


def make_train_step(
    env: JaxEnv,
    cfg: DDPGConfig,
    axis_name: Optional[str] = None,
) -> Callable[[OffPolicyState], tuple[OffPolicyState, dict[str, jax.Array]]]:
    """The fused collect→insert→update program (one jit dispatch)."""
    explore = make_explore_fn(env.spec.action_dim, cfg)
    update_loop = make_update_loop(env.spec.action_dim, cfg, axis_name)
    codecs = replay.offpolicy_codecs(cfg.replay_dtype)

    def train_step(state: OffPolicyState):
        ls = state.learner
        key, rkey = jax.random.split(ls.key)

        # --- collect K steps with the behavior policy ---
        rollout, env_steps, traj = offpolicy_rollout(
            env, explore, ls.actor_params, state.rollout, rkey,
            cfg.steps_per_iter, state.env_steps,
        )
        flat = jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), traj)
        # axis_name syncs the quantizer's running stats across dp so the
        # replicated QuantStats leaves stay identical on every device.
        rbuf = replay.add_batch(ls.replay, flat, codecs, axis_name=axis_name)

        # --- J gradient updates (gated until warmup + one batch in ring) ---
        # The floor is max(batch_size, nstep): sample_sequences clamps a
        # length-n window's start so the window fits inside [0, size), and
        # a ring holding fewer than n inserts would clamp windows into
        # zero-initialized slots — the first updates would train on
        # fabricated transitions.
        do_update = jnp.logical_and(
            env_steps >= cfg.warmup_steps,
            rbuf.size >= max(cfg.batch_size, cfg.nstep),
        )
        ls, metrics = update_loop(
            ls._replace(replay=rbuf, key=key), do_update
        )

        # --- accounting ---
        ep_ret, ep_len, avg_ret, ep_metrics = episode_metrics_update(
            state.ep_return, state.ep_length, state.avg_return, traj
        )
        avg_ret = pmesh.pmean(avg_ret, axis_name)
        ep_metrics["avg_return_ema"] = avg_ret
        metrics = aggregate_metrics(metrics, ep_metrics, axis_name)

        new_state = OffPolicyState(
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
    cfg: DDPGConfig,
    num_iterations: int,
    seed: int = 0,
    state: Optional[OffPolicyState] = None,
    log_every: int = 0,
    log_fn: Optional[Callable[[int, dict], None]] = None,
) -> tuple[OffPolicyState, dict[str, jax.Array]]:
    """Host loop around the fused step (single device), like a2c.train."""
    from actor_critic_tpu.algos.host_loop import fused_train_loop

    return fused_train_loop(
        make_train_step, init_state, env, cfg, num_iterations,
        seed=seed, state=state, log_every=log_every, log_fn=log_fn,
    )


# --------------------------------------------------------------------------
# Host-env path (MuJoCo Walker2d etc. — BASELINE.json:9)
# --------------------------------------------------------------------------

def make_host_act_fn(action_dim: int, cfg: DDPGConfig):
    """Jitted (params, obs, key, env_steps) → exploration action."""
    return jax.jit(make_explore_fn(action_dim, cfg))


def make_host_ingest_update(action_dim: int, cfg: DDPGConfig):
    """Jitted (learner, [K,E] transition block) → (learner, metrics).

    One host→device transfer per iteration; replay insert and the whole
    update loop stay on-device.
    """
    update_loop = make_update_loop(action_dim, cfg)
    codecs = replay.offpolicy_codecs(cfg.replay_dtype)

    @partial(jax.jit, donate_argnums=0)
    def ingest_update(ls: LearnerState, traj: OffPolicyTransition, env_steps):
        flat = jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), traj)
        rbuf = replay.add_batch(ls.replay, flat, codecs)
        # Same max(batch_size, nstep) floor as the fused path: n-step
        # windows must never clamp into zero-initialized ring slots.
        do_update = jnp.logical_and(
            env_steps >= cfg.warmup_steps,
            rbuf.size >= max(cfg.batch_size, cfg.nstep),
        )
        return update_loop(ls._replace(replay=rbuf), do_update)

    return ingest_update


def make_device_ingest_update(
    action_dim: int, cfg: DDPGConfig, ring_codecs: dict
):
    """Device-data-plane ingest (ISSUE 13): the staged block is
    gathered + decoded from the HBM trajectory ring INSIDE the jitted
    program before the replay scatter and update loop — zero
    host→device transfers per consumed block. The update-gate floor is
    the host path's max(batch_size, nstep) (n-step windows must never
    clamp into zero-initialized ring slots)."""
    from actor_critic_tpu.data_plane import device_replay

    return device_replay.make_device_ingest_update(
        make_update_loop, action_dim, cfg, ring_codecs,
        min_size=max(cfg.batch_size, cfg.nstep),
    )


def make_greedy_act(action_dim: int, cfg: DDPGConfig):
    """Noiseless actor for host eval (host_loop.host_evaluate)."""
    actor, _ = _modules(action_dim, cfg)
    return lambda params, obs: actor.apply(params, obs)


def train_host(
    pool,
    cfg: DDPGConfig,
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
    """DDPG/TD3 on a HostEnvPool (host rollout, device learner).

    Recommended pool settings for off-policy MuJoCo: normalize_obs=False
    AND normalize_reward=False — running-stat obs normalization scales
    replayed transitions inconsistently as the stats drift (the critic
    then bootstraps across mixed frames; observed to destabilize SAC on
    Humanoid-v5), and TD targets want raw reward scale.
    `overlap` acts via the numpy host mirror with 1-update-stale params
    so device updates run during collection (host_loop docstring).
    Returns (learner, history).
    """
    from actor_critic_tpu.algos.host_loop import off_policy_train_host
    from actor_critic_tpu.models.host_actor import (
        make_ddpg_host_explore,
        make_ddpg_host_greedy,
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
        overlap=overlap, make_host_explore=make_ddpg_host_explore,
        make_host_greedy=make_ddpg_host_greedy,
        save_replay=save_replay,
    )


def train_host_async(
    pools,
    cfg: DDPGConfig,
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
    """DDPG/TD3 with decoupled actor services (ISSUE 9 satellite; the
    PPO-only restriction of `--async-actors` lifted): one exploration
    thread per pool pushes [K, E_a] transition blocks through the
    bounded trajectory queue; the learner ingests each into the replay
    ring and updates — replay absorbs the behavior staleness natively,
    so there is no correction knob here. `data_plane="device"` stages
    the blocks encoded in HBM instead (ISSUE 13; see
    host_loop.off_policy_train_host_async). Returns (learner, history)."""
    from actor_critic_tpu.algos.host_loop import off_policy_train_host_async
    from actor_critic_tpu.models.host_actor import (
        make_ddpg_host_explore,
        make_ddpg_host_greedy,
    )

    return off_policy_train_host_async(
        pools, cfg, num_iterations,
        init_learner=init_learner,
        make_ingest_update=make_host_ingest_update,
        make_host_explore=make_ddpg_host_explore,
        make_host_greedy=make_ddpg_host_greedy,
        seed=seed, log_every=log_every, log_fn=log_fn,
        eval_every=eval_every, eval_envs=eval_envs, eval_steps=eval_steps,
        queue_depth=queue_depth, max_staleness=max_staleness,
        data_plane=data_plane, plane_codec=plane_codec,
        make_device_ingest_update=make_device_ingest_update,
        publish_hook=publish_hook,
    )


# -- AOT warmup registry (utils/compile_cache.py, ISSUE 4) ------------------
# Registers the host-path act / ingest+update / greedy programs (skipped
# where the numpy mirror replaces them) and the fused step/eval pair, so
# a background warmup compiles them while the env pool spawns/resets.
from actor_critic_tpu.utils import compile_cache as _compile_cache  # noqa: E402

_compile_cache.register_offpolicy_warmups(
    "ddpg", ("ddpg", "td3"),
    init_learner=init_learner,
    make_host_act_fn=make_host_act_fn,
    make_host_ingest_update=make_host_ingest_update,
    make_greedy_act=make_greedy_act,
    init_state=init_state,
    make_train_step=make_train_step,
    make_eval_fn=make_eval_fn,
)
