"""IMPALA / A3C — decoupled-actor semantics, reformulated for TPU.

Capability parity with the reference's fifth config: "A3C / IMPALA on
Atari Pong (CNN encoder, N parallel actors, V-trace)" (BASELINE.json:11;
reference mount empty at survey, SURVEY.md §0).

The reference's genre runs N async host workers feeding a learner over
IPC queues (SURVEY.md §3.3); the off-policyness that V-trace corrects is
an *accident* of that asynchrony.  The TPU-native reformulation
(SURVEY.md §2.3 "Async actor-learner") keeps the semantics and drops the
host machinery:

- the N parallel actors become a vmapped env axis inside one jitted
  program (the same fused rollout as A2C);
- the actor policy is a deliberately STALE copy of the learner params,
  refreshed every `actor_refresh_every` learner steps — reproducing
  IMPALA's k-step policy lag explicitly and deterministically;
- behaviour log-probs are recorded at rollout time and V-trace
  (ops/returns.py) corrects the lag at the learner, exactly as IMPALA's
  importance weights correct queue-induced lag.

`correction="vtrace"` is IMPALA; `correction="none"` computes plain
λ-return advantages under the learner's critic with no importance
weighting — the A3C update rule (which simply tolerates the small bias
that staleness introduces), so both reference algorithms are covered by
one trainer.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from actor_critic_tpu.algos.common import (
    Policy,
    RolloutState,
    as_policy,
    corrected_advantages,
    feedforward_policy,
    init_rollout,
    masked_mean,
    rollout_scan,
    episode_metrics_update,
    truncation_bootstrap,
)
from actor_critic_tpu.algos.metrics import aggregate_metrics
from actor_critic_tpu.envs.jax_env import JaxEnv
from actor_critic_tpu.models import seq_policy
from actor_critic_tpu.models.networks import ActorCriticDiscrete, ActorCriticGaussian
from actor_critic_tpu.parallel import mesh as pmesh


@dataclasses.dataclass(frozen=True)
class ImpalaConfig:
    num_envs: int = 32          # the reference's "N parallel actors"
    rollout_steps: int = 20     # IMPALA's unroll length
    gamma: float = 0.99
    lr: float = 6e-4
    value_coef: float = 0.5
    entropy_coef: float = 0.01
    rho_bar: float = 1.0        # V-trace ρ̄ clip
    c_bar: float = 1.0          # V-trace c̄ clip
    lam: float = 1.0            # V-trace λ (1.0 = canonical IMPALA)
    actor_refresh_every: int = 1  # k-step policy lag (1 = on-policy)
    correction: str = "vtrace"  # "vtrace" (IMPALA) | "none" (A3C)
    max_grad_norm: float = 40.0
    hidden: tuple[int, ...] = (64, 64)
    # RMSProp epsilon/decay follow the IMPALA paper's published settings.
    rms_decay: float = 0.99
    rms_eps: float = 0.1
    bf16_compute: bool = False
    # A sequence-model policy over a token env (`models/seq_policy.py`),
    # reached as `--set seq.<field>=...`; None: the MLP / CNN torsos.
    seq: Optional[seq_policy.SeqPolicyConfig] = None

    def __post_init__(self):
        if self.correction not in ("vtrace", "none"):
            raise ValueError(f"unknown correction: {self.correction!r}")
        if self.actor_refresh_every < 1:
            raise ValueError("actor_refresh_every must be >= 1")


class ImpalaTrainState(NamedTuple):
    params: Any           # learner params
    actor_params: Any     # stale behaviour-policy params
    opt_state: Any
    rollout: RolloutState
    key: jax.Array
    update_step: jax.Array
    ep_return: jax.Array
    ep_length: jax.Array
    avg_return: jax.Array


def make_network(env: JaxEnv, cfg: ImpalaConfig):
    dtype = jnp.bfloat16 if cfg.bf16_compute else jnp.float32
    if env.spec.discrete:
        return ActorCriticDiscrete(
            num_actions=env.spec.action_dim,
            hidden=cfg.hidden,
            pixel_obs=env.spec.pixel_obs,
            compute_dtype=dtype,
        )
    return ActorCriticGaussian(
        action_dim=env.spec.action_dim, hidden=cfg.hidden, compute_dtype=dtype
    )


def make_policy(env: JaxEnv, cfg: ImpalaConfig) -> Policy:
    """How this configuration acts and learns: the torso's `apply` through
    the feed-forward adapter, or the sequence model with its cache (which
    refuses an env whose episode is not exactly one unroll)."""
    if cfg.seq is not None:
        return seq_policy.make_policy(env.spec, cfg.seq, cfg.rollout_steps)
    return feedforward_policy(make_network(env, cfg).apply)


def init_params(env: JaxEnv, cfg: ImpalaConfig, key: jax.Array):
    if cfg.seq is not None:
        return seq_policy.init_params(key, cfg.seq, env.spec.action_dim)
    dummy = jnp.zeros((1, *env.spec.obs_shape), env.spec.obs_dtype)
    return make_network(env, cfg).init(key, dummy)


def make_eval_fn(env: JaxEnv, cfg: "ImpalaConfig"):
    """Greedy (mode-action) eval program (SURVEY.md §3.4)."""
    from actor_critic_tpu.algos.common import make_mode_eval

    return make_mode_eval(env, make_policy(env, cfg))


def make_optimizer(cfg: ImpalaConfig) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(cfg.max_grad_norm),
        optax.rmsprop(cfg.lr, decay=cfg.rms_decay, eps=cfg.rms_eps),
    )


def init_state(env: JaxEnv, cfg: ImpalaConfig, key: jax.Array) -> ImpalaTrainState:
    opt = make_optimizer(cfg)
    key, pkey, rkey = jax.random.split(key, 3)
    params = init_params(env, cfg, pkey)
    E = cfg.num_envs
    return ImpalaTrainState(
        params=params,
        # In sync until the first refresh boundary; materialized as a
        # distinct buffer so donating the whole state never aliases the
        # same array twice (donation is how the fused loops avoid copies).
        actor_params=jax.tree.map(jnp.copy, params),
        opt_state=opt.init(params),
        rollout=init_rollout(env, rkey, E),
        key=key,
        update_step=jnp.zeros((), jnp.int32),
        ep_return=jnp.zeros((E,)),
        ep_length=jnp.zeros((E,)),
        avg_return=jnp.zeros(()),
    )


def impala_loss(
    params: Any,
    policy: Any,
    traj,
    bootstrap_obs: jax.Array,
    cfg: ImpalaConfig,
    can_truncate: bool = True,
    time_axis_name: Optional[str] = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """V-trace (or A3C λ-return) actor-critic loss on a [T, E] trajectory.

    The learner re-evaluates π/V at the stored observations (`policy.unroll`;
    `policy` is a `common.Policy` or a bare `apply_fn`); `traj.log_prob`
    holds the BEHAVIOUR policy's log-probs from rollout time, so the
    ρ = π/μ importance ratios are exact even under parameter staleness.
    Where the policy masks steps (`Unrolled.mask`: the env ignored the
    action there), they leave every mean of the loss and their importance
    ratio is 1, so V-trace passes through them as through on-policy steps.

    With `time_axis_name` the function runs INSIDE shard_map with the
    trajectory's TIME axis sharded over that mesh axis (sequence
    parallelism, SURVEY.md §5.7): the V-trace/GAE recurrences go through
    `parallel.seqpar` (halo exchange + per-segment affine scan + boundary
    chain over ICI), and the returned loss/metrics are LOCAL means whose
    gradients the caller must pmean over the axis (equal time shards make
    the pmean of local-mean grads exactly the global-mean grad).
    """
    policy = as_policy(policy)
    # Each phase below runs under a `jax.named_scope`: metadata only (the
    # HLO is the same), and the first component of every operation's name
    # stack in a profiler trace, which is what tells the pass over `obs`
    # from the pass over `final_obs` (benchmark/phases.py reads them; the
    # backward pass shows as `transpose(jvp(<scope>))` by itself).
    with jax.named_scope("forward"):
        out = policy.unroll(params, traj)
        target_log_probs, values, mask = out.log_prob, out.value, out.mask
        # Explicit fp32 accumulators on every reduction: bit-identical in
        # fp32 mode (the heads cast up), precision-discipline-required
        # under --update-dtype bf16 (bf16 compute, fp32 accumulation).
        entropy = masked_mean(out.entropy, mask)
    with jax.named_scope("bootstrap"):
        bootstrap_value = policy.bootstrap(params, bootstrap_obs)

    if can_truncate:
        # Truncation bootstrap under the LEARNER's critic, on the truncated
        # rows only; primal only (no gradient reaches the rewards below).
        with jax.named_scope("final_obs"):
            rewards = truncation_bootstrap(
                lambda p, obs: (None, policy.bootstrap(p, obs)),
                jax.lax.stop_gradient(params), traj, cfg.gamma,
            )
            truncated_frac = jnp.mean(
                traj.done * (1.0 - traj.terminated), dtype=jnp.float32
            )
    else:
        rewards = traj.reward
        truncated_frac = jnp.zeros((), jnp.float32)

    # Correction machinery shared with the async actor–learner PPO
    # update (ISSUE 6): V-trace or plain λ-return, sequence-parallel
    # when a time axis name is given.
    vtrace_target_lp = jax.lax.stop_gradient(target_log_probs)
    if mask is not None:
        vtrace_target_lp = jnp.where(mask > 0, vtrace_target_lp, traj.log_prob)
    pg_advantages, value_targets, mean_rho = corrected_advantages(
        vtrace_target_lp,
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
        pg_loss = -masked_mean(
            jax.lax.stop_gradient(pg_advantages) * target_log_probs, mask
        )
        v_loss = 0.5 * masked_mean(
            (values - jax.lax.stop_gradient(value_targets)) ** 2, mask
        )
        loss = pg_loss + cfg.value_coef * v_loss - cfg.entropy_coef * entropy
    return loss, {
        "loss": loss,
        "pg_loss": pg_loss,
        "v_loss": v_loss,
        "entropy": entropy,
        "mean_rho": mean_rho,
        "truncated_frac": truncated_frac,
        **out.metrics,
    }


def make_train_step(
    env: JaxEnv,
    cfg: ImpalaConfig,
    axis_name: Optional[str] = None,
) -> Callable[[ImpalaTrainState], tuple[ImpalaTrainState, dict[str, jax.Array]]]:
    """Fused rollout(stale actor) → V-trace → update → k-step actor refresh."""
    policy = make_policy(env, cfg)
    opt = make_optimizer(cfg)

    def train_step(state: ImpalaTrainState):
        key, rkey = jax.random.split(state.key)

        # Actors run the STALE params; behaviour log-probs are recorded.
        new_rollout, traj, counted = rollout_scan(
            env, policy, state.actor_params, state.rollout, rkey,
            cfg.rollout_steps, policy_metrics=True,
        )

        grad_fn = jax.value_and_grad(impala_loss, has_aux=True)
        (_, metrics), grads = grad_fn(
            state.params, policy, traj, new_rollout.obs, cfg,
            env.spec.can_truncate,
        )
        metrics = {**metrics, **counted}
        with jax.named_scope("optimizer"):
            grads = pmesh.pmean_tree(grads, axis_name)
            updates, new_opt_state = opt.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)

            # k-step policy lag: actors pick up the learner params only at
            # refresh boundaries (k=1 degrades gracefully to on-policy,
            # where every ρ is exactly 1 — tested in tests/test_impala.py).
            new_step = state.update_step + 1
            refresh = (new_step % cfg.actor_refresh_every) == 0
            new_actor_params = jax.tree.map(
                lambda n, o: jnp.where(refresh, n, o),
                new_params, state.actor_params,
            )

        ep_ret, ep_len, avg_ret, ep_metrics = episode_metrics_update(
            state.ep_return, state.ep_length, state.avg_return, traj
        )
        avg_ret = pmesh.pmean(avg_ret, axis_name)
        ep_metrics["avg_return_ema"] = avg_ret
        metrics = aggregate_metrics(metrics, ep_metrics, axis_name)

        new_state = ImpalaTrainState(
            params=new_params,
            actor_params=new_actor_params,
            opt_state=new_opt_state,
            rollout=new_rollout,
            key=key,
            update_step=new_step,
            ep_return=ep_ret,
            ep_length=ep_len,
            avg_return=avg_ret,
        )
        return new_state, metrics

    return train_step


def make_sp_update(
    env: JaxEnv, cfg: ImpalaConfig, mesh, axis_name=None, dp_axis_name=None
):
    """Sequence-parallel learner update for LONG trajectories (SURVEY.md
    §5.7 made load-bearing): the [T, E] trajectory's TIME axis is sharded
    over the mesh's "sp" axis, so each device forwards π/V on its T/D
    slice, the V-trace (or λ-return) recurrence runs through
    `parallel.seqpar` (one ppermute halo + per-segment affine scan + a
    tiny all_gather boundary chain — collectives ride ICI), and gradients
    pmean over the axis. Per-device activation memory and scan length
    drop from O(T) to O(T/D): trajectories too long for one device's HBM
    (or one scan's latency budget) become trainable.

    With `dp_axis_name` the update runs over a 2-D sp×dp mesh: the env
    batch axis additionally shards over dp (the recurrence is
    independent per env, so dp needs no extra communication beyond the
    gradient/metric pmean, which then reduces over BOTH axes).

    Returns jitted `(params, opt_state, traj, bootstrap_obs) →
    (params, opt_state, metrics)` on GLOBAL [T, E] arrays; T must divide
    by the mesh's sp size (and E by its dp size). Metric-equivalence
    with the unsharded update is tested on the 8-device CPU mesh, in
    both 1-D sp and 2×4 sp×dp layouts (tests/test_seqpar.py).
    """
    fn, _, _ = _sp_update_shardmap(env, cfg, mesh, axis_name, dp_axis_name)
    return jax.jit(fn)


def _sp_update_shardmap(env, cfg, mesh, axis_name=None, dp_axis_name=None):
    """The shard_map'd sp learner update, un-jitted, plus the traj /
    bootstrap PartitionSpecs — shared by `make_sp_update` (standalone)
    and `make_sp_train_step` (fused rollout→update program)."""
    from jax.sharding import PartitionSpec as P

    from actor_critic_tpu.parallel.seqpar import SP_AXIS

    axis_name = axis_name or SP_AXIS
    # lax.pmean accepts an axis-name tuple: one reduction over both axes.
    reduce_axes = (
        axis_name if dp_axis_name is None else (axis_name, dp_axis_name)
    )
    traj_spec = (
        P(axis_name) if dp_axis_name is None else P(axis_name, dp_axis_name)
    )
    boot_spec = P() if dp_axis_name is None else P(dp_axis_name)
    net = make_network(env, cfg)
    opt = make_optimizer(cfg)

    def local_update(params, opt_state, traj, bootstrap_obs):
        grad_fn = jax.value_and_grad(impala_loss, has_aux=True)
        (_, metrics), grads = grad_fn(
            params, net.apply, traj, bootstrap_obs, cfg,
            env.spec.can_truncate, axis_name,
        )
        grads = pmesh.pmean_tree(grads, reduce_axes)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        metrics = {k: pmesh.pmean(v, reduce_axes) for k, v in metrics.items()}
        return params, opt_state, metrics

    fn = jax.shard_map(
        local_update,
        mesh=mesh,
        in_specs=(P(), P(), traj_spec, boot_spec),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return fn, traj_spec, boot_spec


def make_sp_train_step(
    env: JaxEnv, cfg: ImpalaConfig, mesh, axis_name=None, dp_axis_name=None
):
    """ONE jitted program: rollout(stale actor) → resharding constraint →
    sequence-parallel V-trace update → k-step actor refresh.

    This is the end-to-end form of the claim sp exists for: a trainer
    PRODUCES the long [T, E] trajectory (rollout is time-sequential by
    nature, so it runs env-parallel — sharded over the mesh's dp axis
    when present) and the learner consumes it time-sharded over sp, with
    XLA inserting the redistribution between the two layouts inside the
    same program. Metric/param equivalence with `make_train_step` is
    tested on the 8-device CPU mesh (tests/test_seqpar.py).
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    upd, traj_spec, _ = _sp_update_shardmap(
        env, cfg, mesh, axis_name, dp_axis_name
    )
    net = make_network(env, cfg)
    apply_fn = net.apply

    def train_step(state: ImpalaTrainState):
        key, rkey = jax.random.split(state.key)
        # The rollout is time-SEQUENTIAL (a scan), so it cannot be sp-
        # sharded; pin its carry replicated so sharding propagation from
        # the sp-resharded consumer below can't leak a partitioned
        # layout back into the per-step vmap (explicit-mesh axes are
        # part of the value types).
        rollout_in = jax.tree.map(
            lambda x: jax.sharding.reshard(x, NamedSharding(mesh, P())),
            state.rollout,
        )
        new_rollout, traj = rollout_scan(
            env, apply_fn, state.actor_params, rollout_in, rkey,
            cfg.rollout_steps,
        )
        # Episode accounting folds a scan over TIME, so it reads the
        # rollout-layout trajectory (before the time axis is sharded).
        ep_ret, ep_len, avg_ret, ep_metrics = episode_metrics_update(
            state.ep_return, state.ep_length, state.avg_return, traj
        )

        # Rollout materializes [T, E] time-major on the dp layout; the
        # reshard makes XLA redistribute the TIME axis over sp for the
        # learner (an all-to-all over ICI) inside this program. (The
        # mesh axes are Explicit-typed, so `reshard` is the constraint
        # API — with_sharding_constraint only talks to Auto axes.)
        traj_sp = jax.tree.map(
            lambda x: jax.sharding.reshard(
                x,
                NamedSharding(
                    mesh,
                    P(*traj_spec, *((None,) * (x.ndim - len(traj_spec)))),
                ),
            ),
            traj,
        )
        new_params, new_opt_state, metrics = upd(
            state.params, state.opt_state, traj_sp, new_rollout.obs
        )

        new_step = state.update_step + 1
        refresh = (new_step % cfg.actor_refresh_every) == 0
        new_actor_params = jax.tree.map(
            lambda n, o: jnp.where(refresh, n, o), new_params,
            state.actor_params,
        )
        ep_metrics["avg_return_ema"] = avg_ret
        # Same derived metric keys as make_train_step (mean_finished_
        # return, mean_ep_length, ...): upd's metrics are already
        # mesh-reduced and ep_metrics are global-array sums, so no axis.
        metrics = aggregate_metrics(metrics, ep_metrics, None)
        new_state = ImpalaTrainState(
            params=new_params,
            actor_params=new_actor_params,
            opt_state=new_opt_state,
            rollout=new_rollout,
            key=key,
            update_step=new_step,
            ep_return=ep_ret,
            ep_length=ep_len,
            avg_return=avg_ret,
        )
        return new_state, metrics

    return jax.jit(train_step)


def train(
    env: JaxEnv,
    cfg: ImpalaConfig,
    num_iterations: int,
    seed: int = 0,
    state: Optional[ImpalaTrainState] = None,
    log_every: int = 0,
    log_fn: Optional[Callable[[int, dict], None]] = None,
) -> tuple[ImpalaTrainState, dict[str, jax.Array]]:
    """Host loop around the fused step; `log_every=0` scans all iterations
    on-device in a single dispatch (same pattern as a2c.train)."""
    from actor_critic_tpu.algos.host_loop import fused_train_loop

    return fused_train_loop(
        make_train_step, init_state, env, cfg, num_iterations,
        seed=seed, state=state, log_every=log_every, log_fn=log_fn,
        scan_when_silent=True,
    )


# -- AOT warmup registry (utils/compile_cache.py, ISSUE 4) ------------------
# The sp (mesh-sharded) programs are exempt from warmup: they are built
# only by the explicit parallel drivers (see compile_cache.EXEMPT).
from actor_critic_tpu.utils import compile_cache as _compile_cache  # noqa: E402

_compile_cache.register_fused_warmups(
    "impala", ("impala", "a3c"), init_state, make_train_step, make_eval_fn
)
