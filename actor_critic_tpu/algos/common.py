"""Shared trainer plumbing: transition pytrees, train state, rollout scan.

The fused on-device rollout is the framework's answer to the reference's
per-step host↔device ping-pong (SURVEY.md §3.1 boundary analysis;
reference mount empty, §0): `lax.scan` over T timesteps of
(policy forward → vmapped env step), with the whole thing living inside
one jitted train step (BASELINE.json:5 north star).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from actor_critic_tpu.envs.jax_env import JaxEnv


class Transition(NamedTuple):
    """One time-slice of a vmapped rollout; arrays are [T, E, ...] after scan."""

    obs: jax.Array
    action: jax.Array
    log_prob: jax.Array
    value: jax.Array
    reward: jax.Array
    done: jax.Array        # episode ended this step (term or trunc)
    terminated: jax.Array  # true termination (cuts bootstrap)
    final_obs: jax.Array   # pre-reset obs of the step (== next obs if not done)


class RolloutState(NamedTuple):
    """Carry of the rollout scan (per-env state + current obs)."""

    env_state: Any
    obs: jax.Array


class TrainState(NamedTuple):
    """On-policy trainer state. Total env steps = update_step · T · E,
    computed on the host (int32-on-device would wrap within ~36 min at the
    1M steps/s target)."""

    params: Any
    opt_state: Any
    rollout: RolloutState
    key: jax.Array
    update_step: jax.Array  # number of train_step calls
    # Running episode-return accounting (per env).
    ep_return: jax.Array
    ep_length: jax.Array
    # Exponential-moving stats of completed-episode returns, for metrics.
    avg_return: jax.Array


class Unrolled(NamedTuple):
    """What a loss needs of a policy on a `[T, E]` trajectory."""

    log_prob: jax.Array  # [T, E], of `traj.action` under the given params
    entropy: jax.Array   # per decision, any shape (the loss takes its mean)
    value: jax.Array     # [T, E]
    # [T, E] of 0 / 1, or None where every step is a decision: the steps a
    # loss counts (a token env feeds prompt tokens and ignores the action).
    mask: Optional[jax.Array]
    metrics: dict        # the policy's own scalar counters, into the rows


class Policy(NamedTuple):
    """How the fused trainers act and learn, whatever the network keeps
    between steps.

    `init_carry(num_envs)` is what the policy carries through ONE rollout
    (a sequence model's cache; `()` for a feed-forward network): it lives
    inside `rollout_scan` / `evaluate` and starts fresh with each, so it is
    no part of `RolloutState` or of a checkpoint. `step(params, obs, carry)
    -> (dist, value, carry)` acts on one observation a row; `unroll(params,
    traj) -> Unrolled` re-evaluates a whole trajectory for the update;
    `bootstrap(params, obs) -> value [E]` values the observation after the
    last step. A policy that can take the part of an episode no action
    decides (`EnvSpec.prefill_len` observations: a prompt) in one pass
    offers `prefill(params, obs [P, E, ...], carry) -> (dist of the last of
    them, value [P, E], carry)`; one without it is stepped through them. A
    policy that counts something as it acts offers `rollout_metrics(carry
    after the last step) -> dict` of scalars for the rows
    (`rollout_scan(..., policy_metrics=True)`)."""

    init_carry: Callable[[int], Any]
    step: Callable[[Any, jax.Array, Any], tuple[Any, jax.Array, Any]]
    unroll: Callable[[Any, "Transition"], Unrolled]
    bootstrap: Callable[[Any, jax.Array], jax.Array]
    prefill: Optional[Callable[[Any, jax.Array, Any], tuple[Any, jax.Array, Any]]] = None
    rollout_metrics: Optional[Callable[[Any], dict]] = None


def feedforward_policy(
    apply_fn: Callable[[Any, jax.Array], tuple[Any, jax.Array]],
) -> Policy:
    """The trivial adapter for `apply_fn(params, obs) -> (dist, value)`:
    nothing carried, and `unroll` is one application to the `T * E`
    independent rows."""

    def unroll(params, traj):
        T, E = traj.reward.shape
        obs = traj.obs.reshape(T * E, *traj.obs.shape[2:])
        actions = traj.action.reshape(T * E, *traj.action.shape[2:])
        dist, values = apply_fn(params, obs)
        log_prob = dist.log_prob(actions).reshape(T, E)
        values = values.reshape(T, E)
        return Unrolled(log_prob, dist.entropy(), values, None, {})

    return Policy(
        init_carry=lambda num_envs: (),
        step=lambda params, obs, carry: (*apply_fn(params, obs), carry),
        unroll=unroll,
        bootstrap=lambda params, obs: apply_fn(params, obs)[1],
    )


def as_policy(policy_or_apply) -> Policy:
    """A `Policy` as it is; a bare `apply_fn` through `feedforward_policy`."""
    if isinstance(policy_or_apply, Policy):
        return policy_or_apply
    return feedforward_policy(policy_or_apply)


def masked_mean(x: jax.Array, mask: Optional[jax.Array]) -> jax.Array:
    """float32 mean of `x` over the steps `mask` counts (all where None)."""
    if mask is None:
        return jnp.mean(x, dtype=jnp.float32)
    return jnp.sum(x * mask, dtype=jnp.float32) / jnp.maximum(jnp.sum(mask), 1.0)


def init_rollout(env: JaxEnv, key: jax.Array, num_envs: int) -> RolloutState:
    keys = jax.random.split(key, num_envs)
    env_state, obs = jax.vmap(env.reset)(keys)
    return RolloutState(env_state=env_state, obs=obs)


def rollout_scan(
    env: JaxEnv,
    policy: Any,
    params: Any,
    rstate: RolloutState,
    key: jax.Array,
    num_steps: int,
    policy_metrics: bool = False,
) -> tuple[RolloutState, Transition]:
    """Collect `num_steps` of experience from the vmapped env batch.

    `policy` is a `Policy`, or a bare `apply_fn(params, obs) -> (dist,
    value)` (`as_policy`); actions are sampled per env with per-step keys.
    What the policy carries from step to step starts fresh here and is
    dropped at the end. Where the env hands out the first
    `spec.prefill_len` observations of an episode at once (`JaxEnv.prefill`)
    and the policy can take them in one pass (`Policy.prefill`), the rollout
    starts with that pass: `rstate` must then stand at a reset, as it does
    where an episode is exactly one rollout. Returns time-major Transition
    with arrays [T, E, ...]; with `policy_metrics` a third value, what the
    policy counted over the rollout (`Policy.rollout_metrics` of the carry
    after the last step; `{}` for a policy that counts nothing).
    """
    policy = as_policy(policy)

    def result(rstate, policy_carry, traj):
        if not policy_metrics:
            return rstate, traj
        counted = policy.rollout_metrics
        return rstate, traj, counted(policy_carry) if counted else {}

    def act(carry: RolloutState, dist, value, step_key: jax.Array):
        """Sample a row's action, step its env: (next state, the transition)."""
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

    def step_fn(scan_carry, step_key: jax.Array):
        carry, policy_carry = scan_carry
        dist, value, policy_carry = policy.step(params, carry.obs, policy_carry)
        carry, trans = act(carry, dist, value, step_key)
        return (carry, policy_carry), trans

    # One phase of the fused step's timeline (the scan and all of its
    # body): the scope is the first component of every operation's name
    # stack in a profiler trace, where benchmark/phases.py reads it.
    with jax.named_scope("rollout"):
        step_keys = jax.random.split(key, num_steps)
        policy_carry = policy.init_carry(rstate.obs.shape[0])
        can_prefill = policy.prefill is not None and env.prefill is not None
        P = env.spec.prefill_len if can_prefill else 0
        if not P:
            (rstate, policy_carry), traj = jax.lax.scan(
                step_fn, (rstate, policy_carry), step_keys)
            return result(rstate, policy_carry, traj)
        # The env gives the first P observations of every row at once (no
        # action decides them), one pass of the policy takes them, and the
        # action on the last of them is the first one sampled. The first
        # P - 1 steps of the trajectory are those observations as the env
        # would have stepped through them: the action ignored (`is_prompt`
        # masks it out of a loss; a zero and log-probability 0 stand there),
        # no reward, no end, and the pass's own values.
        with jax.named_scope("prefill"):
            env_state, obs = jax.vmap(env.prefill, out_axes=(0, 1))(rstate.env_state)
            dist, values, policy_carry = policy.prefill(params, obs, policy_carry)
        lead, trans = act(
            RolloutState(env_state=env_state, obs=obs[-1]), dist, values[-1],
            step_keys[P - 1])
        zeros = jnp.zeros((P - 1, *trans.reward.shape), jnp.float32)
        prompt = Transition(
            obs=obs[:-1],
            action=jnp.zeros((P - 1, *trans.action.shape), trans.action.dtype),
            log_prob=zeros, value=values[:-1], reward=zeros, done=zeros,
            terminated=zeros, final_obs=obs[1:],
        )
        (rstate, policy_carry), traj = jax.lax.scan(
            step_fn, (lead, policy_carry), step_keys[P:])
        traj = jax.tree.map(
            lambda a, b, c: jnp.concatenate([a, b[None], c]), prompt, trans, traj)
        return result(rstate, policy_carry, traj)


class OffPolicyTransition(NamedTuple):
    """One replay-ready transition (DDPG/TD3/SAC; BASELINE.json:9-10).

    `next_obs` is the pre-reset successor observation (the env protocol's
    `final_obs`), so the TD bootstrap r + γ·(1−terminated)·Q(next_obs, ·)
    is correct across both terminations (masked) and time-limit
    truncations (bootstrapped through). `done` is kept for episode
    accounting, not for the bootstrap.
    """

    obs: jax.Array
    action: jax.Array
    reward: jax.Array
    next_obs: jax.Array
    terminated: jax.Array
    done: jax.Array


def offpolicy_rollout(
    env: JaxEnv,
    act_fn: Callable[[Any, jax.Array, jax.Array, jax.Array], jax.Array],
    params: Any,
    rstate: RolloutState,
    key: jax.Array,
    num_steps: int,
    env_steps: jax.Array,
) -> tuple[RolloutState, jax.Array, OffPolicyTransition]:
    """Collect `num_steps` exploration steps from the vmapped env batch.

    `act_fn(params, obs, key, env_steps) -> action` owns the exploration
    policy (noise, warmup-uniform gating). `env_steps` is this device's
    running env-step count, threaded through so warmup gating stays
    correct inside the scan; it SATURATES at 2^30 so an int32 wrap can
    never flip the warmup gate back on in a long run (total step counts
    belong on the host — see TrainState's docstring). Returns time-major
    [T, E, ...] transitions.
    """

    def step_fn(carry, step_key: jax.Array):
        rs, steps = carry
        action = act_fn(params, rs.obs, step_key, steps)
        out = jax.vmap(env.step)(rs.env_state, action)
        trans = OffPolicyTransition(
            obs=rs.obs,
            action=action,
            reward=out.reward,
            next_obs=out.info["final_obs"],
            terminated=out.info["terminated"],
            done=out.done,
        )
        steps = jnp.minimum(steps + rs.obs.shape[0], jnp.int32(1 << 30))
        return (RolloutState(env_state=out.state, obs=out.obs), steps), trans

    step_keys = jax.random.split(key, num_steps)
    (rstate, env_steps), traj = jax.lax.scan(step_fn, (rstate, env_steps), step_keys)
    return rstate, env_steps, traj


def gae_targets(
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    bootstrap_value: jax.Array,
    gamma: float,
    lam: float,
    time_axis_name: Optional[str] = None,
) -> tuple[jax.Array, jax.Array]:
    """THE on-policy advantage seam (ISSUE 19): every trainer's GAE /
    λ-return target computation routes through here, so the estimator
    lowers through the Pallas kernel layer — `ops.pallas_scan.gae_auto`
    picks the fused in-VMEM reverse scan on TPU backends and the lax.scan
    reference everywhere else, keeping the whole update ONE program under
    jit on both planes. `time_axis_name` selects the sequence-parallel
    variant inside shard_map. Returns (advantages, returns).

    Runs under the `advantage` phase scope (kernel, padding and slicing),
    as `corrected_advantages`' V-trace branch does."""
    with jax.named_scope("advantage"):
        if time_axis_name is not None:
            from actor_critic_tpu.parallel.seqpar import seqpar_gae

            return seqpar_gae(
                rewards, values, dones, bootstrap_value, gamma, lam,
                axis_name=time_axis_name,
            )
        from actor_critic_tpu.ops.pallas_scan import gae_auto as _gae

        return _gae(rewards, values, dones, bootstrap_value, gamma, lam)


def corrected_advantages(
    target_log_probs: jax.Array,
    behavior_log_probs: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    bootstrap_value: jax.Array,
    gamma: float,
    lam: float,
    rho_bar: float = 1.0,
    c_bar: float = 1.0,
    correction: str = "vtrace",
    time_axis_name: Optional[str] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """THE staleness-correction machinery the off-policy-tolerant
    trainers share (IMPALA's fused learner in `algos/impala.py` and the
    async actor–learner PPO update in `algos/ppo.py` — ISSUE 6).

    `correction="vtrace"`: clipped-importance-weighted value targets and
    policy-gradient advantages (ops vtrace; ρ̄/c̄ clips, λ damping) — the
    behavior policy's log-probs were recorded at rollout time, so the
    ρ = π/μ ratios correct any parameter lag between collection and
    consumption. `correction="none"`: plain λ-return GAE under the
    learner's critic with no importance weighting (the A3C rule, which
    simply tolerates small staleness bias).

    All probability/value inputs must already be stop-gradiented by the
    caller (targets are targets). With π == μ the V-trace value targets
    equal the GAE returns exactly for any λ, and the pg advantages
    coincide at λ=1 (canonical IMPALA) — tested in
    tests/test_async_host.py. Returns (pg_advantages, value_targets,
    mean_clipped_rho).

    `time_axis_name` runs the recurrences sequence-parallel inside
    shard_map via `parallel.seqpar` (the impala sp learner's path).
    """
    from actor_critic_tpu.ops.pallas_scan import vtrace_auto as _vtrace

    if correction == "vtrace":
        # The `advantage` phase of the step's timeline: the Pallas seam
        # with its padding and slicing (`gae_targets` carries the same
        # scope for the "none" branch and the GAE trainers).
        with jax.named_scope("advantage"):
            if time_axis_name is not None:
                from actor_critic_tpu.parallel.seqpar import seqpar_vtrace

                vt = seqpar_vtrace(
                    target_log_probs, behavior_log_probs, rewards, values,
                    dones, bootstrap_value, gamma, rho_bar=rho_bar,
                    c_bar=c_bar, lam=lam, axis_name=time_axis_name,
                )
            else:
                vt = _vtrace(
                    target_log_probs, behavior_log_probs, rewards, values,
                    dones, bootstrap_value, gamma, rho_bar=rho_bar,
                    c_bar=c_bar, lam=lam,
                )
            return vt.pg_advantages, vt.vs, jnp.mean(vt.clipped_rhos)
    if correction == "none":
        pg_advantages, value_targets = gae_targets(
            rewards, values, dones, bootstrap_value, gamma, lam,
            time_axis_name=time_axis_name,
        )
        return pg_advantages, value_targets, jnp.ones(())
    raise ValueError(f"unknown correction: {correction!r}")


def anneal_fraction(
    update_step: jax.Array, anneal_iters: int
) -> Optional[jax.Array]:
    """update_step → clipped [0, 1] anneal fraction; None when annealing
    is off (anneal_iters <= 0). THE progress contract every coefficient
    schedule shares — compute it once per train step and thread it."""
    if anneal_iters <= 0:
        return None
    return jnp.clip(update_step.astype(jnp.float32) / anneal_iters, 0.0, 1.0)


def linear_anneal(
    initial: float, final, progress: Optional[jax.Array]
) -> jax.Array:
    """initial + (final − initial)·progress; the constant `initial` when
    the schedule is disabled (final is None) or progress is None."""
    if final is None or progress is None:
        return jnp.asarray(initial)
    return initial + (final - initial) * progress


def truncation_bootstrap_rewards(
    traj: Transition,
    final_values: jax.Array,
    gamma: float,
) -> jax.Array:
    """Patch rewards so truncated (not terminated) episode ends bootstrap.

    r_t ← r_t + γ·V(final_obs_t) where the episode was truncated at t.
    With this patch, `gae` can treat `done` as a hard cut (SURVEY §7.2.5:
    correct time-limit handling without branching inside the scan).
    """
    truncated = traj.done * (1.0 - traj.terminated)
    return traj.reward + gamma * final_values * truncated


# Rows a trip of `truncation_bootstrap`'s loop evaluates: small enough that
# a learnt policy's handful of truncated rows costs one short pass, large
# enough that a synchronized burst of E rows takes few trips (PERF.md, PR 27:
# 256, 512 and 1024 measured on a v5e at pixel-IMPALA shapes).
TRUNCATION_CHUNK = 512


def truncation_bootstrap(
    apply_fn: Callable[[Any, jax.Array], tuple[Any, jax.Array]],
    params: Any,
    traj: Transition,
    gamma: float,
) -> jax.Array:
    """`truncation_bootstrap_rewards` with the critic run on the truncated
    rows only: the patched rewards [T, E].

    The value of `final_obs` is used only where the time limit cut an
    episode (the formula multiplies every other row by zero), so the rows
    of `traj.final_obs` that need it are gathered `C` at a time under a
    loop whose trip count, ceil(n / C), is read from the data: no truncated
    row, no trip; every row truncated, the full pass in T·E / C pieces.
    Primal only (the loop has no reverse rule): pass `stop_gradient`-ed
    params from inside a differentiated function. No gradient reaches the
    patched rewards in any trainer.
    """
    T, E = traj.reward.shape
    N = T * E
    C = min(N, TRUNCATION_CHUNK)
    truncated = (traj.done * (1.0 - traj.terminated)).reshape(N) > 0
    # Indices of the truncated rows, ascending, compacted to the front; the
    # fill is out of range, so a chunk's rows past n are dropped by the write.
    rows = jnp.sort(jnp.where(truncated, jnp.arange(N, dtype=jnp.int32), N))
    rows = jnp.pad(rows, (0, -N % C), constant_values=N)
    trips = (jnp.sum(truncated, dtype=jnp.int32) + C - 1) // C
    flat_final = traj.final_obs.reshape(N, *traj.final_obs.shape[2:])

    def chunk(i, final_values):
        idx = jax.lax.dynamic_slice(rows, (i * C,), (C,))
        _, v = apply_fn(params, jnp.take(flat_final, idx, axis=0, mode="clip"))
        return final_values.at[idx].set(v, mode="drop")

    final_values = jax.lax.fori_loop(
        0, trips, chunk, jnp.zeros((N,), jnp.float32)
    )
    return truncation_bootstrap_rewards(
        traj, final_values.reshape(T, E), gamma
    )


def evaluate(
    env: JaxEnv,
    act_fn: Callable[[Any, jax.Array], jax.Array],
    params: Any,
    key: jax.Array,
    num_envs: int = 32,
    num_steps: int = 256,
    reset_fn: Optional[Callable] = None,
    init_carry: Optional[Callable[[int], Any]] = None,
) -> jax.Array:
    """Greedy eval: mean return of each env's FIRST episode (SURVEY §3.4).

    `act_fn(params, obs) -> action` is the deterministic policy (mode /
    mean action); with `init_carry` (a `Policy`'s) it is `act_fn(params,
    obs, carry) -> (action, carry)` and the carry is threaded through the
    episode loop. Rewards stop accumulating at the first `done`. Envs
    whose episode outlives `num_steps` are EXCLUDED from the mean (a
    partial return would understate exactly when the policy is good);
    if no env finishes within the horizon, the mean of the partial
    returns is reported instead — a lower bound, and the only number
    available. One jittable program; used by trainers' periodic eval
    and the learning tests. `reset_fn` overrides `env.reset` for
    partitioned eval fleets (the mixture's type-pinned per-type eval
    matrix, envs/mixture.py) — the episode loop itself is shared.
    """
    keys = jax.random.split(key, num_envs)
    env_state, obs = jax.vmap(reset_fn or env.reset)(keys)
    if init_carry is None:
        act = lambda params, obs, carry: (act_fn(params, obs), carry)  # noqa: E731
        policy_carry = ()
    else:
        act, policy_carry = act_fn, init_carry(num_envs)
    init = (env_state, obs, jnp.zeros(num_envs), jnp.ones(num_envs), policy_carry)

    def step(carry, _):
        env_state, obs, ret, alive, policy_carry = carry
        action, policy_carry = act(params, obs, policy_carry)
        out = jax.vmap(env.step)(env_state, action)
        ret = ret + out.reward * alive
        alive = alive * (1.0 - out.done)
        return (out.state, out.obs, ret, alive, policy_carry), None

    (_, _, returns, alive, _), _ = jax.lax.scan(step, init, None, length=num_steps)
    finished = 1.0 - alive
    n_finished = jnp.sum(finished)
    finished_mean = jnp.sum(returns * finished) / jnp.maximum(n_finished, 1.0)
    return jnp.where(n_finished > 0, finished_mean, jnp.mean(returns))


def default_eval_steps(env: JaxEnv) -> int:
    """Eval horizon: the env's episode time-limit plus slack (so a good
    policy's episodes always FINISH within the eval and are counted), or
    512 when the env doesn't declare one."""
    h = env.spec.episode_horizon
    return h + 8 if h > 0 else 512


def make_greedy_eval(
    env: JaxEnv,
    act: Callable[[Any, jax.Array], jax.Array],
    params_of: Callable[[Any], Any],
    init_carry: Optional[Callable[[int], Any]] = None,
):
    """THE eval-program factory shared by every algo's `make_eval_fn`:
    `act(params, obs) → action` is the algo's greedy policy, `params_of`
    extracts the acting params from its train state. Returns
    `eval_fn(state, key, num_envs=32, num_steps=default_eval_steps(env))`
    (jit with static_argnums=(2, 3)). `init_carry` as in `evaluate`."""
    default_steps = default_eval_steps(env)

    def eval_fn(state, key, num_envs: int = 32, num_steps: int = default_steps):
        return evaluate(env, act, params_of(state), key, num_envs, num_steps,
                        init_carry=init_carry)

    return eval_fn


def make_mode_eval(env: JaxEnv, net):
    """`make_greedy_eval` specialization for actor-critic policies: a
    `Policy`, or a net whose `apply(params, obs) → (dist, value)`. Greedy
    action = dist.mode(), the policy's carry threaded through the episode;
    params live at `state.params` (a2c/ppo/impala)."""
    policy = as_policy(getattr(net, "apply", net))

    def act(params, obs, carry):
        dist, _, carry = policy.step(params, obs, carry)
        return dist.mode(), carry

    return make_greedy_eval(env, act, lambda s: s.params, policy.init_carry)


def episode_metrics_update(
    ep_return: jax.Array,
    ep_length: jax.Array,
    avg_return: jax.Array,
    traj: Transition,
    decay: float = 0.99,
) -> tuple[jax.Array, jax.Array, jax.Array, dict[str, jax.Array]]:
    """Fold a [T, E] trajectory into running per-env episode accounting.

    Returns updated (ep_return, ep_length, avg_return EMA, metrics).
    Runs inside jit; O(T·E) elementwise.
    """

    def fold(carry, x):
        ep_ret, ep_len, avg, n_done, sum_done, len_done = carry
        reward, done = x
        ep_ret = ep_ret + reward
        ep_len = ep_len + 1.0
        n_done = n_done + jnp.sum(done)
        sum_done = sum_done + jnp.sum(ep_ret * done)
        len_done = len_done + jnp.sum(ep_len * done)
        # EMA over completed episodes (batch-mean of finished returns).
        batch_done = jnp.sum(done)
        batch_mean = jnp.where(
            batch_done > 0, jnp.sum(ep_ret * done) / jnp.maximum(batch_done, 1.0), avg
        )
        avg = jnp.where(batch_done > 0, decay * avg + (1 - decay) * batch_mean, avg)
        ep_ret = ep_ret * (1.0 - done)
        ep_len = ep_len * (1.0 - done)
        return (ep_ret, ep_len, avg, n_done, sum_done, len_done), None

    (ep_return, ep_length, avg_return, n_done, sum_done, len_done), _ = jax.lax.scan(
        fold,
        (ep_return, ep_length, avg_return,
         jnp.zeros(()), jnp.zeros(()), jnp.zeros(())),
        (traj.reward, traj.done),
    )
    # Raw count and sums so dp callers can psum them and divide AFTER the
    # reduction (an unweighted pmean of per-device means would bias toward
    # devices with zero finished episodes).
    metrics = {
        "episodes_finished": n_done,
        "finished_return_sum": sum_done,
        "finished_length_sum": len_done,
        "avg_return_ema": avg_return,
    }
    return ep_return, ep_length, avg_return, metrics
