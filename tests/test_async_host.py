"""Async actor–learner decoupling (ISSUE 6): lockstep equivalence at
queue depth 1, straggler immunity, drop-oldest back-pressure through the
driver, V-trace correction semantics, the heterogeneous straggler-shard
env plumbing, and the steady-state compile-count regression contract."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from actor_critic_tpu.algos import ppo
from actor_critic_tpu.algos.common import corrected_advantages
from actor_critic_tpu.telemetry import profiler
from actor_critic_tpu.utils import compile_cache

gym = pytest.importorskip("gymnasium")

from actor_critic_tpu.envs.host_pool import HostEnvPool  # noqa: E402
from actor_critic_tpu.envs.sleep_pad import (  # noqa: E402
    QUALIFIED_CARTPOLE_ID,
    QUALIFIED_ENV_ID,
)


def _tree_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ------------------------------------------------------- lockstep equivalence

@pytest.mark.parametrize(
    "data_plane",
    ["host", "device"],
    ids=["host_plane", "device_plane"],
)
@pytest.mark.parametrize(
    "epochs,minibatches",
    [(2, 2), (1, 1)],
    ids=["ppo_shaped", "a2c_shaped"],  # 1 epoch x 1 full-batch mb = A2C-style
)
def test_async_depth1_is_bitwise_lockstep(epochs, minibatches, data_plane):
    """Async mode with one actor, queue depth 1, updates-per-block 1 and
    correction='none' must be bit-for-bit the current train_host
    pipeline (params AND optimizer state) — the refactor is pure
    decoupling, not a silent algorithm change. The device data plane
    (ISSUE 13, fp32 codec: the block round-trips the HBM ring and is
    gathered+decoded in-jit) must preserve the same bits — relocation,
    not a new algorithm."""
    cfg = ppo.PPOConfig(
        num_envs=4, rollout_steps=8, epochs=epochs,
        num_minibatches=minibatches, hidden=(16,),
    )
    pool = HostEnvPool("CartPole-v1", num_envs=4, seed=0)
    try:
        p_lock, o_lock, _ = ppo.train_host(
            pool, cfg, num_iterations=3, seed=0, log_every=0
        )
    finally:
        pool.close()
    pool = HostEnvPool("CartPole-v1", num_envs=4, seed=0)
    try:
        p_async, o_async, hist = ppo.train_host_async(
            [pool], cfg, 3, seed=0, log_every=0, updates_per_block=1,
            queue_depth=1, correction="none", strict_lockstep=True,
            data_plane=data_plane, plane_codec="fp32",
        )
    finally:
        pool.close()
    assert _tree_equal(p_lock, p_async)
    assert _tree_equal(o_lock, o_async)


# ----------------------------------------------------------- straggler / drops

def test_straggler_actor_does_not_stall_learner():
    """One sleep-padded actor must slow only its own contribution: the
    learner's N updates complete far inside the lockstep bound (which
    pays the straggler's pace on every block)."""
    cfg = ppo.PPOConfig(
        num_envs=2, rollout_steps=4, epochs=1, num_minibatches=1,
        hidden=(8,),
    )
    iters, pad = 8, 0.3
    # Lockstep lower bound: every block waits for the padded envs —
    # K steps x E envs x pad seconds each (in-process SyncVectorEnv
    # steps envs serially).
    lockstep_bound = iters * cfg.rollout_steps * 2 * pad  # 19.2 s
    pools = [
        HostEnvPool(
            QUALIFIED_ENV_ID, 2, seed=0, normalize_obs=False,
            normalize_reward=False, env_kwargs={"sleep_s": pad},
        ),
        HostEnvPool(
            QUALIFIED_ENV_ID, 2, seed=100003, normalize_obs=False,
            normalize_reward=False, env_kwargs={"sleep_s": 0.0},
        ),
    ]
    try:
        t0 = time.perf_counter()
        _, _, hist = ppo.train_host_async(
            pools, cfg, iters, seed=0, log_every=1, queue_depth=2,
            max_staleness=None, correction="vtrace",
        )
        wall = time.perf_counter() - t0
    finally:
        for p in pools:
            p.close()
    assert len(hist) == iters
    # Generous compile slack, still far under the lockstep bound.
    assert wall < lockstep_bound * 0.6, (
        f"learner stalled: wall {wall:.1f}s vs lockstep bound "
        f"{lockstep_bound:.1f}s"
    )
    last = hist[-1][1]
    assert np.isfinite(last["loss"]) and np.isfinite(last["mean_rho"])
    # Fairness signal: most consumed blocks came from the FAST actor
    # (id 1) — the straggler contributes, it just can't dominate.
    from_fast = sum(1 for _, m in hist if m["block_actor"] == 1)
    assert from_fast >= iters // 2, [m["block_actor"] for _, m in hist]


def test_actor_death_surfaces_while_queue_is_fed():
    """A mid-run actor crash must raise even though the SURVIVING actor
    keeps the queue non-empty — a silently halved fleet is not a
    healthy run."""
    cfg = ppo.PPOConfig(
        num_envs=2, rollout_steps=4, epochs=1, num_minibatches=1,
        hidden=(8,),
    )
    pools = [
        # Actor 0's envs blow up inside the first collection block.
        HostEnvPool(
            QUALIFIED_ENV_ID, 2, seed=0, normalize_obs=False,
            normalize_reward=False, env_kwargs={"crash_at_step": 3},
        ),
        HostEnvPool(
            QUALIFIED_ENV_ID, 2, seed=100003, normalize_obs=False,
            normalize_reward=False,
        ),
    ]
    try:
        with pytest.raises(RuntimeError, match="actor 0 died"):
            ppo.train_host_async(
                pools, cfg, 200, seed=0, log_every=0, queue_depth=2,
                correction="vtrace",
            )
    finally:
        for p in pools:
            p.close()


def test_backpressure_drops_oldest_through_driver():
    """A producer that outruns the learner must never block: the queue
    recycles oldest blocks and the drop counters surface in the log
    rows."""
    cfg = ppo.PPOConfig(
        num_envs=2, rollout_steps=4, epochs=2, num_minibatches=2,
        hidden=(16,),
    )
    pool = HostEnvPool("CartPole-v1", 2, seed=0)
    try:
        _, _, hist = ppo.train_host_async(
            [pool], cfg, 6, seed=0, log_every=1, updates_per_block=4,
            queue_depth=1, max_staleness=None, correction="vtrace",
        )
    finally:
        pool.close()
    last = hist[-1][1]
    assert last["queue_drops_full"] > 0  # actor ran ahead, nothing blocked
    assert last["env_steps"] >= last["consumed_env_steps"]


# ------------------------------------------------------- V-trace correction

def test_corrected_advantages_on_policy_reduction():
    """With pi == mu the V-trace value targets equal the GAE returns for
    any lambda, and the pg advantages coincide at lambda=1 (canonical
    IMPALA) — async correction degrades gracefully to on-policy."""
    rng = np.random.default_rng(0)
    T, E = 12, 6
    lp = jnp.asarray(rng.normal(size=(T, E)) * 0.3, jnp.float32)
    rewards = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    values = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    dones = jnp.asarray(rng.random((T, E)) < 0.1, jnp.float32)
    boot = jnp.asarray(rng.normal(size=(E,)), jnp.float32)

    for lam in (1.0, 0.9):
        adv_v, ret_v, rho = corrected_advantages(
            lp, lp, rewards, values, dones, boot, 0.99, lam,
            correction="vtrace",
        )
        adv_g, ret_g, _ = corrected_advantages(
            lp, lp, rewards, values, dones, boot, 0.99, lam,
            correction="none",
        )
        np.testing.assert_allclose(
            np.asarray(ret_v), np.asarray(ret_g), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(float(rho), 1.0, rtol=1e-6)
        if lam == 1.0:
            np.testing.assert_allclose(
                np.asarray(adv_v), np.asarray(adv_g), rtol=1e-4, atol=1e-5
            )


def test_vtrace_correction_recovers_on_policy_return_under_staleness():
    """Forced staleness: trajectories SAMPLED under a behavior policy,
    corrected toward a different target policy. With wide clips the
    V-trace value estimate is per-decision importance sampling, so its
    mean must match the target policy's analytic return within sampling
    tolerance; with the canonical rho_bar=c_bar=1 clips (and a zero
    value baseline) the estimator's expectation is also available in
    closed form — both ends of the correction are checked against
    analytic ground truth."""
    rng = np.random.default_rng(1)
    T, E, gamma = 8, 8192, 0.9
    p_b, p_t = 0.5, 0.8  # behavior samples 50/50; target prefers a=1
    actions = (rng.random((T, E)) < p_b).astype(np.float32)
    behavior_lp = np.where(actions == 1.0, np.log(p_b), np.log(1 - p_b))
    target_lp = np.where(actions == 1.0, np.log(p_t), np.log(1 - p_t))
    rewards = actions  # r_t = a_t
    zeros = np.zeros((T, E), np.float32)

    def estimate(rho_bar, c_bar):
        _, vs, _ = corrected_advantages(
            jnp.asarray(target_lp, jnp.float32),
            jnp.asarray(behavior_lp, jnp.float32),
            jnp.asarray(rewards), jnp.asarray(zeros), jnp.asarray(zeros),
            jnp.zeros((E,), jnp.float32), gamma, 1.0,
            rho_bar=rho_bar, c_bar=c_bar, correction="vtrace",
        )
        return float(np.asarray(vs)[0].mean())

    horizon = (1 - gamma**T) / (1 - gamma)
    on_policy = p_t * horizon      # analytic E_pi[G] = 4.556
    unclipped = estimate(1e9, 1e9)
    assert abs(unclipped - on_policy) / on_policy < 0.05, (
        unclipped, on_policy
    )
    # rho_bar=c_bar=1 on a zero value baseline: a=1 ratios (1.6) clip to
    # 1, a=0 ratios stay 0.4, so E[min(rho,1)] = 0.7 per prefix step and
    # E[min(rho_t,1) r_t] = 0.5 — term t is gamma^t * 0.7^t * 0.5.
    clipped_expect = 0.5 * sum((gamma * 0.7) ** t for t in range(T))
    clipped = estimate(1.0, 1.0)
    assert abs(clipped - clipped_expect) / clipped_expect < 0.05, (
        clipped, clipped_expect
    )
    assert clipped < unclipped  # the clip bounds variance by shedding mass


# ------------------------------------------------------- testbed envs

def test_worker_stats_account_each_shard():
    pool = HostEnvPool(
        QUALIFIED_ENV_ID, 4, seed=0, workers=2,
        normalize_obs=False, normalize_reward=False,
        env_kwargs={"sleep_s": 0.01},
    )
    try:
        pool.reset()
        acts = np.zeros(4, np.int64)
        for _ in range(3):
            pool.step(acts)
        stats = pool.worker_stats()
        assert [s["worker"] for s in stats] == [0, 1]
        assert [s["envs"] for s in stats] == [2, 2]
        for s in stats:
            assert s["env_steps"] == 2 * 3
            assert s["busy_s"] > 0.01 * 2 * 3 * 0.5
    finally:
        pool.close()


def test_sleep_pad_cartpole_is_real_cartpole():
    env = gym.make(QUALIFIED_CARTPOLE_ID, sleep_s=0.0)
    obs, _ = env.reset(seed=0)
    assert obs.shape == (4,)
    ref = gym.make("CartPole-v1")
    ref_obs, _ = ref.reset(seed=0)
    np.testing.assert_array_equal(obs, ref_obs)
    env.close()
    ref.close()


# ------------------------------------------- off-policy actor services

def test_offpolicy_async_ddpg_trains_and_accounts_steps():
    """ISSUE 9 satellite: --async-actors is no longer PPO-only — the
    DDPG/TD3 host loop drives collection through ActorService threads
    and the learner ingests queued blocks into the replay ring (replay
    absorbs behavior staleness; no correction knob)."""
    from actor_critic_tpu.algos import ddpg

    cfg = ddpg.DDPGConfig(
        num_envs=2, steps_per_iter=4, updates_per_iter=1,
        buffer_capacity=256, batch_size=8, warmup_steps=16, hidden=(16,),
    )
    pools = [
        HostEnvPool(
            "Pendulum-v1", 1, seed=0,
            normalize_obs=False, normalize_reward=False,
        ),
        HostEnvPool(
            "Pendulum-v1", 1, seed=100003,
            normalize_obs=False, normalize_reward=False,
        ),
    ]
    try:
        learner, hist = ddpg.train_host_async(
            pools, cfg, 12, seed=0, log_every=1,
            eval_every=6, eval_steps=50,
        )
    finally:
        for p in pools:
            p.close()
    rows = dict(hist)
    assert sorted(rows) == list(range(1, 13))
    last = rows[12]
    assert np.isfinite(last["critic_loss"]) and np.isfinite(last["q_mean"])
    # The fleet collected at least what the learner consumed, and the
    # ring really ingested the consumed blocks.
    assert last["env_steps"] >= last["consumed_env_steps"]
    assert int(learner.replay.size) > 0
    assert "eval_return" in rows[6] and np.isfinite(rows[6]["eval_return"])


def test_offpolicy_async_sac_smoke():
    from actor_critic_tpu.algos import sac

    cfg = sac.SACConfig(
        num_envs=1, steps_per_iter=4, updates_per_iter=1,
        buffer_capacity=128, batch_size=8, warmup_steps=8, hidden=(16,),
    )
    pool = HostEnvPool(
        "Pendulum-v1", 1, seed=0,
        normalize_obs=False, normalize_reward=False,
    )
    try:
        learner, hist = sac.train_host_async(
            [pool], cfg, 6, seed=0, log_every=1,
        )
    finally:
        pool.close()
    assert len(hist) == 6
    assert np.isfinite(hist[-1][1]["critic_loss"])
    assert int(learner.replay.size) > 0


# --------------------------------------------- compile-count regression

def test_async_learner_steady_state_zero_recompiles(tmp_path):
    """ISSUE 6 acceptance: the async learner's corrected-update program
    is AOT-warmed (registry planner), the loop's first dispatch hits the
    persistent cache, and steady state compiles nothing — blocks are the
    PR 4 fixed-shape buckets, so zero new XLA programs."""
    if not profiler.ensure_compile_introspection():
        pytest.skip("jax compile funnel unavailable in this jax version")
    cfg = ppo.PPOConfig(
        num_envs=4, rollout_steps=8, epochs=1, num_minibatches=2,
        hidden=(16,),
    )
    pools = [
        HostEnvPool("CartPole-v1", 2, seed=0),
        HostEnvPool("CartPole-v1", 2, seed=100003),
    ]
    try:
        with compile_cache.temporary_cache(tmp_path / "cc"):
            ctx = compile_cache.WarmupContext(
                algo="ppo", fused=False, spec=pools[0].spec, cfg=cfg,
                eval_every=0, overlap=True, async_actors=2,
                async_correction="vtrace",
            )
            plan = compile_cache.plan_warmup(ctx)
            # Acting/eval mirror on the host; the corrected update is
            # the ONLY device program an async run dispatches.
            assert [n for n, _ in plan] == ["ppo.make_async_update_step"]
            n0 = profiler.compile_event_count()
            runner = compile_cache.WarmupRunner(plan).start()
            assert runner.wait(300) and "error" not in runner.results[0], (
                runner.results
            )

            counts = {}

            def log_fn(it, m):
                counts[it] = profiler.compile_event_count()

            ppo.train_host_async(
                pools, cfg, 4, seed=0, log_every=1, log_fn=log_fn,
                correction="vtrace",
            )
    finally:
        for p in pools:
            p.close()

    from conftest import new_compile_records

    records = new_compile_records(n0)
    update_evs = [r for r in records if r["name"] == "jit_async_update"]
    real = [r for r in update_evs if not r.get("cache_hit")]
    assert len(real) == 1, update_evs  # warmup's one true compile
    assert any(r.get("cache_hit") for r in update_evs), update_evs
    # Steady state: iterations past the second compile NOTHING.
    assert counts[4] == counts[2], records


def test_device_plane_steady_state_zero_recompiles(tmp_path):
    """ISSUE 13 acceptance: the device data plane's BOTH new jitted
    programs — the donated ring enqueue and the gather+decode+update —
    are AOT-warmed (registry planners), and steady state compiles
    nothing: blocks are fixed-shape ring slots, the slot index is a
    traced scalar, and the calibrating quant re-uploads are
    shape-stable."""
    if not profiler.ensure_compile_introspection():
        pytest.skip("jax compile funnel unavailable in this jax version")
    cfg = ppo.PPOConfig(
        num_envs=4, rollout_steps=8, epochs=1, num_minibatches=2,
        hidden=(16,),
    )
    pools = [
        HostEnvPool("CartPole-v1", 2, seed=0),
        HostEnvPool("CartPole-v1", 2, seed=100003),
    ]
    try:
        with compile_cache.temporary_cache(tmp_path / "cc"):
            ctx = compile_cache.WarmupContext(
                algo="ppo", fused=False, spec=pools[0].spec, cfg=cfg,
                eval_every=0, overlap=True, async_actors=2,
                async_correction="vtrace", data_plane="device",
                plane_codec="int8", queue_depth=4,
            )
            plan = compile_cache.plan_warmup(ctx)
            # The device plane's two programs — and NOT the host
            # plane's argument-fed update.
            assert [n for n, _ in plan] == [
                "ppo.make_device_update_step", "ring.make_enqueue",
            ]
            n0 = profiler.compile_event_count()
            runner = compile_cache.WarmupRunner(plan).start()
            assert runner.wait(300), runner.results
            assert not any("error" in r for r in runner.results), (
                runner.results
            )

            counts = {}

            def log_fn(it, m):
                counts[it] = profiler.compile_event_count()

            ppo.train_host_async(
                pools, cfg, 4, seed=0, log_every=1, log_fn=log_fn,
                correction="vtrace", data_plane="device",
                plane_codec="int8", queue_depth=4,
            )
    finally:
        for p in pools:
            p.close()

    from conftest import new_compile_records

    records = new_compile_records(n0)
    for name in ("jit_device_update", "jit_enqueue"):
        evs = [r for r in records if r["name"] == name]
        real = [r for r in evs if not r.get("cache_hit")]
        assert len(real) <= 1, (name, evs)  # at most warmup's compile
    # Steady state: iterations past the second compile NOTHING.
    assert counts[4] == counts[2], records
