"""Distributed tests on the fake 8-device CPU mesh (SURVEY.md §4):
psum-grad equivalence with single-device, replication invariants, and a
dp learning smoke test."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_tpu.algos import a2c
from actor_critic_tpu.algos.common import Transition
from actor_critic_tpu.envs import make_two_state_mdp
from actor_critic_tpu.parallel import (
    DP_AXIS,
    distribute_state,
    make_dp_train_step,
    make_mesh,
    train_state_specs,
)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 (fake) devices"
)


def _mesh():
    return make_mesh()


def test_mesh_shape():
    mesh = _mesh()
    assert mesh.shape[DP_AXIS] == 8


def test_sharded_grad_equals_full_batch_grad():
    """pmean of per-shard grads == grad on the full batch (the core
    MirroredStrategy/NCCL-equivalence property, SURVEY §2.4)."""
    from jax.sharding import PartitionSpec as P

    env = make_two_state_mdp()
    cfg = a2c.A2CConfig(num_envs=8, rollout_steps=4, hidden=(16,))
    net = a2c.make_network(env, cfg)
    params = net.init(jax.random.key(0), jnp.zeros((1, 2)))

    T, E = 4, 16
    rng = np.random.RandomState(0)
    traj = Transition(
        obs=jnp.asarray(rng.rand(T, E, 2), jnp.float32),
        action=jnp.asarray(rng.randint(0, 2, (T, E))),
        log_prob=jnp.zeros((T, E)),
        value=jnp.zeros((T, E)),
        reward=jnp.asarray(rng.rand(T, E), jnp.float32),
        done=jnp.zeros((T, E)),
        terminated=jnp.zeros((T, E)),
        final_obs=jnp.asarray(rng.rand(T, E, 2), jnp.float32),
    )
    adv = jnp.asarray(rng.randn(T, E), jnp.float32)
    ret = jnp.asarray(rng.randn(T, E), jnp.float32)

    def loss_grads(params, traj, adv, ret, axis_name=None):
        g = jax.grad(
            lambda p: a2c.a2c_loss(p, net.apply, traj, adv, ret, cfg)[0]
        )(params)
        if axis_name is not None:
            g = jax.tree.map(lambda x: jax.lax.pmean(x, axis_name), g)
        return g

    g_full = loss_grads(params, traj, adv, ret)

    mesh = _mesh()
    sharded = jax.shard_map(
        lambda p, t, a, r: loss_grads(p, t, a, r, DP_AXIS),
        mesh=mesh,
        in_specs=(P(), P(None, DP_AXIS), P(None, DP_AXIS), P(None, DP_AXIS)),
        out_specs=P(),
        check_vma=False,
    )
    g_dp = sharded(params, traj, adv, ret)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g_full,
        g_dp,
    )


def test_dp_train_step_runs_and_replicates():
    env = make_two_state_mdp()
    cfg = a2c.A2CConfig(num_envs=32, rollout_steps=4, hidden=(16,))
    mesh = _mesh()
    state = a2c.init_state(env, cfg, jax.random.key(0))
    state = distribute_state(state, mesh)
    step = make_dp_train_step(a2c.make_train_step(env, cfg, axis_name=DP_AXIS), mesh)

    state, metrics = step(state)
    jax.block_until_ready(state)  # see note in test_dp_learning_two_state
    state, metrics = step(state)
    jax.block_until_ready(state)

    # params must be bitwise identical across devices (replicated after pmean)
    leaf = jax.tree.leaves(state.params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state.update_step) == 2


def test_dp_learning_two_state():
    """8-device dp training still reaches the known optimum."""
    env = make_two_state_mdp()
    cfg = a2c.A2CConfig(
        num_envs=32, rollout_steps=8, lr=3e-3, gamma=0.9, hidden=(32,),
        entropy_coef=0.001,
    )
    mesh = _mesh()
    state = a2c.init_state(env, cfg, jax.random.key(1))
    state = distribute_state(state, mesh)
    step = make_dp_train_step(a2c.make_train_step(env, cfg, axis_name=DP_AXIS), mesh)
    for _ in range(200):
        state, metrics = step(state)
        # XLA CPU's InProcessCommunicator deadlocks (AwaitAndLogIfStuck →
        # SIGABRT) when in-flight executions of collective programs overlap
        # and >1 collective executable exists in the process — verified
        # in-session on the fake 8-device mesh. Serialize steps in tests;
        # real TPU execution does not have this constraint.
        jax.block_until_ready(state)
    net = a2c.make_network(env, cfg)
    dist, v = net.apply(state.params, jnp.eye(2))
    p1 = jax.nn.softmax(dist.logits)[:, 1]
    assert float(p1.min()) > 0.9, f"dp training failed to learn: P(a=1)={p1}"


def test_distribute_state_rejects_indivisible():
    env = make_two_state_mdp()
    cfg = a2c.A2CConfig(num_envs=12, rollout_steps=4, hidden=(16,))
    state = a2c.init_state(env, cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="not divisible"):
        distribute_state(state, _mesh())


def test_dp_offpolicy_train_step_runs_shards_replay():
    """DDPG/TD3 fused trainer under dp: replay sharded over devices,
    params/targets replicated after pmean, per-device sampling streams
    (BASELINE.json:5 'replay buffer lives in HBM as a sharded
    DeviceArray')."""
    from jax.sharding import PartitionSpec as P

    from actor_critic_tpu.algos import ddpg
    from actor_critic_tpu.envs import make_point_mass
    from actor_critic_tpu.parallel import offpolicy_state_specs

    env = make_point_mass()
    cfg = ddpg.td3_config(
        num_envs=16, steps_per_iter=4, updates_per_iter=2,
        buffer_capacity=512, batch_size=8, warmup_steps=0, hidden=(16,),
    )
    mesh = _mesh()
    state = ddpg.init_state(env, cfg, jax.random.key(0))
    state = distribute_state(state, mesh, offpolicy_state_specs())

    # The ring's storage really is dp-sharded: each device owns 512/8 rows.
    obs_leaf = state.learner.replay.storage.obs
    assert obs_leaf.sharding.spec == P(DP_AXIS)
    assert obs_leaf.addressable_shards[0].data.shape[0] == 512 // 8

    step = make_dp_train_step(
        ddpg.make_train_step(env, cfg, axis_name=DP_AXIS),
        mesh,
        offpolicy_state_specs(),
    )
    state, metrics = step(state)
    jax.block_until_ready(state)  # see note in test_dp_learning_two_state
    state, metrics = step(state)
    jax.block_until_ready(state)

    # Params and targets bitwise identical across devices (pmean-ed grads).
    for tree in (
        state.learner.actor_params, state.learner.critic_params,
        state.learner.target_actor, state.learner.target_critic,
    ):
        leaf = jax.tree.leaves(tree)[0]
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)
    # Each device's sub-ring received ITS OWN env shard's transitions
    # (different envs → different obs), so replay shards must differ.
    # (Re-read from the post-step state: the step donates its input, so
    # the pre-step obs_leaf buffer no longer exists.)
    shard0, shard1 = (
        np.asarray(s.data)
        for s in state.learner.replay.storage.obs.addressable_shards[:2]
    )
    assert not np.array_equal(shard0, shard1)
    # Cursor scalars evolved identically (replicated): 2 iters × 4 steps
    # × 2 local envs = 16 local inserts.
    assert int(state.learner.replay.size) == 16
    assert np.isfinite(float(metrics["critic_loss"]))
    assert int(state.learner.update_count) == 4


def test_dp_offpolicy_quantized_replay_shards_and_syncs_stats():
    """ISSUE 8: the QUANTIZED ring under dp on the 8-device CPU mesh —
    int8 storage sharded over devices like the fp32 ring, quantizer
    running stats replicated AND bit-identical across devices (add_batch
    pmean/pmax-syncs the batch moments over the dp axis), train step
    runs with finite losses."""
    from jax.sharding import PartitionSpec as P

    from actor_critic_tpu.algos import ddpg
    from actor_critic_tpu.envs import make_point_mass
    from actor_critic_tpu.parallel import offpolicy_state_specs

    env = make_point_mass()
    cfg = ddpg.td3_config(
        num_envs=16, steps_per_iter=4, updates_per_iter=2,
        buffer_capacity=512, batch_size=8, warmup_steps=0, hidden=(16,),
        replay_dtype="mixed",
    )
    mesh = _mesh()
    state = ddpg.init_state(env, cfg, jax.random.key(0))
    state = distribute_state(state, mesh, offpolicy_state_specs())

    obs_leaf = state.learner.replay.storage.obs
    assert obs_leaf.dtype == jnp.int8  # quantized storage, dp-sharded
    assert obs_leaf.sharding.spec == P(DP_AXIS)
    assert obs_leaf.addressable_shards[0].data.shape[0] == 512 // 8

    step = make_dp_train_step(
        ddpg.make_train_step(env, cfg, axis_name=DP_AXIS),
        mesh,
        offpolicy_state_specs(),
    )
    state, metrics = step(state)
    jax.block_until_ready(state)
    state, metrics = step(state)
    jax.block_until_ready(state)

    # Quantizer stats: live (count > 0, scale grew) and IDENTICAL on
    # every device — each device folds different env transitions, so
    # only the cross-device moment sync keeps the replicated spec true.
    stats = state.learner.replay.quant.obs
    assert int(stats.count) > 0
    for leaf in (stats.mean, stats.scale):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)
    # The sub-rings themselves still differ (per-device env shards).
    shard0, shard1 = (
        np.asarray(s.data)
        for s in state.learner.replay.storage.obs.addressable_shards[:2]
    )
    assert not np.array_equal(shard0, shard1)
    assert np.isfinite(float(metrics["critic_loss"]))


def test_dp_sac_train_step_runs_and_replicates():
    """SAC fused trainer under dp: same layout as DDPG plus replicated
    log-α; two steps run with finite losses and replicated params."""
    from actor_critic_tpu.algos import sac
    from actor_critic_tpu.envs import make_point_mass
    from actor_critic_tpu.parallel import sac_state_specs

    env = make_point_mass()
    cfg = sac.SACConfig(
        num_envs=16, steps_per_iter=4, updates_per_iter=2,
        buffer_capacity=512, batch_size=8, warmup_steps=0, hidden=(16,),
    )
    mesh = _mesh()
    state = sac.init_state(env, cfg, jax.random.key(0))
    state = distribute_state(state, mesh, sac_state_specs())
    step = make_dp_train_step(
        sac.make_train_step(env, cfg, axis_name=DP_AXIS),
        mesh,
        sac_state_specs(),
    )
    state, metrics = step(state)
    jax.block_until_ready(state)  # see note in test_dp_learning_two_state
    state, metrics = step(state)
    jax.block_until_ready(state)

    for tree in (state.learner.actor_params, state.learner.critic_params):
        leaf = jax.tree.leaves(tree)[0]
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)
    # log_alpha is a replicated scalar updated by pmean-ed gradients.
    ashards = [
        np.asarray(s.data) for s in state.learner.log_alpha.addressable_shards
    ]
    for s in ashards[1:]:
        np.testing.assert_array_equal(ashards[0], s)
    assert np.isfinite(float(metrics["critic_loss"]))
    assert np.isfinite(float(metrics["alpha"]))


def test_dp_impala_train_step_runs_and_replicates():
    """IMPALA's state (with stale actor params) shards and stays replicated
    across the dp mesh; staleness refresh happens identically per device."""
    from actor_critic_tpu.algos import impala
    from actor_critic_tpu.parallel import impala_state_specs

    env = make_two_state_mdp()
    cfg = impala.ImpalaConfig(
        num_envs=16, rollout_steps=4, hidden=(16,), actor_refresh_every=2
    )
    mesh = _mesh()
    state = impala.init_state(env, cfg, jax.random.key(0))
    state = distribute_state(state, mesh, impala_state_specs())
    step = make_dp_train_step(
        impala.make_train_step(env, cfg, axis_name=DP_AXIS),
        mesh,
        impala_state_specs(),
    )
    state, metrics = step(state)
    jax.block_until_ready(state)  # see note in test_dp_learning_two_state
    state, metrics = step(state)
    jax.block_until_ready(state)

    for tree in (state.params, state.actor_params):
        leaf = jax.tree.leaves(tree)[0]
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)
    # Step 2 is a refresh boundary ⇒ actor == learner params.
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        state.params,
        state.actor_params,
    )
    assert np.isfinite(float(metrics["loss"]))
