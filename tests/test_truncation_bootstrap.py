"""The truncation bootstrap on the truncated rows only (ISSUE 27).

`common.truncation_bootstrap` gathers the truncated rows of `traj.final_obs`
a chunk at a time under a loop whose trip count is read from the data. The
reference kept here, not in the program, is the pass it replaced: `apply_fn`
over all T·E rows, then `truncation_bootstrap_rewards`.

(a) the seam: patched rewards equal the full pass's within 1e-6 for n = 0
    (and the loop body is not entered), n > C, n = T·E, truncated beside
    terminated rows, n at and one past a multiple of C, a vector env; a loop
    that stops one chunk short fails the same comparison;
(b) the step: one fused IMPALA / A2C / PPO step built on the new function
    gives the loss, gradients, parameters and metrics of the step built on
    the full pass, and `jax.grad` of `impala_loss` traces, time-sharded too.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_tpu.algos import a2c, common, impala, ppo
from actor_critic_tpu.envs import make_two_state_mdp
from actor_critic_tpu.envs.pong import make_pong

GAMMA = 0.99
T, E = 20, 8


def full_pass(apply_fn, params, traj, gamma):
    """The pass the program ran before: the critic over every row."""
    t, e = traj.reward.shape
    flat = traj.final_obs.reshape(t * e, *traj.final_obs.shape[2:])
    _, final_values = apply_fn(params, flat)
    return common.truncation_bootstrap_rewards(
        traj, final_values.reshape(t, e), gamma)


def _rollout(env, mod, cfg, seed=0, steps=T):
    net = mod.make_network(env, cfg)
    state = mod.init_state(env, cfg, jax.random.key(seed))
    rollout = jax.jit(lambda p, r, k: common.rollout_scan(
        env, net.apply, p, r, k, steps))
    rstate, traj = rollout(state.params, state.rollout, jax.random.key(seed + 1))
    return net, state, rstate, traj


def _planted(traj, n, seed=0):
    """`traj` with exactly `n` truncated rows at seeded places, and a
    terminated row beside a truncated one in every step that has room."""
    t, e = traj.reward.shape
    rng = np.random.default_rng(seed)
    done = np.zeros(t * e, np.float32)
    terminated = np.zeros(t * e, np.float32)
    cut = rng.choice(t * e, size=n, replace=False)
    done[cut] = 1.0
    for i in cut:  # the next env of the same step terminates, if it is free
        j = (i // e) * e + (i + 1) % e
        if done[j] == 0.0:
            done[j] = terminated[j] = 1.0
    return traj._replace(done=jnp.asarray(done.reshape(t, e)),
                         terminated=jnp.asarray(terminated.reshape(t, e)))


def _pong_case(max_steps, planted=None):
    env = make_pong(size=36, max_steps=max_steps)
    cfg = impala.ImpalaConfig(num_envs=E, rollout_steps=T)
    net, state, _, traj = _rollout(env, impala, cfg)
    if planted is not None:
        traj = _planted(traj, planted)
    return net.apply, state.params, traj


def _mdp_case():
    env = make_two_state_mdp(horizon=8)
    cfg = a2c.A2CConfig(num_envs=E, rollout_steps=T, hidden=(16,))
    net, state, _, traj = _rollout(env, a2c, cfg)
    return net.apply, state.params, traj


# name -> (builder, chunk C0 patched in, truncated rows expected, trips)
CASES = {
    "pong_no_truncation_zero_trips": (lambda: _pong_case(1000), 4, 0, 0),
    "pong_max_steps_7_n_over_c": (lambda: _pong_case(7), 4, 2 * E, 4),
    "pong_max_steps_1_every_row": (lambda: _pong_case(1), 64, T * E, 3),
    "pong_truncated_beside_terminated": (lambda: _pong_case(1000, 11), 4, 11, 3),
    "pong_n_a_multiple_of_c": (lambda: _pong_case(1000, 12), 4, 12, 3),
    "pong_n_one_past_a_multiple_of_c": (lambda: _pong_case(1000, 13), 4, 13, 4),
    "pong_default_chunk": (lambda: _pong_case(7), None, 2 * E, 1),
    "two_state_mdp_vector_obs": (_mdp_case, 4, 2 * E, 4),
}


def _both(apply_fn, params, traj, chunk, monkeypatch):
    """(rewards of the full pass, rewards of the loop, trips of the loop)."""
    if chunk is not None:
        monkeypatch.setattr(common, "TRUNCATION_CHUNK", chunk)
    calls = []

    def counting_apply(p, obs):
        jax.debug.callback(lambda: calls.append(obs.shape[0]))
        return apply_fn(p, obs)

    want = jax.jit(lambda p, t: full_pass(apply_fn, p, t, GAMMA))(params, traj)
    got = jax.jit(lambda p, t: common.truncation_bootstrap(
        counting_apply, p, t, GAMMA))(params, traj)
    jax.block_until_ready(got)
    jax.effects_barrier()
    return np.asarray(want), np.asarray(got), calls


@pytest.mark.parametrize("case", list(CASES))
def test_rewards_equal_the_full_pass(case, monkeypatch):
    build, chunk, n, trips = CASES[case]
    apply_fn, params, traj = build()
    truncated = np.asarray(traj.done * (1.0 - traj.terminated))
    assert truncated.sum() == n, "the case does not hold the rows it names"
    if "beside_terminated" in case:
        both = np.asarray(traj.done * traj.terminated).sum(axis=1)
        assert (both * truncated.sum(axis=1)).any()
    want, got, calls = _both(apply_fn, params, traj, chunk, monkeypatch)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    # The full pass changes a reward at every truncated row, and only there.
    assert ((want != np.asarray(traj.reward)) <= (truncated > 0)).all()
    rows = min(T * E, chunk or common.TRUNCATION_CHUNK)
    assert calls == [rows] * trips, "the loop's trips are ceil(n / C)"


def test_a_loop_that_drops_the_last_partial_chunk_fails(monkeypatch):
    """Planted fault: one trip short leaves the 13th row unpatched."""
    apply_fn, params, traj = _pong_case(1000, 13)
    fori_loop = jax.lax.fori_loop
    monkeypatch.setattr(
        jax.lax, "fori_loop",
        lambda lo, hi, body, init: fori_loop(lo, hi - 1, body, init))
    want, got, calls = _both(apply_fn, params, traj, 4, monkeypatch)
    assert calls == [4] * 3
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    assert (np.abs(got - want) > 1e-6).sum() == 1


# -- (b) the step ----------------------------------------------------------

def _close(a, b, atol):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=0, atol=atol), a, b)


STEPS = {
    "impala": (impala, lambda: impala.ImpalaConfig(num_envs=E, rollout_steps=T)),
    "a2c": (a2c, lambda: a2c.A2CConfig(num_envs=E, rollout_steps=T)),
    "ppo": (ppo, lambda: ppo.PPOConfig(
        num_envs=E, rollout_steps=T, epochs=1, num_minibatches=2)),
}


@pytest.mark.parametrize("algo", list(STEPS))
def test_one_fused_step_equals_the_step_on_the_full_pass(algo, monkeypatch):
    mod, make_cfg = STEPS[algo]
    env = make_pong(size=36, max_steps=7)
    cfg = make_cfg()
    monkeypatch.setattr(common, "TRUNCATION_CHUNK", 4)
    state = mod.init_state(env, cfg, jax.random.key(0))
    new_state, new_metrics = jax.jit(mod.make_train_step(env, cfg))(state)
    monkeypatch.setattr(mod, "truncation_bootstrap", full_pass)
    old_state, old_metrics = jax.jit(mod.make_train_step(env, cfg))(state)
    assert float(new_metrics["episodes_finished"]) == 2 * E
    if algo == "impala":
        assert float(new_metrics["truncated_frac"]) == pytest.approx(0.1)
    assert sorted(new_metrics) == sorted(old_metrics)
    _close(new_metrics, old_metrics, 1e-5)
    _close(new_state.params, old_state.params, 1e-5)


def _impala_loss_inputs():
    env = make_pong(size=36, max_steps=7)
    cfg = impala.ImpalaConfig(num_envs=E, rollout_steps=T)
    net, state, rstate, traj = _rollout(env, impala, cfg)
    return env, cfg, net, state, rstate, traj


def test_impala_loss_and_gradients_equal_the_full_pass(monkeypatch):
    env, cfg, net, state, rstate, traj = _impala_loss_inputs()
    monkeypatch.setattr(common, "TRUNCATION_CHUNK", 4)

    def grad():
        fn = jax.value_and_grad(impala.impala_loss, has_aux=True)
        return jax.jit(lambda p: fn(p, net.apply, traj, rstate.obs, cfg))(
            state.params)

    (new_loss, new_metrics), new_grads = grad()
    monkeypatch.setattr(impala, "truncation_bootstrap", full_pass)
    (old_loss, old_metrics), old_grads = grad()
    assert float(new_metrics["truncated_frac"]) == pytest.approx(0.1)
    np.testing.assert_allclose(float(new_loss), float(old_loss), atol=1e-5)
    _close(new_metrics, old_metrics, 1e-5)
    _close(new_grads, old_grads, 1e-5)
    assert any(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(new_grads))


def test_impala_loss_differentiates_with_the_time_axis_sharded(monkeypatch):
    """No reverse-mode error from the loop inside `shard_map` either: each
    time shard runs its own trips (no collective in the loop), and the
    pmean-ed gradients equal the unsharded step's."""
    from jax.sharding import PartitionSpec as P

    from actor_critic_tpu.parallel import mesh as pmesh
    from actor_critic_tpu.parallel.seqpar import SP_AXIS

    env, cfg, net, state, rstate, traj = _impala_loss_inputs()
    monkeypatch.setattr(common, "TRUNCATION_CHUNK", 4)
    mesh = jax.make_mesh((4,), (SP_AXIS,))

    def local(params, traj, bootstrap_obs):
        fn = jax.value_and_grad(impala.impala_loss, has_aux=True)
        (_, metrics), grads = fn(
            params, net.apply, traj, bootstrap_obs, cfg, True, SP_AXIS)
        return (pmesh.pmean_tree(grads, SP_AXIS),
                pmesh.pmean(metrics["truncated_frac"], SP_AXIS))

    sharded = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(SP_AXIS), P()),
        out_specs=(P(), P()), check_vma=False))
    grads, frac = sharded(state.params, traj, rstate.obs)
    want = jax.jit(jax.grad(lambda p: impala.impala_loss(
        p, net.apply, traj, rstate.obs, cfg)[0]))(state.params)
    assert float(frac) == pytest.approx(0.1)
    _close(grads, want, 1e-5)
