"""Native C++ env engine vs gymnasium: exact dynamics parity, SAME_STEP
auto-reset semantics, and HostEnvPool integration."""

import numpy as np
import pytest

gym = pytest.importorskip("gymnasium")

from actor_critic_tpu.envs.host_pool import HostEnvPool
from actor_critic_tpu.envs.native_pool import NativeVecEnv


def test_cartpole_dynamics_match_gymnasium():
    """From identical injected states, N steps of the native engine must
    reproduce gymnasium's CartPole-v1 trajectory bitwise-closely."""
    genv = gym.make("CartPole-v1").unwrapped
    genv.reset(seed=0)
    nenv = NativeVecEnv("CartPole-v1", num_envs=1)
    nenv.reset(seed=0)

    rng = np.random.default_rng(42)
    start = rng.uniform(-0.05, 0.05, size=4).astype(np.float32)
    genv.state = np.asarray(start, np.float64)
    nenv.set_state(start[None, :])

    for t in range(60):
        a = int(rng.integers(0, 2))
        gobs, grew, gterm, gtrunc, _ = genv.step(a)
        nobs, nrew, nterm, ntrunc, ninfo = nenv.step(np.array([a]))
        if gterm:
            # native autoresets; compare the pre-reset obs
            np.testing.assert_allclose(
                ninfo["final_obs"][0], gobs.astype(np.float32), rtol=1e-5, atol=1e-6
            )
            assert bool(nterm[0])
            break
        np.testing.assert_allclose(nobs[0], gobs.astype(np.float32), rtol=1e-5, atol=1e-6)
        assert nrew[0] == grew
        assert not bool(nterm[0])


def test_pendulum_dynamics_match_gymnasium():
    genv = gym.make("Pendulum-v1").unwrapped
    genv.reset(seed=0)
    nenv = NativeVecEnv("Pendulum-v1", num_envs=1)
    nenv.reset(seed=0)

    rng = np.random.default_rng(1)
    start = np.array([rng.uniform(-np.pi, np.pi), rng.uniform(-1, 1)], np.float32)
    genv.state = np.asarray(start, np.float64)
    nenv.set_state(start[None, :])

    for t in range(50):
        a = rng.uniform(-2, 2, size=1).astype(np.float32)
        gobs, grew, _, _, _ = genv.step(a)
        nobs, nrew, nterm, ntrunc, _ = nenv.step(a[None, :])
        np.testing.assert_allclose(nobs[0], gobs.astype(np.float32), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(nrew[0], grew, rtol=1e-4, atol=1e-5)
        assert not bool(nterm[0])


def test_autoreset_same_step_semantics():
    """Termination: final_obs carries the ending obs, obs the new episode,
    and step counters restart (time-limit truncation at 500)."""
    nenv = NativeVecEnv("CartPole-v1", num_envs=4)
    obs, _ = nenv.reset(seed=7)
    assert obs.shape == (4, 4)
    done_seen = False
    for t in range(600):
        acts = np.ones(4, np.int64)  # constant push → quick termination
        obs, rew, term, trunc, info = nenv.step(acts)
        assert obs.shape == (4, 4) and rew.shape == (4,)
        if (term | trunc).any():
            done_seen = True
            i = int(np.argmax(term | trunc))
            # SAME_STEP contract: final_obs[i] is the PRE-reset terminal
            # observation. For a true termination that state must violate
            # the CartPole bounds (|x| > 2.4 or |theta| > 12°) — a reset
            # state (uniform [-0.05, 0.05]) can never satisfy this, so the
            # assertion genuinely distinguishes the two.
            fo = np.asarray(info["final_obs"][i], np.float64)
            if term[i]:
                assert abs(fo[0]) > 2.4 or abs(fo[2]) > 12 * np.pi / 180
            # reset obs is near the origin (fresh uniform [-0.05, 0.05])
            assert np.all(np.abs(obs[i]) <= 0.05 + 1e-6)
        if done_seen and t > 20:
            break
    assert done_seen


def test_hostenvpool_native_backend():
    pool = HostEnvPool(
        "CartPole-v1", num_envs=8, backend="native",
        normalize_obs=True, normalize_reward=False,
    )
    obs = pool.reset()
    assert obs.shape == (8, 4)
    for _ in range(10):
        out = pool.step(np.zeros(8, np.int64))
    assert out.obs.shape == (8, 4)
    assert out.raw_reward.shape == (8,)
    assert pool.spec.discrete and pool.spec.action_dim == 2


def test_native_faster_than_gym():
    """The point of the native engine: batch stepping beats the Python
    per-env loop (sanity margin only — CI noise tolerant)."""
    import time

    E, T = 64, 200
    native = HostEnvPool("CartPole-v1", E, backend="native",
                         normalize_obs=False, normalize_reward=False)
    gympool = HostEnvPool("CartPole-v1", E, backend="gym",
                          normalize_obs=False, normalize_reward=False)
    acts = np.zeros(E, np.int64)
    for pool in (native, gympool):
        pool.reset()
        pool.step(acts)  # warm
    t0 = time.perf_counter(); [native.step(acts) for _ in range(T)]
    t_native = time.perf_counter() - t0
    t0 = time.perf_counter(); [gympool.step(acts) for _ in range(T)]
    t_gym = time.perf_counter() - t0
    assert t_native < t_gym, (t_native, t_gym)


@pytest.mark.parametrize("seed", [5, 11, 23, 47])
def test_mountaincar_dynamics_match_gymnasium(seed):
    """MountainCarContinuous-v0: clipped force, inelastic left wall, raw-
    action reward penalty, +100 goal bonus — stepped against gymnasium
    from identical injected states. Multiple seeds because the env's
    float32 per-op arithmetic (emulated in C) diverges chaotically if
    even one op rounds differently — a single lucky seed can't certify
    it."""
    genv = gym.make("MountainCarContinuous-v0").unwrapped
    genv.reset(seed=0)
    nenv = NativeVecEnv("MountainCarContinuous-v0", num_envs=1)
    nenv.reset(seed=0)

    rng = np.random.default_rng(seed)
    # float32 start state: gymnasium's MountainCar state IS float32, so
    # injecting float64 would give its first step different (float64)
    # per-op arithmetic than every later step.
    start32 = np.array([rng.uniform(-0.6, -0.4), 0.0], np.float32)
    genv.state = start32.copy()
    nenv.set_state(start32.astype(np.float64)[None, :])

    # Full-episode horizon: gymnasium rounds MountainCar state to float32
    # each step (unlike its other classic-control envs); the native
    # engine mirrors that, and without the mirroring the wall/clip
    # discontinuities amplify the rounding difference chaotically
    # (~0.55 obs divergence by step 999) — so the long horizon is the
    # assertion that matters.
    for t in range(990):  # just under the 999 limit (unwrapped gym never
        # truncates; the native engine would auto-reset at 999)
        # Out-of-range actions exercise the clip-for-force /
        # raw-for-penalty asymmetry.
        a = np.array([rng.uniform(-1.5, 1.5)], np.float32)
        gobs, grew, gterm, gtrunc, _ = genv.step(a)
        nobs, nrew, nterm, ntrunc, ninfo = nenv.step(a[None, :])
        if gterm:
            np.testing.assert_allclose(
                ninfo["final_obs"][0], gobs.astype(np.float32),
                rtol=1e-5, atol=1e-6,
            )
            assert bool(nterm[0])
            break
        np.testing.assert_allclose(
            nobs[0], gobs.astype(np.float32), rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(nrew[0], grew, rtol=1e-5, atol=1e-6)
        assert not bool(nterm[0])


def test_acrobot_dynamics_match_gymnasium():
    """Acrobot-v1: RK4 book dynamics, angle wrap, velocity bounds — the
    native trajectory must track gymnasium's step for step."""
    genv = gym.make("Acrobot-v1").unwrapped
    genv.reset(seed=0)
    nenv = NativeVecEnv("Acrobot-v1", num_envs=1)
    nenv.reset(seed=0)

    rng = np.random.default_rng(9)
    start = rng.uniform(-0.1, 0.1, size=4)
    genv.state = start.astype(np.float64)
    nenv.set_state(start[None, :])

    for t in range(120):
        a = int(rng.integers(0, 3))
        gobs, grew, gterm, gtrunc, _ = genv.step(a)
        nobs, nrew, nterm, ntrunc, ninfo = nenv.step(np.array([a]))
        if gterm:
            np.testing.assert_allclose(
                ninfo["final_obs"][0], gobs.astype(np.float32),
                rtol=1e-4, atol=1e-5,
            )
            assert bool(nterm[0])
            break
        np.testing.assert_allclose(
            nobs[0], gobs.astype(np.float32), rtol=1e-4, atol=1e-5
        )
        assert nrew[0] == grew
        assert not bool(nterm[0])


def test_new_native_envs_under_hostenvpool():
    """Both new envs ride HostEnvPool's native backend end-to-end."""
    for env_id, disc in (
        ("MountainCarContinuous-v0", False), ("Acrobot-v1", True),
    ):
        pool = HostEnvPool(
            env_id, num_envs=4, seed=3, backend="native",
            normalize_obs=False, normalize_reward=False,
        )
        obs = pool.reset()
        assert obs.shape == (4, pool.spec.obs_shape[0])
        if disc:
            acts = np.zeros(4, np.int64)
        else:
            acts = np.zeros((4, 1), np.float32)
        out = pool.step(acts)
        assert np.isfinite(out.obs).all()
        pool.close()


def test_mountaincar_goal_termination_and_bonus():
    """The +100 goal bonus, raw-action penalty, and termination flag —
    injected near-goal state so the terminal branch actually runs."""
    genv = gym.make("MountainCarContinuous-v0").unwrapped
    genv.reset(seed=0)
    nenv = NativeVecEnv("MountainCarContinuous-v0", num_envs=1)
    nenv.reset(seed=0)

    start32 = np.array([0.445, 0.055], np.float32)
    genv.state = start32.copy()
    nenv.set_state(start32.astype(np.float64)[None, :])

    a = np.array([1.0], np.float32)
    gobs, grew, gterm, _, _ = genv.step(a)
    nobs, nrew, nterm, _, ninfo = nenv.step(a[None, :])
    assert gterm, "test setup must reach the goal in one step"
    assert bool(nterm[0])
    np.testing.assert_allclose(nrew[0], grew, rtol=1e-6)  # ≈ 100 - 0.1
    assert nrew[0] > 99.0
    np.testing.assert_allclose(
        ninfo["final_obs"][0], gobs.astype(np.float32), rtol=1e-5, atol=1e-6
    )
    # SAME_STEP: obs holds the fresh episode (position ∈ [-0.6, -0.4]).
    assert -0.6 <= nobs[0, 0] <= -0.4 and nobs[0, 1] == 0.0


def test_acrobot_termination_parity():
    """Terminal condition (-cosθ1 - cos(θ1+θ2) > 1) and 0-vs-(-1) reward,
    from an injected state one step short of the goal height."""
    genv = gym.make("Acrobot-v1").unwrapped
    genv.reset(seed=0)
    nenv = NativeVecEnv("Acrobot-v1", num_envs=1)
    nenv.reset(seed=0)

    start = np.array([2.8, 0.0, 0.0, 0.0], np.float64)  # near-vertical link 1
    genv.state = start.copy()
    nenv.set_state(start[None, :])

    a = 1  # zero torque
    gobs, grew, gterm, _, _ = genv.step(a)
    nobs, nrew, nterm, _, ninfo = nenv.step(np.array([a]))
    assert gterm, "test setup must terminate in one step"
    assert bool(nterm[0])
    assert nrew[0] == grew == 0.0
    np.testing.assert_allclose(
        ninfo["final_obs"][0], gobs.astype(np.float32), rtol=1e-5, atol=1e-6
    )
    # fresh episode obs: all four state vars uniform in [-0.1, 0.1]
    assert abs(nobs[0, 4]) <= 0.1 and abs(nobs[0, 5]) <= 0.1


@pytest.mark.slow
def test_ppo_learns_native_acrobot():
    """Learning test on the C++ engine's Acrobot: the full host PPO path
    (native batch stepping + normalization + jitted learner) reaches
    greedy eval >= -100 (the conventional solve bar) within 150
    iterations / 307k env steps. The recorded run
    (results/ppo_acrobot_native_cpu.jsonl) hits -83.8 by iteration 25,
    so 150 leaves wide margin; wall-clock is ~10 s of stepping on the
    1-core host."""
    from actor_critic_tpu.algos import ppo

    pool = HostEnvPool(
        "Acrobot-v1", num_envs=16, seed=0, backend="native",
        normalize_obs=True, normalize_reward=True,
    )
    cfg = ppo.PPOConfig(
        num_envs=16, rollout_steps=128, epochs=4, num_minibatches=8,
        anneal_iters=300, lr_final=0.0,  # the recorded run's schedule —
        # this test replays its first 150 iterations exactly
    )
    best = -float("inf")
    _, _, history = ppo.train_host(
        pool, cfg, num_iterations=150, seed=0, log_every=0,
        eval_every=50, eval_envs=8, eval_steps=500,
    )
    for _, m in history:
        if "eval_return" in m:
            best = max(best, m["eval_return"])
    pool.close()
    assert best >= -100.0, f"native Acrobot not learned: best eval {best}"


def test_library_from_another_key_is_rebuilt_not_loaded(tmp_path, monkeypatch):
    """The cached engine is keyed on source, flags and this machine's CPU
    (`-march=native` code is only valid where it was built, and a working
    tree can be copied whole to another machine): a library left under any
    other key — or the pre-key `_vecenv.so` name — is never dlopen'ed; the
    engine is rebuilt from the source and the strays are removed."""
    import shutil

    from actor_critic_tpu import native

    shutil.copy(native._SRC, tmp_path / "vecenv.cpp")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(tmp_path / "vecenv.cpp"))
    strays = [tmp_path / "_vecenv.so", tmp_path / "_vecenv.0123456789abcdef.so"]
    for stray in strays:
        stray.write_bytes(b"not a shared object: dlopen would fail")

    builds = []
    real_build = native._build
    monkeypatch.setattr(
        native, "_build", lambda lib: (builds.append(lib), real_build(lib))
    )
    load = native.load.__wrapped__  # bypass the per-process lru_cache
    lib = load()
    here = native.lib_path()
    assert builds == [here] and lib._name == here
    assert hasattr(lib, "cartpole_step")
    assert sorted(p.name for p in tmp_path.glob("_vecenv*")) == [
        here.rsplit("/", 1)[1]
    ], "stale libraries must not survive a build"
    load()
    assert builds == [here], "a library with the right key is reused"

    # The same tree on another CPU: another key, so another build.
    monkeypatch.setattr(native, "_cpu_identity", lambda: "another machine")
    there = native.lib_path()
    assert there != here
    assert load()._name == there and builds == [here, there]
