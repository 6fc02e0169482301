"""Pallas scan kernels vs. the lax.scan golden implementations
(interpret mode on the CPU test backend; compiled path exercised on TPU
by the fused trainers)."""

import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_tpu.ops import pallas_scan, returns

GAMMA, LAM = 0.99, 0.95


@pytest.fixture(scope="module")
def traj():
    rng = np.random.default_rng(0)
    T, E = 17, 512  # odd T; E hits one full block
    rewards = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    values = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    dones = jnp.asarray(rng.random(size=(T, E)) < 0.1, jnp.float32)
    bootstrap = jnp.asarray(rng.normal(size=(E,)), jnp.float32)
    return rewards, values, dones, bootstrap


def test_gae_matches_golden(traj):
    rewards, values, dones, bootstrap = traj
    adv_g, ret_g = returns.gae(rewards, values, dones, bootstrap, GAMMA, LAM)
    adv, ret = pallas_scan.gae(rewards, values, dones, bootstrap, GAMMA, LAM)
    np.testing.assert_allclose(np.asarray(adv), np.asarray(adv_g), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ret), np.asarray(ret_g), rtol=1e-6, atol=1e-6)


def test_gae_multi_block(traj):
    """E larger than one block → grid > 1, blocks must not interact."""
    rewards, values, dones, bootstrap = traj
    r2 = jnp.concatenate([rewards, rewards * 2.0], axis=1)
    v2 = jnp.concatenate([values, values * -1.0], axis=1)
    d2 = jnp.concatenate([dones, dones], axis=1)
    b2 = jnp.concatenate([bootstrap, bootstrap], axis=0)
    adv_g, _ = returns.gae(r2, v2, d2, b2, GAMMA, LAM)
    adv, _ = pallas_scan.gae(r2, v2, d2, b2, GAMMA, LAM)
    np.testing.assert_allclose(np.asarray(adv), np.asarray(adv_g), rtol=1e-6, atol=1e-6)


def test_gae_small_batch_lane_padded(traj):
    """E below one 128-lane tile → zero-padded to one tile, sliced back;
    the kernel must ENGAGE (ISSUE 19), not silently fall back."""
    rewards, values, dones = (a[:, :96] for a in traj[:3])
    bootstrap = traj[3][:96]
    assert pallas_scan.kernel_block("gae", rewards.shape[0], 96) == 128
    adv_g, _ = returns.gae(rewards, values, dones, bootstrap, GAMMA, LAM)
    adv, _ = pallas_scan.gae(rewards, values, dones, bootstrap, GAMMA, LAM)
    assert adv.shape == rewards.shape
    np.testing.assert_allclose(np.asarray(adv), np.asarray(adv_g), rtol=1e-6, atol=1e-6)


def test_gae_non_2d_falls_back(traj):
    rewards, values, dones, bootstrap = traj
    adv, _ = pallas_scan.gae(rewards[:, 0], values[:, 0], dones[:, 0],
                             bootstrap[0], GAMMA, LAM)
    adv_g, _ = returns.gae(rewards[:, 0], values[:, 0], dones[:, 0],
                           bootstrap[0], GAMMA, LAM)
    np.testing.assert_allclose(np.asarray(adv), np.asarray(adv_g), rtol=1e-6, atol=1e-6)


def test_vtrace_cbar_above_rhobar(traj):
    """c clips the RAW ratio: c_bar > rho_bar must still match golden
    (regression: kernel once derived c from the rho_bar-clipped rho)."""
    rewards, values, dones, bootstrap = traj
    rng = np.random.default_rng(5)
    tlp = jnp.asarray(rng.normal(size=rewards.shape), jnp.float32)
    blp = jnp.asarray(rng.normal(size=rewards.shape), jnp.float32)
    golden = returns.vtrace(tlp, blp, rewards, values, dones, bootstrap,
                            GAMMA, rho_bar=1.0, c_bar=2.0, lam=0.9)
    got = pallas_scan.vtrace(tlp, blp, rewards, values, dones, bootstrap,
                             GAMMA, rho_bar=1.0, c_bar=2.0, lam=0.9)
    for name in ("vs", "pg_advantages", "clipped_rhos"):
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(golden, name)),
            rtol=1e-5, atol=1e-5, err_msg=name,
        )


def test_gae_long_T_shrinks_block_or_falls_back(traj):
    """T large enough to force a narrow block (or the lax.scan fallback)
    still produces golden results."""
    rng = np.random.default_rng(3)
    T, E = 4096, 128
    rewards = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    values = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    dones = jnp.asarray(rng.random(size=(T, E)) < 0.02, jnp.float32)
    bootstrap = jnp.asarray(rng.normal(size=(E,)), jnp.float32)
    adv_g, _ = returns.gae(rewards, values, dones, bootstrap, GAMMA, LAM)
    adv, _ = pallas_scan.gae(rewards, values, dones, bootstrap, GAMMA, LAM)
    np.testing.assert_allclose(np.asarray(adv), np.asarray(adv_g), rtol=1e-5, atol=1e-5)


def test_vtrace_matches_golden(traj):
    rewards, values, dones, bootstrap = traj
    rng = np.random.default_rng(1)
    tlp = jnp.asarray(rng.normal(size=rewards.shape) * 0.3, jnp.float32)
    blp = jnp.asarray(rng.normal(size=rewards.shape) * 0.3, jnp.float32)
    golden = returns.vtrace(tlp, blp, rewards, values, dones, bootstrap,
                            GAMMA, rho_bar=1.0, c_bar=1.0, lam=0.9)
    got = pallas_scan.vtrace(tlp, blp, rewards, values, dones, bootstrap,
                             GAMMA, rho_bar=1.0, c_bar=1.0, lam=0.9)
    for name in ("vs", "pg_advantages", "clipped_rhos"):
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(golden, name)),
            rtol=1e-5, atol=1e-6, err_msg=name,
        )


def test_kernel_block_engagement():
    """kernel_block must report exactly when the kernels engage vs fall
    back — benches and future callers rely on it to avoid attributing
    lax.scan timings to Pallas (the T=2048 V-trace fallback burned the
    round-3 bench once already)."""
    from actor_critic_tpu.ops import pallas_scan as ps

    # 11-array V-trace: T=2048 exceeds the VMEM tile budget → fallback.
    assert ps.kernel_block("vtrace", 2048, 256) == 0
    # T=1024 still fits a 128-lane tile.
    assert ps.kernel_block("vtrace", 1024, 256) == 128
    # 7-array GAE fits at T=2048.
    assert ps.kernel_block("gae", 2048, 256) == 128
    # λ-returns ride the GAE kernel, so they price identically.
    assert ps.kernel_block("lambda", 2048, 256) == 128
    # Headline trainer shape: full default tile.
    assert ps.kernel_block("gae", 32, 4096) == 512
    # Ragged/small E lane-pads to the next 128 multiple (ISSUE 19):
    # the kernel now ENGAGES instead of silently falling back.
    assert ps.kernel_block("gae", 32, 100) == 128
    assert ps.kernel_block("gae", 32, 8) == 128
    assert ps.kernel_block("vtrace", 64, 200) == 256  # pads 200 → 256, one block
    # Only an impossible T still reports the lax.scan fallback.
    assert ps.kernel_block("gae", 1 << 20, 256) == 0


# ---------------------------------------------------------------------------
# ISSUE 19 boundary-shape golden parity: T=1, E below one lane tile,
# non-divisible E/block, and done-at-t0, for all three fused scans.
# ---------------------------------------------------------------------------


def _rand_batch(T, E, seed=7):
    rng = np.random.default_rng(seed)
    rewards = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    values = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    dones = jnp.asarray(rng.random(size=(T, E)) < 0.15, jnp.float32)
    bootstrap = jnp.asarray(rng.normal(size=(E,)), jnp.float32)
    return rewards, values, dones, bootstrap


@pytest.mark.parametrize(
    "T,E",
    [(1, 128), (1, 7), (5, 96), (3, 300), (17, 640)],
    ids=["T1-tile", "T1-tiny", "E-sub-tile", "E-ragged", "E-nondiv-block"],
)
def test_boundary_shapes_gae_lambda_golden(T, E):
    rewards, values, dones, bootstrap = _rand_batch(T, E)
    adv_g, ret_g = returns.gae(rewards, values, dones, bootstrap, GAMMA, LAM)
    adv, ret = pallas_scan.gae(rewards, values, dones, bootstrap, GAMMA, LAM)
    np.testing.assert_allclose(np.asarray(adv), np.asarray(adv_g), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ret), np.asarray(ret_g), rtol=1e-6, atol=1e-6)
    lam_g = returns.lambda_returns(rewards, values, dones, bootstrap, GAMMA, LAM)
    lam_k = pallas_scan.lambda_returns(rewards, values, dones, bootstrap, GAMMA, LAM)
    assert lam_k.shape == (T, E)
    np.testing.assert_allclose(np.asarray(lam_k), np.asarray(lam_g), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize(
    "T,E", [(1, 128), (1, 7), (5, 96), (3, 300)],
    ids=["T1-tile", "T1-tiny", "E-sub-tile", "E-ragged"],
)
def test_boundary_shapes_vtrace_golden(T, E):
    rewards, values, dones, bootstrap = _rand_batch(T, E, seed=11)
    rng = np.random.default_rng(13)
    tlp = jnp.asarray(rng.normal(size=(T, E)) * 0.3, jnp.float32)
    blp = jnp.asarray(rng.normal(size=(T, E)) * 0.3, jnp.float32)
    golden = returns.vtrace(tlp, blp, rewards, values, dones, bootstrap,
                            GAMMA, rho_bar=1.0, c_bar=1.0, lam=0.9)
    got = pallas_scan.vtrace(tlp, blp, rewards, values, dones, bootstrap,
                             GAMMA, rho_bar=1.0, c_bar=1.0, lam=0.9)
    for name in ("vs", "pg_advantages", "clipped_rhos"):
        assert getattr(got, name).shape == (T, E)
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(golden, name)),
            rtol=1e-5, atol=1e-6, err_msg=name,
        )


def test_done_at_t0_golden():
    """done on the very first row must cut the recurrence exactly as the
    lax reference does (the carry enters the loop non-zero)."""
    T, E = 4, 128
    rewards, values, _, bootstrap = _rand_batch(T, E, seed=17)
    dones = jnp.zeros((T, E), jnp.float32).at[0, :].set(1.0)
    adv_g, ret_g = returns.gae(rewards, values, dones, bootstrap, GAMMA, LAM)
    adv, ret = pallas_scan.gae(rewards, values, dones, bootstrap, GAMMA, LAM)
    np.testing.assert_allclose(np.asarray(adv), np.asarray(adv_g), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ret), np.asarray(ret_g), rtol=1e-6, atol=1e-6)
    got = pallas_scan.vtrace(values * 0.1, rewards * 0.1, rewards, values,
                             dones, bootstrap, GAMMA)
    golden = returns.vtrace(values * 0.1, rewards * 0.1, rewards, values,
                            dones, bootstrap, GAMMA)
    np.testing.assert_allclose(np.asarray(got.vs), np.asarray(golden.vs),
                               rtol=1e-5, atol=1e-6)


def test_lambda_returns_auto_dispatch(traj):
    """lambda_returns_auto falls back to the lax reference off-TPU and
    matches it bitwise there (the interpret-mode kernel is test-only)."""
    rewards, values, dones, bootstrap = traj
    got = pallas_scan.lambda_returns_auto(rewards, values, dones, bootstrap,
                                          GAMMA, LAM)
    ref = returns.lambda_returns(rewards, values, dones, bootstrap, GAMMA, LAM)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
