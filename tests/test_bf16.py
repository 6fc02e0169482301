"""bf16 compute path: every fused trainer's bf16_compute flag produces a
runnable, finite train step with f32 params (mixed precision — MXU-sized
matmuls in bf16, accumulation/optimizer in f32), and — ISSUE 19 — the
`--update-dtype bf16` path lands same-seed eval parity with fp32 on every
on-policy algo, mirroring the PR 8 replay-dtype parity suite."""

import os
import tempfile

import jax
import jax.numpy as jnp
import pytest

from actor_critic_tpu.algos import a2c, impala, ppo
from actor_critic_tpu.envs import make_cartpole, make_point_mass, make_pong


@pytest.mark.parametrize(
    "mod,cfg,make_env",
    [
        (a2c, a2c.A2CConfig(num_envs=8, rollout_steps=4, hidden=(16,),
                            bf16_compute=True), make_cartpole),
        (impala, impala.ImpalaConfig(num_envs=4, rollout_steps=4, hidden=(16,),
                                     bf16_compute=True), make_cartpole),
    ],
)
def test_bf16_train_step_finite(mod, cfg, make_env):
    env = make_env()
    state = mod.init_state(env, cfg, jax.random.key(0))
    # params stay f32 (mixed precision: casts happen in the modules)
    assert all(
        x.dtype == jnp.float32
        for x in jax.tree.leaves(state.params)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
    )
    step = jax.jit(mod.make_train_step(env, cfg), donate_argnums=0)
    for _ in range(3):
        state, metrics = step(state)
    assert bool(jnp.isfinite(metrics["loss"]))


# -- ISSUE 19: --update-dtype bf16 vs fp32 eval parity ----------------------
#
# Same-seed short runs in both precisions must BOTH learn point_mass
# (optimal 0, random ≈ −6) and land within a tolerance of each other —
# bf16 matmul compute with fp32 accumulators must not change what the
# policy converges to. Configs were tuned so the fp32 leg demonstrably
# learns in a few seconds on CPU; thresholds mirror PR 8's
# test_eval_parity_fp32_vs_mixed.
#
# The six training legs run behind ONE session-scoped fixture (ISSUE 20
# satellite) under the ISSUE 4 persistent compilation cache: these legs
# are compile-bound (~4 s XLA compile vs ~0.3 s of actual training per
# leg on this 1-core host), so the steady-state tier-1 run deserializes
# every leg's programs instead of recompiling them — measured 24 s cold
# vs 10 s warm (~17 s clawed back from the second run onward). The
# assertions are unchanged; only where the compiled programs come from
# moved.

_PARITY_CACHE_DIR = os.environ.get(
    "BF16_PARITY_CACHE_DIR",
    os.path.join(
        tempfile.gettempdir(), "actor_critic_tpu_bf16_parity_cache"
    ),
)

_PARITY_CFGS = {
    "ppo": (ppo, lambda bf16: ppo.PPOConfig(
        num_envs=32, rollout_steps=16, epochs=4, num_minibatches=2,
        lr=3e-3, hidden=(32, 32), bf16_compute=bf16,
    ), 120),
    # 300, not 200: under jax 0.9.0 the A2C curve on point_mass has its
    # knee AT 200 iterations (fp32 evals at seeds 0-3: -3.7, -0.7, -3.6,
    # -3.7 at 200; all four > -0.03 at 300, both precisions), so the old
    # budget tested where the knee fell, not whether the trainer learns.
    "a2c": (a2c, lambda bf16: a2c.A2CConfig(
        num_envs=32, rollout_steps=16, lr=3e-3, hidden=(32, 32),
        bf16_compute=bf16,
    ), 300),
    "impala": (impala, lambda bf16: impala.ImpalaConfig(
        num_envs=32, rollout_steps=16, lr=3e-3, hidden=(32, 32),
        bf16_compute=bf16,
    ), 200),
}


def _train_and_eval(mod, env, cfg, iters, seed):
    state = mod.init_state(env, cfg, jax.random.key(seed))
    step = jax.jit(mod.make_train_step(env, cfg), donate_argnums=0)
    for _ in range(iters):
        state, _ = step(state)
    eval_fn = jax.jit(mod.make_eval_fn(env, cfg), static_argnums=(2, 3))
    return float(eval_fn(state, jax.random.key(99), 32, 16))


@pytest.fixture(scope="session")
def bf16_parity_legs():
    """Lazy per-algo trainer: `legs('ppo') -> {False: ret, True: ret}`,
    each algo's two precision legs trained at most once per session,
    all compiles routed through the persistent cache so repeat tier-1
    runs skip straight to the ~0.3 s of actual training per leg."""
    from actor_critic_tpu.utils import compile_cache

    trained: dict = {}

    def legs(algo: str) -> dict:
        if algo not in trained:
            mod, make_cfg, iters = _PARITY_CFGS[algo]
            env = make_point_mass()
            with compile_cache.temporary_cache(_PARITY_CACHE_DIR):
                trained[algo] = {
                    bf16: _train_and_eval(
                        mod, env, make_cfg(bf16), iters, seed=0
                    )
                    for bf16 in (False, True)
                }
        return trained[algo]

    return legs


@pytest.mark.parametrize("algo", ["ppo", "a2c", "impala"])
def test_eval_parity_fp32_vs_bf16(algo, bf16_parity_legs):
    results = bf16_parity_legs(algo)
    assert results[False] > -1.0, results
    assert results[True] > -1.0, results
    assert abs(results[False] - results[True]) < 1.0, results


