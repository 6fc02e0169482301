"""Generator for results/td3_nstep_pendulum_cpu.json: fused TD3 on pure-JAX
Pendulum at nstep=1 vs nstep=3 (the DDPGConfig.nstep /
replay.sample_sequences consumer), same budget and seed. Run on CPU:

    JAX_PLATFORMS=cpu python scripts/td3_nstep_compare.py
"""
import json, os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
from actor_critic_tpu.algos import ddpg
from actor_critic_tpu.envs import make_pendulum
from actor_critic_tpu.algos.common import evaluate

results = {}
for nstep in (1, 3):
    env = make_pendulum()
    cfg = ddpg.td3_config(
        num_envs=1, steps_per_iter=64, updates_per_iter=64,
        buffer_capacity=100_000, batch_size=256, warmup_steps=1_000,
        exploration_noise=0.1, nstep=nstep,
    )
    t0 = time.monotonic()
    state, m = ddpg.train(env, cfg, num_iterations=1200, seed=0)
    actor, _ = ddpg._modules(env.spec.action_dim, cfg)
    ret = float(evaluate(env, actor.apply, state.learner.actor_params,
                         jax.random.key(99), num_envs=32, num_steps=200))
    results[f"nstep{nstep}"] = {
        "greedy_eval": round(ret, 1),
        "env_steps": 1200 * 64,
        "wall_s": round(time.monotonic() - t0, 1),
        "critic_loss": round(float(m["critic_loss"]), 4),
    }
    print(nstep, results[f"nstep{nstep}"], flush=True)
with open("results/td3_nstep_pendulum_cpu.json", "w") as f:
    json.dump({"config": "fused TD3 JAX-Pendulum, E=1, 76.8k steps/updates, seed 0",
               "note": "nstep=3 uses replay.sample_sequences n-step targets",
               **results}, f, indent=1)
print("saved")
