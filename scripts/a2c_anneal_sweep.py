"""A2C CartPole solve-gap sweep (VERDICT r3 missing #6 / next #4).

The flagship `a2c_cartpole` preset reaches greedy eval 465/458 — under
the 475 solve bar that PPO clears. This harness sweeps the anneal
schedule/rollout shape at CPU-calibration scale (E=256, the same shape
tests/test_a2c.py guards) and reports greedy eval at several points, so
the winning schedule can be promoted into the preset and re-certified at
E=4096.

Usage:
    JAX_PLATFORMS=cpu python scripts/a2c_anneal_sweep.py \
        [--configs NAME ...] [--seeds 0 1 2] [--out results/a2c_sweep.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIGS: dict[str, dict] = {
    # The shipped preset's schedule, at calibration scale (baseline).
    "preset400": dict(iterations=400, anneal_iters=400),
    # Longer schedule: the 465-eval curve was still creeping at iter 400.
    "preset600": dict(iterations=600, anneal_iters=600),
    "preset800": dict(iterations=800, anneal_iters=800),
    # Longer rollouts: T=64 halves GAE truncation bias per update.
    "t64_400": dict(iterations=400, anneal_iters=400, rollout_steps=64),
    "t64_600": dict(iterations=600, anneal_iters=600, rollout_steps=64),
    # Keep a little entropy/lr at the end instead of full decay.
    "lrfloor600": dict(iterations=600, anneal_iters=600, lr_final=1e-4),
    # Tighter GAE (lower variance targets late in training).
    "lam90_600": dict(iterations=600, anneal_iters=600, gae_lambda=0.90),
    # E=4096 preset-scale grid: the E=256 winner (t64_400) ceilinged at
    # ~465 at E=4096/lr=1e-3 — a 16× batch at the same lr is underfit
    # per update, so scale lr (and optionally keep exploration alive
    # longer with a slower entropy anneal).
    "big_lr15": dict(iterations=400, anneal_iters=400, num_envs=4096,
                     rollout_steps=64, lr=1.5e-3),
    "big_lr2": dict(iterations=400, anneal_iters=400, num_envs=4096,
                    rollout_steps=64, lr=2e-3),
    "big_lr3": dict(iterations=400, anneal_iters=400, num_envs=4096,
                    rollout_steps=64, lr=3e-3),
    "big_lr2_t32": dict(iterations=400, anneal_iters=400, num_envs=4096,
                        rollout_steps=32, lr=2e-3),
    # Stabilizers for the lr=3e-3 winner's seed sensitivity (seed 2
    # oscillated 452->256->443->251 and never settled).
    "big_lr3_nadv": dict(iterations=400, anneal_iters=400, num_envs=4096,
                         rollout_steps=64, lr=3e-3, normalize_adv=True),
    "big_lr25": dict(iterations=400, anneal_iters=400, num_envs=4096,
                     rollout_steps=64, lr=2.5e-3),
    # normalize_adv collapsed to ~230 at this scale (it rescales the
    # advantage signal the big batch already denoises); try taming
    # lr=3e-3's oscillation with a tighter grad clip instead.
    "big_lr3_clip25": dict(iterations=400, anneal_iters=400, num_envs=4096,
                           rollout_steps=64, lr=3e-3, max_grad_norm=0.25),
}


def run_one(name: str, spec: dict, seed: int) -> dict:
    import dataclasses

    import jax

    from actor_critic_tpu.algos import a2c
    from actor_critic_tpu.envs import make_cartpole

    spec = dict(spec)
    iterations = spec.pop("iterations")
    base = dict(
        num_envs=256, rollout_steps=32, lr=1e-3, lr_final=0.0,
        entropy_coef=0.01, entropy_coef_final=0.0,
    )  # sweep default; configs override num_envs for preset-scale runs
    base.update(spec)
    cfg = a2c.A2CConfig(**base)
    env = make_cartpole()
    state = a2c.init_state(env, cfg, jax.random.key(seed))
    step = jax.jit(a2c.make_train_step(env, cfg), donate_argnums=0)
    eval_fn = jax.jit(a2c.make_eval_fn(env, cfg), static_argnums=(2, 3))
    ekey = jax.random.key(seed + 1)
    t0 = time.perf_counter()
    evals = {}
    checkpoints = sorted({iterations // 2, 3 * iterations // 4, iterations})
    it = 0
    for target in checkpoints:
        while it < target:
            state, m = step(state)
            it += 1
        ekey, sub = jax.random.split(ekey)
        evals[it] = round(float(eval_fn(state, sub, 64, 512)), 1)
    row = {
        "config": name, "seed": seed,
        "final_train_ema": round(float(m["avg_return_ema"]), 1),
        "evals": evals, "wall_s": round(time.perf_counter() - t0, 1),
        "cfg": {k: v for k, v in dataclasses.asdict(cfg).items()
                if not isinstance(v, tuple)},
    }
    print(json.dumps(row), flush=True)
    return row


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--configs", nargs="*", default=list(CONFIGS))
    p.add_argument("--seeds", nargs="*", type=int, default=[0])
    p.add_argument("--out", default="")
    args = p.parse_args()
    rows = [
        run_one(name, CONFIGS[name], seed)
        for name in args.configs
        for seed in args.seeds
    ]
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
