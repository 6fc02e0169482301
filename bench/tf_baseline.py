"""TF2-CPU reference-shaped PPO baseline — BASELINE HARNESS, NOT FRAMEWORK CODE.

The north-star claim (BASELINE.json:5) is "match TF-GPU PPO HalfCheetah
return in <=0.5x wall-clock". No GPU and no reference code exist on this
host (SURVEY.md section 0: empty reference mount), so that ratio was
unfalsifiable for four rounds. This harness makes it a measurement: a
minimal TensorFlow 2 PPO — the reference's execution model (graph-mode
TF via `tf.function`, MLP encoders, GAE-lambda, clipped surrogate;
BASELINE.json:5,8) — run on the SAME host, the SAME gymnasium
HalfCheetah-v5 pipeline, the SAME hyperparameters, and the SAME eval
protocol as the framework's recorded PPO run 3 (BASELINE.md: 7,872.7 @
10.24M steps; crosses 3,000 at 2.05M steps / 8.7 min on the 1-core CPU
host).

Controlled-comparison design: the env side (HostEnvPool — SyncVectorEnv
SAME_STEP autoreset, running mean/std obs normalization, discounted-
return reward scaling, greedy frozen-stats eval) is IMPORTED from the
framework, so both arms see byte-identical data pipelines and the
measured difference is the learner execution path alone: TF2 tf.function
graphs vs JAX/XLA jitted programs.

Faithful-mirror details (matched to algos/ppo.py + the run-3 CLI in
scripts/round4_queue.sh):
  E=16 envs, T=256 (4,096 steps/iter), 10 epochs x 32 minibatches of 128,
  gamma .99, GAE-lambda .95, clip .2 (flat), value-clip .2, value_coef .5,
  entropy 0, global-norm clip .5, Adam(eps=1e-5), lr 3e-4 -> 0 linear over
  2500 iters x 320 optimizer steps, hidden (256,256) tanh with orthogonal
  init (sqrt(2) torsos, 0.01 policy head, 1.0 value head), separate
  actor/critic torsos, state-independent log_std init 0, per-minibatch
  advantage normalization, truncation-aware GAE (reward + gamma *
  V(final_obs) on truncation), V(last_obs) bootstrap, raw actions clipped
  to the Box by the pool.

TF is given its idiomatic best shot: the rollout policy step, the
minibatch update, and the greedy eval action are all `tf.function`
graphs (traced once per shape); GAE runs in numpy exactly as the TF1-era
genre did. TF's default CPU threading is left untouched. Run with an
otherwise-idle host, like the JAX run it is compared against:

  JAX_PLATFORMS=cpu python bench/tf_baseline.py \
      --metrics runs/tf_baseline_ppo_hc.jsonl

Emits per-iteration JSONL and a final one-line summary JSON with
steps/sec, wall-clock-to-3000 (if crossed), and the ratio against the
recorded JAX-arm numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import tensorflow as tf  # noqa: E402

from actor_critic_tpu.algos.host_loop import (  # noqa: E402
    EpisodeTracker,
    host_collect,
    host_evaluate,
)
from actor_critic_tpu.envs.host_pool import HostEnvPool  # noqa: E402

# The JAX arm this baseline is measured against (BASELINE.md PPO run 3,
# 1-core CPU host, identical config): effective env-steps/sec and
# wall-clock to the 3,000 greedy-eval target.
JAX_ARM = {
    "steps_per_sec": 10_240_000 / (42.8 * 60.0),  # ~3,988
    "secs_to_3000": 8.7 * 60.0,
    "steps_to_3000": 2_048_000,
}


def ortho_init(shape, gain, rng):
    """Orthogonal initializer matching flax.nn.initializers.orthogonal."""
    a = rng.normal(size=(shape[0], shape[1]))
    q, r = np.linalg.qr(a if shape[0] >= shape[1] else a.T)
    q = q * np.sign(np.diag(r))
    if shape[0] < shape[1]:
        q = q.T
    return (gain * q[: shape[0], : shape[1]]).astype(np.float32)


class PPONet(tf.Module):
    """Separate-torso Gaussian actor-critic MLP (mirrors
    models/networks.py ActorCriticGaussian: tanh torsos, orthogonal init,
    state-independent log_std)."""

    def __init__(self, obs_dim: int, act_dim: int, hidden=(256, 256), seed=0):
        super().__init__()
        rng = np.random.default_rng(seed)
        self.vars_pi, self.vars_vf = [], []
        for torso, store in (("pi", self.vars_pi), ("vf", self.vars_vf)):
            d_in = obs_dim
            for i, h in enumerate(hidden):
                store.append(
                    tf.Variable(
                        ortho_init((d_in, h), np.sqrt(2.0), rng),
                        name=f"{torso}_w{i}",
                    )
                )
                store.append(tf.Variable(tf.zeros([h]), name=f"{torso}_b{i}"))
                d_in = h
        self.w_mean = tf.Variable(
            ortho_init((hidden[-1], act_dim), 0.01, rng), name="policy_w"
        )
        self.b_mean = tf.Variable(tf.zeros([act_dim]), name="policy_b")
        self.w_v = tf.Variable(ortho_init((hidden[-1], 1), 1.0, rng), name="value_w")
        self.b_v = tf.Variable(tf.zeros([1]), name="value_b")
        self.log_std = tf.Variable(tf.zeros([act_dim]), name="log_std")

    @staticmethod
    def _torso(x, store):
        for w, b in zip(store[0::2], store[1::2]):
            x = tf.tanh(tf.linalg.matmul(x, w) + b)
        return x

    def dist_value(self, obs):
        mean = tf.linalg.matmul(self._torso(obs, self.vars_pi), self.w_mean) + self.b_mean
        value = tf.linalg.matmul(self._torso(obs, self.vars_vf), self.w_v) + self.b_v
        return mean, self.log_std, value[:, 0]


LOG_2PI = float(np.log(2.0 * np.pi))


def gaussian_log_prob(mean, log_std, x):
    z = (x - mean) * tf.exp(-log_std)
    return tf.reduce_sum(-0.5 * (z * z + LOG_2PI) - log_std, axis=-1)


def gae_numpy(rewards, values, dones, bootstrap, gamma, lam):
    """Truncation-folded GAE (mirror of ops/returns.gae): `rewards`
    already carry the gamma*V(final_obs) truncation bootstrap."""
    T = rewards.shape[0]
    adv = np.zeros_like(rewards)
    adv_next = np.zeros(rewards.shape[1], rewards.dtype)
    v_next = bootstrap
    for t in range(T - 1, -1, -1):
        nonterm = 1.0 - dones[t]
        delta = rewards[t] + gamma * v_next * nonterm - values[t]
        adv_next = delta + gamma * lam * nonterm * adv_next
        adv[t] = adv_next
        v_next = values[t]
    return adv, adv + values


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--iterations", type=int, default=625,
                   help="4,096 env-steps each (default 625 = 2.56M steps, "
                        "just past the JAX arm's 2.05M crossing point)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--eval-every", type=int, default=125,
                   help="JAX run-3 cadence (512k steps)")
    p.add_argument("--eval-envs", type=int, default=8)
    p.add_argument("--log-every", type=int, default=5)
    p.add_argument("--max-minutes", type=float, default=150.0,
                   help="hard wall cap; summary marks capped=true")
    p.add_argument("--metrics", type=str, default="runs/tf_baseline_ppo_hc.jsonl")
    p.add_argument("--hidden", type=str, default="256,256")
    args = p.parse_args()

    E, T, EPOCHS, MB = 16, 256, 10, 32
    GAMMA, LAM, CLIP, VF_CLIP, VCOEF, MAXGN = 0.99, 0.95, 0.2, 0.2, 0.5, 0.5
    LR0, TOTAL_OPT_STEPS = 3e-4, 2500 * EPOCHS * MB
    hidden = tuple(int(h) for h in args.hidden.split(","))
    B = T * E
    mb_size = B // MB

    np.random.seed(args.seed)
    tf.random.set_seed(args.seed)
    rng = np.random.default_rng(args.seed + 0x5EED)

    pool = HostEnvPool("HalfCheetah-v5", E, seed=args.seed)
    obs_dim = pool.spec.obs_shape[0]
    act_dim = pool.spec.action_dim
    net = PPONet(obs_dim, act_dim, hidden, seed=args.seed)
    opt = tf.keras.optimizers.Adam(learning_rate=LR0, epsilon=1e-5)
    tf_gen = tf.random.Generator.from_seed(args.seed)

    @tf.function
    def policy_step(obs):
        mean, log_std, value = net.dist_value(obs)
        eps = tf_gen.normal(tf.shape(mean))
        action = mean + tf.exp(log_std) * eps
        return action, gaussian_log_prob(mean, log_std, action), value

    @tf.function
    def values_of(obs):
        return net.dist_value(obs)[2]

    @tf.function
    def greedy_act(obs):
        return net.dist_value(obs)[0]

    @tf.function
    def train_minibatch(obs, action, logp_old, v_old, adv, ret, lr):
        a_mean = tf.reduce_mean(adv)
        a_std = tf.math.reduce_std(adv)
        adv_n = (adv - a_mean) / (a_std + 1e-8)
        with tf.GradientTape() as tape:
            mean, log_std, value = net.dist_value(obs)
            logp = gaussian_log_prob(mean, log_std, action)
            ratio = tf.exp(logp - logp_old)
            surr1 = ratio * adv_n
            surr2 = tf.clip_by_value(ratio, 1.0 - CLIP, 1.0 + CLIP) * adv_n
            pg_loss = -tf.reduce_mean(tf.minimum(surr1, surr2))
            v_clipped = v_old + tf.clip_by_value(value - v_old, -VF_CLIP, VF_CLIP)
            v_loss = 0.5 * tf.reduce_mean(
                tf.maximum((value - ret) ** 2, (v_clipped - ret) ** 2)
            )
            loss = pg_loss + VCOEF * v_loss
        grads = tape.gradient(loss, net.trainable_variables)
        grads, _ = tf.clip_by_global_norm(grads, MAXGN)
        opt.learning_rate.assign(lr)
        opt.apply_gradients(zip(grads, net.trainable_variables))
        return loss, pg_loss, v_loss

    eval_pool = pool.eval_pool(args.eval_envs)
    tracker = EpisodeTracker(E)
    metrics_path = Path(args.metrics)
    metrics_path.parent.mkdir(parents=True, exist_ok=True)
    log_f = metrics_path.open("a")

    def act_fn(o):
        a, lp, v = policy_step(tf.constant(o, tf.float32))
        return np.asarray(a), {"log_prob": np.asarray(lp), "value": np.asarray(v)}

    obs = pool.reset()
    t0 = time.monotonic()
    opt_step = 0
    iter_times: list[float] = []
    crossed_at = None  # (env_steps, wall_secs)
    capped = False

    for it in range(args.iterations):
        it_t0 = time.monotonic()
        obs, block = host_collect(pool, obs, T, act_fn, tracker)
        t_collect = time.monotonic() - it_t0

        bootstrap = np.asarray(values_of(tf.constant(obs, tf.float32)))
        fobs = block["final_obs"].reshape(B, obs_dim)
        final_values = np.asarray(
            values_of(tf.constant(fobs, tf.float32))
        ).reshape(T, E)
        truncated = block["done"] * (1.0 - block["terminated"])
        rewards = block["reward"] + GAMMA * final_values * truncated
        adv, ret = gae_numpy(
            rewards, block["value"], block["done"], bootstrap, GAMMA, LAM
        )

        flat = {
            "obs": block["obs"].reshape(B, obs_dim),
            "action": block["action"].reshape(B, act_dim),
            "logp": block["log_prob"].reshape(B),
            "v_old": block["value"].reshape(B),
            "adv": adv.reshape(B),
            "ret": ret.reshape(B),
        }
        tensors = {k: tf.constant(v, tf.float32) for k, v in flat.items()}
        for _ in range(EPOCHS):
            perm = rng.permutation(B)
            for m in range(MB):
                idx = tf.constant(perm[m * mb_size : (m + 1) * mb_size])
                lr = LR0 * max(0.0, 1.0 - opt_step / TOTAL_OPT_STEPS)
                train_minibatch(
                    tf.gather(tensors["obs"], idx),
                    tf.gather(tensors["action"], idx),
                    tf.gather(tensors["logp"], idx),
                    tf.gather(tensors["v_old"], idx),
                    tf.gather(tensors["adv"], idx),
                    tf.gather(tensors["ret"], idx),
                    tf.constant(lr, tf.float32),
                )
                opt_step += 1
        iter_wall = time.monotonic() - it_t0
        iter_times.append(iter_wall)
        env_steps = (it + 1) * B

        row = None
        if (it + 1) % args.eval_every == 0:
            ev = host_evaluate(
                eval_pool, lambda o: np.asarray(greedy_act(tf.constant(o, tf.float32)))
            )
            row = {"eval_return": ev}
            if ev >= 3000.0 and crossed_at is None:
                crossed_at = (env_steps, time.monotonic() - t0)
        if row is not None or (it + 1) % args.log_every == 0:
            rec = {
                "iter": it + 1,
                "env_steps": env_steps,
                "wall_secs": round(time.monotonic() - t0, 2),
                "iter_secs": round(iter_wall, 3),
                "collect_secs": round(t_collect, 3),
                **tracker.report(),
                **(row or {}),
            }
            log_f.write(json.dumps(rec) + "\n")
            log_f.flush()
        if (time.monotonic() - t0) / 60.0 > args.max_minutes:
            capped = True
            break

    wall = time.monotonic() - t0
    final_eval = host_evaluate(
        eval_pool, lambda o: np.asarray(greedy_act(tf.constant(o, tf.float32)))
    )
    steady = iter_times[1:] or iter_times  # drop the tracing iteration
    sps = B / float(np.median(steady))
    summary = {
        "arm": "tf2_cpu_reference_shaped_ppo",
        "tf_version": tf.__version__,
        "env_steps": (it + 1) * B,
        "wall_secs": round(wall, 1),
        "steps_per_sec_median": round(sps, 1),
        "final_eval_return": round(final_eval, 1),
        "secs_to_3000": round(crossed_at[1], 1) if crossed_at else None,
        "steps_to_3000": crossed_at[0] if crossed_at else None,
        "capped": capped,
        "jax_arm": JAX_ARM,
        "tf_over_jax_steps_per_sec": round(sps / JAX_ARM["steps_per_sec"], 3),
        "jax_over_tf_wall_to_3000": (
            round(JAX_ARM["secs_to_3000"] / crossed_at[1], 3) if crossed_at else None
        ),
    }
    log_f.write(json.dumps({"summary": summary}) + "\n")
    log_f.close()
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
