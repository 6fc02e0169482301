"""Host environment pool: gymnasium/MuJoCo behind the JaxEnv-like protocol.

The reference steps single host envs inline with the session loop
(SURVEY.md §3.1-3.2; reference mount empty, §0). Here host envs are a
batched pool (SyncVectorEnv, SAME_STEP autoreset) whose step/reset
semantics mirror envs/jax_env.py exactly — `done` marks the ending step,
`final_obs` carries the pre-reset observation, the returned obs is the
new episode's — so trainers see one protocol regardless of backend.

Includes the genre-standard MuJoCo preprocessing (SURVEY §2.1 "Env
wrappers"): running mean/std observation normalization (clipped) and
discounted-return-scale reward normalization, both checkpointable via
`get_state`/`set_state`.

On this machine the host has a single CPU core (SURVEY §7.0), so the pool
is the throughput-limiting path by design; the trainers overlap device
compute with host stepping where it matters (SURVEY §7.2 item 2).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

from actor_critic_tpu.envs.jax_env import EnvSpec


class RunningMeanStd:
    """Welford-style running mean/variance over batches (float64 host-side)."""

    def __init__(self, shape: tuple[int, ...]):
        self.mean = np.zeros(shape, np.float64)
        self.var = np.ones(shape, np.float64)
        self.count = 1e-4

    def update(self, x: np.ndarray) -> None:
        bmean = x.mean(axis=0)
        bvar = x.var(axis=0)
        bcount = x.shape[0]
        delta = bmean - self.mean
        tot = self.count + bcount
        self.mean = self.mean + delta * bcount / tot
        m_a = self.var * self.count
        m_b = bvar * bcount
        m2 = m_a + m_b + delta**2 * self.count * bcount / tot
        self.var = m2 / tot
        self.count = tot

    def normalize(self, x: np.ndarray, clip: float) -> np.ndarray:
        z = (x - self.mean) / np.sqrt(self.var + 1e-8)
        return np.clip(z, -clip, clip).astype(np.float32)

    def state_dict(self) -> dict[str, Any]:
        return {"mean": self.mean, "var": self.var, "count": self.count}

    def load_state_dict(self, d: dict[str, Any]) -> None:
        self.mean = np.asarray(d["mean"], np.float64)
        self.var = np.asarray(d["var"], np.float64)
        self.count = float(d["count"])


@dataclasses.dataclass
class HostStepOutput:
    obs: np.ndarray          # post-reset obs (normalized)
    reward: np.ndarray       # normalized reward
    raw_reward: np.ndarray   # unnormalized (for episode-return reporting)
    done: np.ndarray         # 1.0 where episode ended this step
    terminated: np.ndarray   # true termination (cuts bootstrap)
    final_obs: np.ndarray    # pre-reset obs (normalized); == obs if not done


def scalable_bounds(discrete: bool, low, high) -> bool:
    """Whether an action space supports the [-1,1]→Box affine map: a
    continuous Box with finite bounds (an infinite bound would make the
    mid/half-range constants inf/nan and every scaled action nan)."""
    return not discrete and bool(
        np.isfinite(low).all() and np.isfinite(high).all()
    )


class HostEnvPool:
    """Batched gymnasium envs with normalization, one `step(actions)` call.

    Actions: for Box spaces the policy's raw (Gaussian) actions are clipped
    to the space bounds; for Discrete they pass through as int arrays.
    With `scale_actions=True` the pool instead treats policy actions as
    normalized [-1, 1] and affine-maps them onto the Box bounds — the
    standard tanh-policy convention. This keeps the REPLAYED action
    consistent with the EXECUTED one on envs whose bounds are narrower
    than [-1, 1] (Humanoid-v5's ±0.4: clipping executes ±0.4 while the
    buffer stores the raw sample, so Q(s,a) trains on actions that were
    never taken; scaling removes the mismatch and restores full actuator
    authority). Off by default: recorded runs used clip semantics, and
    the flag must never change under a resumed process.

    `workers=W > 1` shards the gym backend's E envs across W worker
    processes (envs/shard_pool.py): shared-memory step exchange, global
    per-env seeding, SAME_STEP autoreset per shard — trajectories AND
    normalization statistics identical to `workers=1` at fixed seeds,
    but slow simulator steps overlap across workers. `workers=1`
    (default) is the in-process SyncVectorEnv, unchanged.
    """

    def __init__(
        self,
        env_id: str,
        num_envs: int,
        seed: int = 0,
        normalize_obs: bool = True,
        normalize_reward: bool = True,
        clip_obs: float = 10.0,
        clip_reward: float = 10.0,
        gamma: float = 0.99,
        backend: str = "gym",
        pixel_preprocess: bool = False,
        scale_actions: bool = False,
        env_kwargs: dict | None = None,
        workers: int = 1,
    ):
        self.env_id = env_id
        self.num_envs = num_envs
        env_kwargs = dict(env_kwargs or {})
        if pixel_preprocess and backend != "gym":
            raise ValueError("pixel_preprocess applies to the gym backend only")
        if env_kwargs and backend != "gym":
            raise ValueError(
                "env_kwargs go to gym.make; the native engine takes none"
            )
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        if workers > 1 and backend != "gym":
            raise ValueError(
                "workers applies to the gym backend only (the native "
                "engine already steps the whole batch in one C call)"
            )
        self._workers = int(workers)
        if backend == "native":
            # First-party C++ batched engine: one C call per batch step
            # (envs/native_pool.py; native/vecenv.cpp).
            from actor_critic_tpu.envs.native_pool import NativeVecEnv

            self._envs = NativeVecEnv(env_id, num_envs)
        elif backend == "gym":
            if self._workers > 1:
                # Sharded multi-process pool (envs/shard_pool.py): same
                # env factory, same SAME_STEP semantics per shard, global
                # per-env seeding — trajectories match the workers=1 path
                # bit-for-bit at fixed seeds (tests/test_shard_pool.py).
                from actor_critic_tpu.envs.shard_pool import ShardedVecEnv

                self._envs = ShardedVecEnv(
                    env_id, num_envs, workers=self._workers,
                    env_kwargs=env_kwargs,
                    pixel_preprocess=pixel_preprocess,
                )
            else:
                from gymnasium.vector import AutoresetMode, SyncVectorEnv

                from actor_critic_tpu.envs.shard_pool import make_host_env

                self._envs = SyncVectorEnv(
                    [
                        (lambda: make_host_env(
                            env_id, env_kwargs, pixel_preprocess
                        ))
                        for _ in range(num_envs)
                    ],
                    autoreset_mode=AutoresetMode.SAME_STEP,
                )
        else:
            raise ValueError(f"backend must be 'gym' or 'native', got {backend!r}")
        try:
            space = self._envs.single_action_space
            obs_space = self._envs.single_observation_space
            self._discrete = hasattr(space, "n")
            if self._discrete:
                action_dim = int(space.n)
                self._act_low = self._act_high = None
            else:
                action_dim = int(np.prod(space.shape))
                self._act_low = np.asarray(space.low, np.float32)
                self._act_high = np.asarray(space.high, np.float32)
            if scale_actions and not scalable_bounds(
                self._discrete, self._act_low, self._act_high
            ):
                raise ValueError(
                    "scale_actions needs a finite continuous action Box"
                )
        except Exception:
            # The backend is already live (sharded pools hold worker
            # PROCESSES and a registered sampler gauge) — a validation
            # failure must tear it down, not leak it.
            self._envs.close()
            raise
        self._scale_actions = scale_actions
        if scale_actions:
            self._act_mid = 0.5 * (self._act_high + self._act_low)
            self._act_half = 0.5 * (self._act_high - self._act_low)
        # uint8 pixel obs keep their dtype (the CNN's /255 branch fires on
        # it); everything else is delivered as float32 regardless of the
        # env's native dtype — MuJoCo emits float64, and letting that flow
        # into host buffers/transfers would double memory for no benefit.
        raw_dtype = np.dtype(obs_space.dtype)
        self.spec = EnvSpec(
            obs_shape=tuple(obs_space.shape),
            action_dim=action_dim,
            discrete=self._discrete,
            can_truncate=True,
            obs_dtype=raw_dtype if raw_dtype == np.uint8 else np.dtype(np.float32),
        )
        self._seed = seed
        self._normalize_obs = normalize_obs
        self._normalize_reward = normalize_reward
        self._clip_obs = clip_obs
        self._clip_reward = clip_reward
        self._gamma = gamma
        self._frozen_stats = False
        self.obs_rms = RunningMeanStd(tuple(obs_space.shape))
        self.ret_rms = RunningMeanStd(())
        self._returns = np.zeros(num_envs, np.float64)
        self._backend = backend
        self._pixel_preprocess = pixel_preprocess
        self._env_kwargs = env_kwargs

    @property
    def normalizes_obs(self) -> bool:
        """Whether observations are normalized with running stats — part of
        the pool's public contract because resume-time compatibility checks
        (algos/host_loop.host_resume) depend on it."""
        return self._normalize_obs

    @property
    def scales_actions(self) -> bool:
        """Whether policy actions are affine-mapped from [-1,1] onto the
        action Box (vs clipped) — public for the same resume-time
        compatibility checks as `normalizes_obs`."""
        return self._scale_actions

    def eval_pool(self, num_envs: int = 4, seed: int = 1234) -> "HostEnvPool":
        """A companion pool for greedy evaluation: same env/backend and the
        SAME obs-normalization statistics (shared by reference, read-only —
        eval must see the training policy's input distribution), raw
        rewards (no reward normalization), fresh episodes."""
        pool = HostEnvPool(
            self.env_id, num_envs, seed=seed,
            normalize_obs=self._normalize_obs, normalize_reward=False,
            clip_obs=self._clip_obs, gamma=self._gamma,
            backend=self._backend, pixel_preprocess=self._pixel_preprocess,
            scale_actions=self._scale_actions,
            env_kwargs=self._env_kwargs,
            # Eval pools inherit the sharding (capped by their smaller E).
            workers=min(self._workers, num_envs),
        )
        pool.obs_rms = self.obs_rms  # aliased on purpose; frozen below
        pool._frozen_stats = True
        return pool

    # -- normalization ----------------------------------------------------
    def _norm_obs(self, obs: np.ndarray, update: bool = True) -> np.ndarray:
        if not self._normalize_obs:
            # uint8 pixel obs must reach the CNN encoder as uint8 so its
            # /255 branch fires (models/networks.py; same contract as
            # envs/pong.py); any other dtype is cast to float32 to match
            # spec.obs_dtype (float64 MuJoCo obs must not reach buffers).
            obs = np.asarray(obs)
            return obs if obs.dtype == np.uint8 else obs.astype(np.float32)
        obs = np.asarray(obs, np.float32)
        if update and not self._frozen_stats:
            self.obs_rms.update(obs)
        return self.obs_rms.normalize(obs, self._clip_obs)

    def _norm_reward(self, reward: np.ndarray, done: np.ndarray) -> np.ndarray:
        reward = np.asarray(reward, np.float64)
        if not self._normalize_reward:
            return reward.astype(np.float32)
        self._returns = self._returns * self._gamma * (1.0 - done) + reward
        self.ret_rms.update(self._returns)
        scaled = reward / np.sqrt(self.ret_rms.var + 1e-8)
        return np.clip(scaled, -self._clip_reward, self._clip_reward).astype(
            np.float32
        )

    # -- protocol ---------------------------------------------------------
    def reset(self) -> np.ndarray:
        obs, _ = self._envs.reset(seed=self._seed)
        self._returns[:] = 0.0
        return self._norm_obs(obs)

    def step(self, actions: np.ndarray) -> HostStepOutput:
        actions = np.asarray(actions)
        if self._discrete:
            actions = actions.astype(np.int64)
        elif self._scale_actions:
            a = np.clip(actions.astype(np.float32), -1.0, 1.0)
            actions = self._act_mid + self._act_half * a
        else:
            actions = np.clip(
                actions.astype(np.float32), self._act_low, self._act_high
            )
        obs, reward, term, trunc, info = self._envs.step(actions)
        term = np.asarray(term)
        trunc = np.asarray(trunc)
        done = (term | trunc).astype(np.float32)

        raw_obs = np.asarray(obs)
        fos = info.get("final_obs")
        if isinstance(fos, np.ndarray) and fos.dtype != object:
            # Native engine and the sharded pool: full [E, ...] numeric
            # array, already correct for non-done envs — no obs copy, no
            # per-env loop. Dtype-preserving (astype to the env's obs
            # dtype): uint8 pixel final_obs must stay uint8 here.
            final_obs = fos.astype(raw_obs.dtype, copy=False)
        else:
            # gymnasium object array of Optional rows (or no done envs):
            # start from a dtype-preserving obs copy, patch done rows.
            final_obs = raw_obs.copy()
            if fos is not None:
                for i, fo in enumerate(fos):
                    if fo is not None:
                        final_obs[i] = fo

        nobs = self._norm_obs(obs)
        # final_obs normalized with the SAME stats, not updating them twice.
        if self._normalize_obs:
            nfinal = self.obs_rms.normalize(final_obs, self._clip_obs)
        elif final_obs.dtype == np.uint8:  # same dtype policy as _norm_obs
            nfinal = final_obs
        else:
            nfinal = final_obs.astype(np.float32)
        nreward = self._norm_reward(reward, done)
        return HostStepOutput(
            obs=nobs,
            reward=nreward,
            raw_reward=np.asarray(reward, np.float32),
            done=done,
            terminated=term.astype(np.float32),
            final_obs=nfinal,
        )

    # -- telemetry ---------------------------------------------------------
    def drain_telemetry(self) -> int:
        """Relay the sharded backend's buffered per-worker span records
        into the installed telemetry session (envs/shard_pool.py); 0 for
        backends without worker processes."""
        fn = getattr(self._envs, "drain_telemetry", None)
        return 0 if fn is None else fn()

    def worker_stats(self) -> Optional[list[dict]]:
        """Per-worker step accounting (sharded backend only)."""
        fn = getattr(self._envs, "worker_stats", None)
        return None if fn is None else fn()

    # -- checkpointable state --------------------------------------------
    def get_state(self) -> dict[str, Any]:
        return {
            "obs_rms": self.obs_rms.state_dict(),
            "ret_rms": self.ret_rms.state_dict(),
            "returns": self._returns.copy(),
        }

    def set_state(self, state: dict[str, Any]) -> None:
        self.obs_rms.load_state_dict(state["obs_rms"])
        self.ret_rms.load_state_dict(state["ret_rms"])
        self._returns = np.asarray(state["returns"], np.float64).copy()

    def close(self) -> None:
        self._envs.close()
