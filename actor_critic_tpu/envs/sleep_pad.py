"""Sleep-padded gym testbed env for the host-pool and actor tests.

The sharded pool's win is overlapping per-env simulator WALL time, but
CI has no MuJoCo-scale simulator and the container may be single-core —
a CPU-bound env would show no multi-process speedup there. `SleepPadEnv`
pads every step with a `time.sleep(sleep_s)` (wall-bound, zero CPU), so
worker overlap is real on any host. Dynamics are a deterministic drift
on a 4-dim state, seeded through gymnasium's `np_random`, so it also
serves the sharded-vs-sync trajectory-equivalence tests.

`crash_at_step > 0` raises inside `step()` once that many steps have run
in the env instance — the injection point for the worker-crash-surfaces-
as-error tests (a wedged pool must raise, never hang).

Make it from any process (sharded workers included) via gymnasium's
module-import id syntax — the module registers the env at import:

    gym.make("actor_critic_tpu.envs.sleep_pad:SleepPad-v0", sleep_s=0.002)
"""

from __future__ import annotations

import time
from typing import Optional

import gymnasium as gym
import numpy as np
from gymnasium import spaces

ENV_ID = "SleepPad-v0"
# The full id workers can gym.make with no prior registration import.
QUALIFIED_ENV_ID = f"{__name__}:{ENV_ID}"


class SleepPadEnv(gym.Env):
    metadata: dict = {"render_modes": []}

    def __init__(
        self,
        sleep_s: float = 0.0,
        horizon: int = 200,
        crash_at_step: int = 0,
    ):
        self.observation_space = spaces.Box(-np.inf, np.inf, (4,), np.float32)
        self.action_space = spaces.Discrete(2)
        self._sleep_s = float(sleep_s)
        self._horizon = int(horizon)
        self._crash_at_step = int(crash_at_step)
        self._t = 0
        self._lifetime_steps = 0
        self._state = np.zeros(4, np.float32)

    def reset(self, *, seed: Optional[int] = None, options=None):
        super().reset(seed=seed)
        self._t = 0
        self._state = self.np_random.uniform(-1.0, 1.0, size=4).astype(
            np.float32
        )
        return self._state.copy(), {}

    def step(self, action):
        self._lifetime_steps += 1
        if self._crash_at_step and self._lifetime_steps >= self._crash_at_step:
            raise RuntimeError(
                "SleepPadEnv: injected crash at lifetime step "
                f"{self._lifetime_steps} (crash_at_step={self._crash_at_step})"
            )
        if self._sleep_s > 0:
            time.sleep(self._sleep_s)
        self._t += 1
        drift = np.float32(0.01) * (np.float32(int(action)) * 2.0 - 1.0)
        self._state = (self._state + drift).astype(np.float32)
        reward = float(action)
        truncated = self._t >= self._horizon
        return self._state.copy(), reward, False, truncated, {}


CARTPOLE_ENV_ID = "SleepPadCartPole-v0"
QUALIFIED_CARTPOLE_ID = f"{__name__}:{CARTPOLE_ENV_ID}"


class SleepPadCartPoleEnv(gym.Env):
    """CartPole-v1 with a per-step wall-time pad: REAL dynamics (so a
    learner can be judged on eval return) under a simulator-shaped wall
    cost. `scripts/launch_multihost.py --straggler-rank` pads one
    process's envs further to make a straggler while the rest run at
    the base pad — a sync fleet slows to the straggler's pace at its
    barrier; a gossip fleet does not. A plain delegating Env (not
    gym.Wrapper): registered entry points need a class-level `metadata`
    dict."""

    metadata: dict = {"render_modes": []}

    def __init__(self, sleep_s: float = 0.0):
        self._env = gym.make("CartPole-v1")
        self._sleep_s = float(sleep_s)
        self.observation_space = self._env.observation_space
        self.action_space = self._env.action_space

    def reset(self, *, seed: Optional[int] = None, options=None):
        return self._env.reset(seed=seed, options=options)

    def step(self, action):
        if self._sleep_s > 0:
            time.sleep(self._sleep_s)
        return self._env.step(action)

    def close(self):
        self._env.close()


if ENV_ID not in gym.registry:
    gym.register(
        id=ENV_ID,
        entry_point="actor_critic_tpu.envs.sleep_pad:SleepPadEnv",
    )
if CARTPOLE_ENV_ID not in gym.registry:
    gym.register(
        id=CARTPOLE_ENV_ID,
        entry_point="actor_critic_tpu.envs.sleep_pad:SleepPadCartPoleEnv",
    )
