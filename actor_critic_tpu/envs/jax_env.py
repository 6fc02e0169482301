"""Pure-JAX environment protocol — the on-device rollout substrate.

The reference steps host environments (gym classic-control / MuJoCo / ALE)
one process boundary away from the device (SURVEY.md §3.1 boundary
analysis; reference mount empty, SURVEY.md §0). On TPU that ping-pong is
the throughput killer, so the framework's first-class env interface is a
*functional* one: `reset` and `step` are pure jit-safe functions over an
explicit state pytree, vmapped over thousands of env instances and fused
into the training step (north star ≥1M steps/s, BASELINE.json:5).

Conventions:
- `reset(key) -> (state, obs)`;
  `step(state, action) -> (state, obs, reward, done, info)`.
- `done` is 1.0 at a step that *ends* the episode (termination OR
  truncation); `info["terminated"]` distinguishes true termination so GAE
  can bootstrap through time-limit truncations.
- `step` must auto-reset: when an episode ends, the returned state/obs are
  from a fresh episode (the returned `obs` is the new episode's first obs;
  the pre-reset terminal obs is in `info["final_obs"]`). This keeps the
  vmapped batch rectangular with no host intervention.
- Everything is float32; shapes static; randomness via explicit keys
  threaded in `state`.

Scenario fleet (ISSUE 8): envs that support domain randomization carry a
per-instance `ScenarioParams`-style NamedTuple of physics scalars INSIDE
their state pytree, drawn in `reset` from configurable ranges
(`scenario_ranges` / `draw_scenario` below). Because the params live in
the state, the existing `jax.vmap(env.reset)` / `jax.vmap(env.step)`
fleet path needs no protocol change: thousands of instances with
different masses/lengths/force scales step inside ONE fused XLA program,
and `auto_reset`'s end-of-episode reset re-draws a fresh scenario from
the instance's own PRNG stream — per-episode re-randomization, the
standard domain-randomization regime. Same key ⇒ same draw (tested in
tests/test_scenarios.py), so fleets are reproducible.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class StepOutput(NamedTuple):
    state: Any  # env state pytree (post auto-reset)
    obs: jax.Array
    reward: jax.Array
    done: jax.Array  # 1.0 where episode ended this step (term or trunc)
    info: dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class EnvSpec:
    """Static metadata a trainer needs to build networks."""

    obs_shape: tuple[int, ...]
    action_dim: int  # num discrete actions, or continuous action dims
    discrete: bool
    obs_dtype: Any = jnp.float32
    # False ⇒ episodes only ever terminate (never time-limit truncate), so
    # trainers can statically skip the truncation-bootstrap forward pass.
    can_truncate: bool = True
    # Upper bound on episode length (the time-limit), 0 = unknown. Eval
    # programs size their rollout horizon from this so a good policy's
    # still-running episodes are never cut (and then wrongly excluded
    # from the finished-episode mean — common.evaluate docstring).
    episode_horizon: int = 0
    # How many observations from a reset no action decides (a token env's
    # prompt), which `JaxEnv.prefill` yields at once; 0: none.
    prefill_len: int = 0

    @property
    def pixel_obs(self) -> bool:
        """Whether observations are image-shaped ([H, W, C]) — the single
        rule every algorithm's make_network uses to pick the Nature CNN
        over the MLP torso (keep it here, not copy-pasted per algo)."""
        return len(self.obs_shape) == 3


@dataclasses.dataclass(frozen=True)
class JaxEnv:
    """A pure-functional environment: a spec plus reset/step closures.

    Instances are static (hashable) so they can be closed over by jitted
    trainers without retracing.
    """

    spec: EnvSpec
    reset: Callable[[jax.Array], tuple[Any, jax.Array]]
    step: Callable[[Any, jax.Array], StepOutput]
    # Where `spec.prefill_len` = P > 0: `prefill(state at a reset) -> (state
    # at the last of the episode's first P observations, those observations
    # [P, ...])`, as P - 1 steps would give them whatever the actions.
    prefill: Optional[Callable[[Any], tuple[Any, jax.Array]]] = None

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


def scenario_ranges(
    defaults: dict[str, float],
    randomize: float = 0.0,
    overrides: dict[str, Any] | None = None,
) -> dict[str, tuple[float, float]]:
    """Resolve per-parameter (lo, hi) draw ranges for a scenario fleet.

    `randomize=r` widens every default d to [d·(1−r), d·(1+r)] — the one
    knob that makes a whole fleet heterogeneous (`--env-set
    randomize=0.3`). `overrides` then pins individual params: a (lo, hi)
    pair / list, a "lo,hi" string (the `--env-set masspole=0.05,0.5`
    spelling — env-set coerces unrecognized values to str), or a bare
    number to FIX the param at a non-default value. randomize <= 0 with
    no overrides returns degenerate [d, d] ranges (the deterministic
    single-scenario env).
    """
    if randomize < 0:
        raise ValueError(f"randomize must be >= 0, got {randomize}")
    out = {}
    for name, d in defaults.items():
        r = abs(d) * randomize
        out[name] = (d - r, d + r)
    for name, val in (overrides or {}).items():
        if name not in defaults:
            raise ValueError(
                f"unknown scenario parameter {name!r}; "
                f"valid: {sorted(defaults)}"
            )
        if val is None:
            continue
        if isinstance(val, str):
            parts = [p for p in val.split(",") if p.strip()]
            vals = tuple(float(p) for p in parts)
        elif isinstance(val, (tuple, list)):
            vals = tuple(float(v) for v in val)
        else:
            vals = (float(val),)
        if len(vals) == 1:
            out[name] = (vals[0], vals[0])
        elif len(vals) == 2:
            out[name] = (min(vals), max(vals))
        else:
            raise ValueError(
                f"scenario range for {name!r} must be a number or "
                f"lo,hi pair, got {val!r}"
            )
    return out


def draw_scenario(key: jax.Array, ranges: dict[str, tuple[float, float]]) -> dict[str, jax.Array]:
    """One uniform draw per parameter from `ranges`, each from its own
    stream folded on a stable CRC32 of the parameter NAME — not a
    positional index, so adding or removing a parameter never perturbs
    the draws of the others. Deterministic in `key`: the scenario-fleet
    reproducibility contract. Returns {name: f32 scalar}."""
    import zlib

    out = {}
    for name in sorted(ranges):
        lo, hi = ranges[name]
        if lo == hi:
            # Degenerate range: emit the exact constant — float blends
            # like (1−u)·lo + u·hi need not round back to it, and the
            # gymnasium-parity tests compare against exact constants.
            out[name] = jnp.asarray(lo, jnp.float32)
            continue
        sub = jax.random.fold_in(
            key, zlib.crc32(name.encode()) & 0x7FFFFFFF
        )
        out[name] = jax.random.uniform(
            sub, (), jnp.float32, minval=lo, maxval=hi
        )
    return out


def is_randomized(ranges: dict[str, tuple[float, float]]) -> bool:
    """Whether any parameter's range is non-degenerate (lo < hi)."""
    return any(lo != hi for lo, hi in ranges.values())


def auto_reset(
    reset_fn: Callable[[jax.Array], tuple[Any, jax.Array]],
    raw_step: Callable[[Any, jax.Array], tuple[Any, jax.Array, jax.Array, jax.Array, jax.Array]],
    key_of_state: Callable[[Any], jax.Array],
) -> Callable[[Any, jax.Array], StepOutput]:
    """Wrap a raw step (no reset logic) into the auto-resetting protocol.

    `raw_step(state, action) -> (state, obs, reward, terminated, truncated)`.
    On done, replaces state/obs with a fresh `reset` (keyed off the env
    state's PRNG key) via `lax.cond`-free `tree.map(where)` select — branchless,
    so the vmapped batch stays a single fused program.
    """

    def step(state, action) -> StepOutput:
        nstate, obs, reward, terminated, truncated = raw_step(state, action)
        done = jnp.maximum(terminated, truncated)
        key = key_of_state(nstate)
        reset_key, _ = jax.random.split(key)
        rstate, robs = reset_fn(reset_key)

        def select(a, b):
            d = done.reshape(done.shape + (1,) * (a.ndim - done.ndim))
            return jnp.where(d.astype(jnp.bool_), a, b)

        out_state = jax.tree.map(select, rstate, nstate)
        out_obs = select(robs, obs)
        return StepOutput(
            state=out_state,
            obs=out_obs,
            reward=reward,
            done=done,
            info={"terminated": terminated, "final_obs": obs},
        )

    return step
