"""Heterogeneous scenario-mixture fleet: many env TYPES, one XLA program.

The tentpole of ISSUE 11. PR 8's scenario fleet randomizes parameters of
ONE env type; this module steps a fleet that mixes different env TYPES —
CartPole + Pendulum + Acrobot + the procedural maze family — inside a
single fused program, the GA3C/Accelerated-Methods move (arxiv
1611.06256, 1803.02811: the parallelism lives in large-batch device-side
heterogeneous batching) applied to training:

- **Padded shared obs interface**: each member's vector obs is
  zero-padded to the width of the widest member; the per-type validity
  mask is a static [n_types, obs_max] table (`MixtureEnv.obs_masks`,
  indexable by the per-instance type ids) so consumers can distinguish
  "this lane is zero" from "this lane does not exist". Padding is
  mask-MULTIPLIED, not just concatenated, so padded lanes are exactly
  0.0 by construction regardless of member behavior — the collection
  blocks the fused rollout scan gathers are mask-clean without any
  per-algo special-casing.
- **Discrete/continuous action adapter**: the mixture presents ONE
  discrete action space of width A = max over members (a discrete
  member's action count; `action_bins` levels for a continuous member).
  A discrete member takes `action % n_i`; a continuous member maps the
  index onto `linspace(-1, 1, action_bins)` in its normalized action
  convention — discretized control, the standard adapter for mixing a
  torque env into a discrete-policy fleet.
- **`lax.switch` over per-type step/reset fns**: every instance carries
  an int32 `type_id` in its state plus one state slot PER member type;
  branch i steps member i (through its own auto-resetting, scenario
  re-drawing `step`) and passes the other slots through untouched.
  Under `vmap` the switch lowers to a select over all branches — each
  instance pays the summed member step cost, the known price of SIMD
  heterogeneity; the win is that the WHOLE fleet stays inside one
  compiled program with zero host round-trips.
- **Type-preserving auto-reset**: an episode end re-rolls the member's
  scenario params from the instance's own PRNG stream (the member's
  `auto_reset` does this already) while the type id is preserved. With
  `redraw_types=True` (the curriculum mode) the end of an episode
  additionally re-draws the instance's TYPE from the `weights`
  distribution carried in the state — a traced input, so shifting the
  distribution never recompiles — and fresh-resets the newly drawn
  member; when the draw lands on the same type, the member's own
  auto-reset result is kept bit-for-bit, which is what makes a
  single-type mixture exactly equal to the homogeneous member fleet
  (tested in tests/test_mixture.py).

Curriculum (ISSUE 11): `Curriculum`/`CurriculumController` implement the
host-side schedule — stage s advances to s+1 when learner eval progress
crosses `thresholds[s]`, installing `stage_weights[s]` into the fleet
via `set_fleet_weights` (weights AND stage ride the env state inside the
train state, so orbax checkpoints carry them and a resumed run continues
the schedule; `CurriculumController.sync` re-aligns the host counter
from the restored state). `parse_curriculum` owns the `--curriculum`
spec grammar: `"THRESHOLD:w0,w1,..;THRESHOLD:w0,w1,.."`.

Per-type eval matrix: `make_typed_eval` builds ONE jitted eval program
whose fleet is pinned to a traced `type_id` (`reset_typed`), so the
per-type return/solved matrix costs one compile total, not one per
type; `scripts/run_report.py` renders it and the sampler-registry gauge
`mixture_eval` exports it at `/metrics`. The program is AOT-warmed via
the `mixture.make_typed_eval` registry planner below.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from actor_critic_tpu.envs.jax_env import EnvSpec, JaxEnv, StepOutput


def member_makers() -> dict[str, Callable[..., JaxEnv]]:
    """Name → maker for every env type a mixture can include (vector-obs
    members only; lazy so importing this module stays light)."""
    from actor_critic_tpu.envs.acrobot import make_acrobot
    from actor_critic_tpu.envs.cartpole import make_cartpole
    from actor_critic_tpu.envs.maze import make_maze
    from actor_critic_tpu.envs.pendulum import make_pendulum

    return {
        "cartpole": make_cartpole,
        "pendulum": make_pendulum,
        "acrobot": make_acrobot,
        "maze": make_maze,
    }


# Per-member "solved" bars for the eval matrix gauges (greedy eval
# return at or above the bar counts as solved). CartPole's is the
# repo's 475 certification bar; the others are the conventional
# classic-control bars / a reached-the-goal maze return.
SOLVE_BARS: dict[str, float] = {
    "cartpole": 475.0,
    "pendulum": -300.0,
    "acrobot": -100.0,
    "maze": 0.0,
}


def parse_mixture_spec(spec) -> list[tuple[str, float]]:
    """`"cartpole*2,pendulum,acrobot"` → [(name, weight), ...].

    Weights default to 1; `name*W` sets the type's draw weight (the
    `--env mixture:cartpole*2,pendulum` spelling). Order defines the
    type-id numbering. Duplicates are rejected (one state slot per
    TYPE; weight the draw instead of repeating the member)."""
    if isinstance(spec, str):
        parts = [p.strip() for p in spec.split(",") if p.strip()]
    else:
        parts = [str(p) for p in spec]
    if not parts:
        raise ValueError("mixture spec names no members")
    valid = member_makers()
    out: list[tuple[str, float]] = []
    for part in parts:
        name, _, w = part.partition("*")
        name = name.strip()
        if name not in valid:
            raise ValueError(
                f"unknown mixture member {name!r}; valid: {sorted(valid)}"
            )
        if any(name == n for n, _ in out):
            raise ValueError(
                f"duplicate mixture member {name!r} — weight the draw "
                f"('{name}*2') instead of repeating the member"
            )
        try:
            weight = float(w) if w else 1.0
        except ValueError:
            raise ValueError(f"bad weight in mixture member {part!r}")
        if weight < 0 or (w and weight != weight):
            raise ValueError(f"mixture weight must be >= 0, got {part!r}")
        out.append((name, weight))
    if not any(weight > 0 for _, weight in out):
        raise ValueError("mixture weights must not all be zero")
    return out


class MixtureState(NamedTuple):
    """Per-instance fleet state: the active type, one state slot per
    member type (only the active slot is live; the others are parked at
    their last episode start), the mixture-level PRNG key (type
    re-draws only — member streams stay untouched, preserving bitwise
    equivalence with homogeneous fleets), and the curriculum-controlled
    draw distribution + stage (traced, so re-weighting never
    recompiles; checkpointed with the train state)."""

    type_id: jax.Array
    members: tuple
    key: jax.Array
    weights: jax.Array  # [n_types] f32 draw weights
    stage: jax.Array    # int32 curriculum stage


@dataclasses.dataclass(frozen=True, eq=False)
class MixtureEnv(JaxEnv):
    """A JaxEnv whose fleet mixes member types, plus the mixture-only
    surface: member metadata, the static obs-validity mask table,
    type-pinned resets for the per-type eval matrix, and the initial
    draw weights (`eq=False` keeps JaxEnv's identity hash)."""

    member_names: tuple[str, ...] = ()
    member_specs: tuple[EnvSpec, ...] = ()
    obs_masks: Any = None            # [n_types, obs_max] f32
    init_weights: tuple[float, ...] = ()
    reset_typed: Optional[Callable] = None  # (key, type_id) -> (state, obs)
    redraw_types: bool = False

    @property
    def n_types(self) -> int:
        return len(self.member_names)


def make_mixture(
    members: Any = "cartpole,pendulum,acrobot,maze",
    randomize: float = 0.0,
    action_bins: int = 5,
    redraw_types: bool = False,
    member_kwargs: Optional[dict] = None,
) -> MixtureEnv:
    """Build the heterogeneous mixture fleet env.

    `members` is a spec string (`"cartpole*2,pendulum,acrobot"`) or a
    name sequence; `randomize` is forwarded to every member's scenario
    draw; `action_bins` sets the discretization of continuous members'
    action range; `redraw_types` re-draws an instance's TYPE from the
    state-carried weights at each episode end (required for the
    curriculum; off by default so types are preserved across
    auto-reset). `member_kwargs` maps member name → extra maker kwargs
    (e.g. {"maze": {"size": 6}}).
    """
    if action_bins < 2:
        raise ValueError(f"action_bins must be >= 2, got {action_bins}")
    parsed = parse_mixture_spec(members)
    names = tuple(n for n, _ in parsed)
    init_weights = tuple(w for _, w in parsed)
    makers = member_makers()
    member_kwargs = dict(member_kwargs or {})
    unknown = sorted(set(member_kwargs) - set(names))
    if unknown:
        raise ValueError(
            f"member_kwargs for non-member(s) {unknown}; members: {names}"
        )
    envs = tuple(
        makers[n](randomize=randomize, **member_kwargs.get(n, {}))
        for n in names
    )
    n = len(envs)
    for name, e in zip(names, envs):
        if len(e.spec.obs_shape) != 1:
            raise ValueError(
                f"mixture members need vector obs; {name!r} has shape "
                f"{e.spec.obs_shape}"
            )
    widths = tuple(e.spec.obs_shape[0] for e in envs)
    obs_max = max(widths)
    masks = jnp.asarray(
        [[1.0] * w + [0.0] * (obs_max - w) for w in widths], jnp.float32
    )
    n_actions = tuple(
        e.spec.action_dim if e.spec.discrete else action_bins for e in envs
    )
    action_dim = max(n_actions)
    levels = jnp.linspace(-1.0, 1.0, action_bins, dtype=jnp.float32)

    def _pad(i: int, obs: jax.Array) -> jax.Array:
        # Mask-multiplied zero pad: padded lanes are exactly 0.0 even if
        # a member emitted NaN/garbage outside its width (there is no
        # such member today; the multiply is the contract, not a patch).
        return jnp.pad(obs, (0, obs_max - widths[i])) * masks[i]

    def _adapt(i: int, action: jax.Array):
        a = action.astype(jnp.int32)
        if envs[i].spec.discrete:
            return a % n_actions[i]
        # Continuous member: discretized normalized action. Members use
        # the scale-to-bounds convention (e.g. pendulum maps [-1, 1]
        # onto its torque range), matching levels' range.
        u = levels[a % action_bins]
        return jnp.full((envs[i].spec.action_dim,), u, jnp.float32)

    def _make_step_branch(i: int):
        def branch(members_tuple, action):
            out = envs[i].step(members_tuple[i], _adapt(i, action))
            new_members = (
                members_tuple[:i] + (out.state,) + members_tuple[i + 1:]
            )
            return (
                new_members,
                _pad(i, out.obs),
                out.reward.astype(jnp.float32),
                out.done,
                out.info["terminated"],
                _pad(i, out.info["final_obs"]),
            )
        return branch

    def _make_reset_branch(i: int):
        def branch(members_tuple, key):
            s, o = envs[i].reset(key)
            return (
                members_tuple[:i] + (s,) + members_tuple[i + 1:],
                _pad(i, o),
            )
        return branch

    step_branches = [_make_step_branch(i) for i in range(n)]
    reset_branches = [_make_reset_branch(i) for i in range(n)]

    def _fresh(key: jax.Array, type_id: jax.Array, weights: jax.Array):
        ks = jax.random.split(key, n + 1)
        states, obss = [], []
        for i, e in enumerate(envs):
            s, o = e.reset(ks[i])
            states.append(s)
            obss.append(_pad(i, o))
        obs = jnp.stack(obss)[type_id]
        state = MixtureState(
            type_id=type_id.astype(jnp.int32),
            members=tuple(states),
            key=ks[n],
            weights=weights,
            stage=jnp.zeros((), jnp.int32),
        )
        return state, obs

    init_w = jnp.asarray(init_weights, jnp.float32)

    def reset(key: jax.Array):
        key, tkey = jax.random.split(key)
        # Guarded normalization (ISSUE 14, nonfinite-hazard): an
        # all-zero weight vector (a curriculum stage zeroing every
        # type) would make the draw probabilities 0/0 = nan — and a
        # bare denominator floor would silently bias every draw to
        # type 0; degrade to a UNIFORM draw instead (visible, unbiased).
        # Bit-identical for any real (positive-sum) weight vector.
        s = jnp.sum(init_w)
        type_id = jax.random.choice(
            tkey, n,
            p=jnp.where(s > 0, init_w / jnp.maximum(s, 1e-6), 1.0 / n),
        )
        return _fresh(key, type_id, init_w)

    def reset_typed(key: jax.Array, type_id: jax.Array):
        # Type-pinned fleet for the per-type eval matrix: one-hot
        # weights so redraw_types keeps the pin across episode ends.
        # type_id is TRACED — one compiled eval program covers every
        # type (the compile-once contract, tests/test_compile_cache.py).
        type_id = jnp.asarray(type_id, jnp.int32)
        return _fresh(key, type_id, jax.nn.one_hot(type_id, n))

    def step(state: MixtureState, action: jax.Array) -> StepOutput:
        new_members, obs, reward, done, terminated, final_obs = jax.lax.switch(
            state.type_id, step_branches, state.members, action
        )
        info = {"terminated": terminated, "final_obs": final_obs}
        if not redraw_types:
            out_state = state._replace(members=new_members)
            info["type_id"] = out_state.type_id
            return StepOutput(out_state, obs, reward, done, info)

        # Curriculum mode: at an episode end, re-draw the instance's
        # type from the state-carried weights and fresh-reset the new
        # member. A draw landing on the SAME type keeps the member's
        # own auto-reset result untouched (the bitwise single-type
        # equivalence contract); only a genuine type change swaps in
        # the mixture-keyed reset.
        key, tkey, rkey = jax.random.split(state.key, 3)
        # Same guarded normalization as reset(): uniform on a zeroed
        # weight vector, bit-identical otherwise.
        ws = jnp.sum(state.weights)
        drawn = jax.random.choice(
            tkey, n,
            p=jnp.where(
                ws > 0, state.weights / jnp.maximum(ws, 1e-6), 1.0 / n
            ),
        ).astype(jnp.int32)
        new_type = jnp.where(done > 0, drawn, state.type_id)
        changed = (done > 0) & (new_type != state.type_id)
        r_members, r_obs = jax.lax.switch(
            new_type, reset_branches, new_members, rkey
        )

        def sel(a, b):
            c = changed.reshape(changed.shape + (1,) * (a.ndim - changed.ndim))
            return jnp.where(c, a, b)

        out_state = MixtureState(
            type_id=new_type,
            members=jax.tree.map(sel, r_members, new_members),
            key=key,
            weights=state.weights,
            stage=state.stage,
        )
        info["type_id"] = new_type
        return StepOutput(out_state, sel(r_obs, obs), reward, done, info)

    spec = EnvSpec(
        obs_shape=(obs_max,),
        action_dim=action_dim,
        discrete=True,
        can_truncate=any(e.spec.can_truncate for e in envs),
        episode_horizon=max(e.spec.episode_horizon for e in envs),
    )
    return MixtureEnv(
        spec=spec, reset=reset, step=step,
        member_names=names,
        member_specs=tuple(e.spec for e in envs),
        obs_masks=masks,
        init_weights=init_weights,
        reset_typed=reset_typed,
        redraw_types=redraw_types,
    )


def set_fleet_weights(env_state: MixtureState, weights, stage: int) -> MixtureState:
    """Install curriculum weights + stage into a (vmapped) fleet state —
    the host-side application point between dispatches. Shapes/dtypes
    are preserved exactly, so the jitted train step never retraces."""
    w = jnp.asarray(weights, jnp.float32)
    return env_state._replace(
        weights=jnp.broadcast_to(w, env_state.weights.shape).astype(
            env_state.weights.dtype
        ),
        stage=jnp.full_like(env_state.stage, stage),
    )


def fleet_stage(env_state: MixtureState) -> int:
    """The curriculum stage carried by a (vmapped) fleet state — the
    resume hook `CurriculumController.sync` reads."""
    return int(jnp.asarray(env_state.stage).reshape(-1)[0])


# ---------------------------------------------------------------------------
# Curriculum schedule
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Curriculum:
    """Stage s advances to s+1 when eval progress crosses
    `thresholds[s]`; entering stage s+1 installs `stage_weights[s]`
    (stage 0 runs the mixture's own init weights)."""

    thresholds: tuple[float, ...]
    stage_weights: tuple[tuple[float, ...], ...]

    def __post_init__(self):
        if len(self.thresholds) != len(self.stage_weights):
            raise ValueError(
                "curriculum needs one weight vector per threshold"
            )
        if any(
            b <= a for a, b in zip(self.thresholds, self.thresholds[1:])
        ):
            raise ValueError(
                f"curriculum thresholds must be strictly increasing, "
                f"got {self.thresholds}"
            )
        for w in self.stage_weights:
            if not any(x > 0 for x in w):
                raise ValueError("curriculum stage weights all zero")

    @property
    def n_stages(self) -> int:
        return len(self.thresholds) + 1


def parse_curriculum(spec: str, member_names: tuple[str, ...]) -> Curriculum:
    """`--curriculum` grammar: `"THR:w0,w1,..;THR:w0,w1,.."` — one
    `threshold:weights` stage per semicolon-separated entry, weights in
    member order (as many as the mixture has members)."""
    thresholds: list[float] = []
    weights: list[tuple[float, ...]] = []
    for entry in (e.strip() for e in spec.split(";")):
        if not entry:
            continue
        thr, sep, ws = entry.partition(":")
        if not sep:
            raise ValueError(
                f"curriculum stage {entry!r} is not 'THRESHOLD:w0,w1,..'"
            )
        try:
            thresholds.append(float(thr))
            w = tuple(float(x) for x in ws.split(","))
        except ValueError:
            raise ValueError(f"bad curriculum stage {entry!r}")
        if len(w) != len(member_names):
            raise ValueError(
                f"curriculum stage {entry!r} has {len(w)} weights; the "
                f"mixture has {len(member_names)} members {member_names}"
            )
        weights.append(w)
    if not thresholds:
        raise ValueError(f"curriculum spec {spec!r} names no stages")
    return Curriculum(tuple(thresholds), tuple(weights))


class CurriculumController:
    """Host-side schedule state: feed it each eval's progress metric and
    apply what it returns. Single-threaded by design (the fused loop's
    log path owns it)."""

    def __init__(self, curriculum: Curriculum):
        self.curriculum = curriculum
        self.stage = 0

    def sync(self, stage: int) -> None:
        """Re-align from a restored fleet state (resume continues the
        schedule instead of replaying stage 0)."""
        self.stage = max(self.stage, min(int(stage), self.curriculum.n_stages - 1))

    def update(self, progress: float) -> Optional[tuple[int, tuple[float, ...]]]:
        """Advance through every threshold `progress` has crossed;
        returns (new stage, weights to install) when the stage moved,
        None otherwise. Stages only ever move forward — a later bad
        eval never demotes the fleet."""
        advanced = None
        cur = self.curriculum
        while (
            self.stage < len(cur.thresholds)
            and progress >= cur.thresholds[self.stage]
        ):
            self.stage += 1
            advanced = (self.stage, cur.stage_weights[self.stage - 1])
        return advanced


# ---------------------------------------------------------------------------
# Per-type eval matrix
# ---------------------------------------------------------------------------

def make_typed_eval(env: MixtureEnv, net):
    """Greedy per-type eval program: `eval_fn(state, key, type_id,
    num_envs=16, num_steps=...)` evaluates the CURRENT policy on a
    fleet pinned to `type_id` (traced — one program serves every type;
    jit with static_argnums=(3, 4)). `net` is the actor-critic network
    whose `apply(params, obs) → (dist, value)` and whose params live at
    `state.params` (a2c/ppo/impala)."""
    from actor_critic_tpu.algos.common import default_eval_steps, evaluate

    default_steps = default_eval_steps(env)

    def act(params, obs):
        dist, _ = net.apply(params, obs)
        return dist.mode()

    def eval_fn(state, key, type_id, num_envs: int = 16,
                num_steps: int = default_steps):
        type_id = jnp.asarray(type_id, jnp.int32)
        return evaluate(
            env, act, state.params, key, num_envs, num_steps,
            reset_fn=lambda k: env.reset_typed(k, type_id),
        )

    return eval_fn


def eval_matrix_row(name: str, ret: float) -> dict[str, float]:
    """Flat gauge fields for one member's eval result (flat so the
    Prometheus exporter's one-level dict flattening renders them)."""
    bar = SOLVE_BARS.get(name)
    row = {f"{name}_return": round(float(ret), 3)}
    if bar is not None:
        row[f"{name}_solved"] = float(ret >= bar)
    return row


# -- AOT warmup registry (utils/compile_cache.py, ISSUE 4) ------------------
from actor_critic_tpu.utils import compile_cache as _compile_cache  # noqa: E402


@_compile_cache.register_warmup("mixture.make_typed_eval")
def _typed_eval_planner(ctx):
    """Warm the per-type eval program for fused mixture runs with eval
    on (the train/eval step programs themselves are warmed by the
    per-algo `<algo>.make_train_step`/`make_eval_fn` planners, which
    already take the mixture env through `ctx.env`)."""
    if not ctx.fused or ctx.eval_every <= 0:
        return None
    if not isinstance(ctx.env, MixtureEnv):
        return None
    modules = {"a2c": "a2c", "ppo": "ppo", "impala": "impala",
               "a3c": "impala"}
    if ctx.algo not in modules:
        return None
    import importlib

    mod = importlib.import_module(
        f"actor_critic_tpu.algos.{modules[ctx.algo]}"
    )
    state_abs = _compile_cache.fused_state_struct(ctx, mod.init_state)
    ev = jax.jit(
        make_typed_eval(ctx.env, mod.make_network(ctx.env, ctx.cfg)),
        static_argnums=(3, 4),
    )
    k = _compile_cache.key_struct()
    t = _compile_cache.scalar_struct(jnp.int32)
    return lambda: _compile_cache.aot_compile(ev, state_abs, k, t)
