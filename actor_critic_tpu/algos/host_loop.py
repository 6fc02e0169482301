"""Shared host-env rollout plumbing for the `train_host` paths.

PPO (on-policy), DDPG/TD3 and SAC (off-policy) all step a `HostEnvPool`
from a host loop (SURVEY.md §3.1-3.2 host boundary; reference mount
empty, §0) and need the same bookkeeping: stack per-step arrays into a
time-major [K, E] block for the single host→device transfer, and track
raw episode returns for reporting. This module owns both so the trainers
don't each carry a diverging copy.
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import Callable, Optional

import numpy as np

from actor_critic_tpu import telemetry


class EpisodeTracker:
    """Raw-return episode accounting across host steps."""

    def __init__(self, num_envs: int):
        self._ep_ret = np.zeros(num_envs)
        self.finished: list[float] = []

    def update(self, raw_reward: np.ndarray, done: np.ndarray) -> None:
        self._ep_ret += raw_reward
        for i in np.nonzero(done)[0]:
            # jaxlint: disable=host-sync (numpy episode accounting — no
            # device value; the coercion below is host-only)
            self.finished.append(float(self._ep_ret[i]))
            self._ep_ret[i] = 0.0

    def report(self, window: int = 20) -> dict[str, float]:
        return {
            "recent_return": (
                float(np.mean(self.finished[-window:]))
                if self.finished
                else float("nan")
            ),
            "episodes": float(len(self.finished)),
        }


class MergedEpisodeTracker:
    """Read-only `report()` view over several actors' EpisodeTrackers.

    The async actor–learner driver (ppo.train_host_async / ISSUE 6)
    runs one EpisodeTracker per actor thread; the learner's log rows
    want ONE recent-return figure across the fleet. Reads the tail of
    each tracker's `finished` list (appends from actor threads are
    atomic; a row that lands mid-read shows up next log row).
    """

    def __init__(self, trackers: list[EpisodeTracker]):
        self._trackers = trackers

    def report(self, window: int = 20) -> dict[str, float]:
        # Mean over EACH actor's last `window` episodes (up to A·window
        # entries) — truncating the concatenation to one window would
        # silently drop every actor but the last-listed one as soon as
        # it alone fills the window (straggler layouts are exactly the
        # case where actors finish episodes at very different rates).
        recent: list[float] = []
        total = 0
        for t in self._trackers:
            finished = t.finished
            total += len(finished)
            recent.extend(finished[-window:])
        return {
            "recent_return": (
                float(np.mean(recent)) if recent else float("nan")
            ),
            "episodes": float(total),
        }


class BlockBuffers:
    """Preallocated, double-buffered time-major [K, E, ...] block storage.

    The old collect path appended per-step arrays to Python lists and
    `np.stack`ed them into a fresh block every iteration — one full-block
    allocation + copy per iteration, forever. BlockBuffers instead writes
    each step straight into preallocated [K, E, ...] arrays (allocated
    lazily from the first recorded value's shape/dtype, then reused).

    DOUBLE buffering is the correctness half: `begin_block()` flips
    between two buffer sets, so the arrays handed to the device transfer
    for block N stay untouched while block N+1 is collected into the
    other set. That lets the (async-dispatched) host→device transfer and
    jitted update of block N overlap collection of block N+1 — the
    transfer-stage extension of the `overlap=True` stale-params
    machinery; a block's buffers are only rewritten two `begin_block()`s
    later, after its update has long been consumed.
    """

    def __init__(self, num_steps: int):
        if num_steps < 1:
            raise ValueError(f"num_steps must be >= 1, got {num_steps}")
        self.num_steps = int(num_steps)
        self._bufs: tuple[dict, dict] = ({}, {})
        self._active = 0
        self._seen: set[str] = set()

    def begin_block(self) -> None:
        """Flip to the other buffer set; its previous contents (block
        N-2) are dead by contract."""
        self._active ^= 1
        self._seen = set()

    def record(self, t: int, name: str, value) -> None:
        value = np.asarray(value)
        buf = self._bufs[self._active]
        arr = buf.get(name)
        if (
            arr is None
            or arr.shape[1:] != value.shape
            or arr.dtype != value.dtype
        ):
            arr = np.empty((self.num_steps, *value.shape), value.dtype)
            buf[name] = arr
        arr[t] = value  # copies into the preallocated slot
        self._seen.add(name)

    def block(self) -> dict[str, np.ndarray]:
        """The CURRENT block's arrays: only keys recorded since
        `begin_block()` — a key an earlier block recorded but this one
        didn't must not leak two-block-stale data into the update."""
        buf = self._bufs[self._active]
        return {k: buf[k] for k in buf if k in self._seen}


def host_collect(
    pool,
    obs: np.ndarray,
    num_steps: int,
    act_fn: Callable[[np.ndarray], tuple[np.ndarray, dict[str, np.ndarray]]],
    tracker: EpisodeTracker,
    buffers: Optional[BlockBuffers] = None,
) -> tuple[np.ndarray, dict[str, np.ndarray]]:
    """Step the pool `num_steps` times; return (last obs, [K, E] block).

    `act_fn(obs) -> (action, extras)`; extras (e.g. log_prob/value for
    on-policy) are recorded alongside the standard fields. The block's
    arrays are time-major [K, E, ...] numpy — exactly one device
    transfer's worth, written into `buffers` (a loop-lived BlockBuffers;
    the trainers pass one so blocks reuse preallocated double-buffered
    storage). With `buffers=None` a private BlockBuffers is allocated
    per call — correct, just without the reuse.
    """
    if buffers is None:
        buffers = BlockBuffers(num_steps)
    elif buffers.num_steps != num_steps:
        raise ValueError(
            f"buffers hold {buffers.num_steps}-step blocks, collect asked "
            f"for {num_steps}"
        )
    buffers.begin_block()
    record = buffers.record

    from actor_critic_tpu.utils import watchdog

    # Per-worker spans, only while a telemetry session is installed: the
    # sharded pool relays the workers' OWN per-step records after the
    # block (drain_telemetry — real pid lanes in the trace; 0 records
    # for backends without worker processes).
    drain_fn = None
    if telemetry.current() is not None:
        drain_fn = getattr(pool, "drain_telemetry", None)

    # One span per collection block, not per pool step: a MuJoCo run
    # takes millions of env steps, and the per-phase breakdown needs the
    # block total, not 10^6 micro-events.
    with telemetry.span("env_step", steps=num_steps):
        for t in range(num_steps):
            watchdog.beat()  # progress heartbeat (utils/watchdog.py)
            action, extras = act_fn(obs)
            out = pool.step(action)
            record(t, "obs", obs)
            record(t, "action", action)
            for k, v in extras.items():
                record(t, k, v)
            record(t, "reward", out.reward)
            record(t, "done", out.done)
            record(t, "terminated", out.terminated)
            record(t, "final_obs", out.final_obs)
            tracker.update(out.raw_reward, out.done)
            obs = out.obs

    if drain_fn is not None:
        # Worker→parent relay: the workers buffered one span per batch
        # step during the block; one drain round-trip per worker ships
        # them into spans.jsonl under the workers' real pids.
        try:
            drain_fn()
        except RuntimeError:
            raise  # dead worker: same contract as a step failure
        except Exception:
            pass  # telemetry must never take the run down

    return obs, buffers.block()


def host_evaluate(
    pool,
    act_fn: Callable[[np.ndarray], np.ndarray],
    max_steps: int = 1000,
) -> float:
    """Greedy host eval: mean RAW return of each env's FIRST episode
    (host counterpart of common.evaluate; SURVEY.md §3.4). `act_fn(obs)
    -> action` is the deterministic policy. Stops early once every env
    has finished an episode."""
    from actor_critic_tpu.utils import watchdog

    obs = pool.reset()
    E = pool.num_envs
    returns = np.zeros(E)
    alive = np.ones(E)
    for _ in range(max_steps):
        watchdog.beat()  # an eval sweep is progress, not a stall
        out = pool.step(act_fn(obs))
        returns += out.raw_reward * alive
        alive *= 1.0 - out.done
        obs = out.obs
        if not alive.any():
            break
    return float(returns.mean())


def host_ckpt_state(pool, save_replay: bool = True, **device_state) -> dict:
    """Assemble the host-trainer checkpoint pytree: the device-side state
    (learner/params/opt/key/env_steps) plus the pool's normalizer stats,
    every leaf coerced to an array so orbax round-trips it.

    `save_replay=False` strips the learner's replay ring down to a
    one-slot stub (SURVEY §5.4 scopes buffer checkpointing as optional):
    a Humanoid-scale ring is ~3 GB per save, untenable at a real save
    cadence. Resuming such a checkpoint restarts with an EMPTY buffer —
    the warmup gate (`size >= batch_size`) pauses updates for the few
    iterations the fresh ring needs to refill, then training continues
    on fresh experience only.
    """
    if not save_replay and "learner" in device_state:
        device_state = dict(device_state)
        device_state["learner"] = strip_replay(device_state["learner"])
    return {
        **device_state,
        "pool": np_tree(pool.get_state()),
    }


def strip_replay(learner):
    """Learner with its replay storage truncated to one slot (shape and
    dtype preserved so save/restore templates stay structurally stable;
    cursors ride along but are discarded on reattach). The quantizer's
    running mean/scale stats (`ReplayState.quant`, replay/quantize.py)
    are deliberately NOT touched: they are item-shaped (no capacity
    axis), cost bytes, and must survive a replay-free checkpoint — a
    resumed run re-encodes fresh transitions against the SAME
    standardization the restored critic trained under, instead of
    re-learning stats that would decode early post-resume batches
    through a different affine map."""
    import jax

    rb = learner.replay
    return learner._replace(
        replay=rb._replace(
            storage=jax.tree.map(lambda x: x[:1], rb.storage)
        )
    )


def np_tree(d):
    """Recursively np.asarray every leaf (python floats → 0-d arrays)."""
    if isinstance(d, dict):
        return {k: np_tree(v) for k, v in d.items()}
    return np.asarray(d)


from actor_critic_tpu.utils.cadence import should_save  # noqa: E402, F401


def host_maybe_save(
    ckpt, it: int, save_every: int, num_iterations: int, pool, metrics: dict,
    save_replay: bool = True, **device_state,
) -> None:
    """Save the host-trainer state on the `should_save` cadence (`it` is
    1-based). Syncs the device state first; the orbax device→host fetch
    is synchronous within save(), so donation in the next iteration is
    safe, and the disk write completes asynchronously."""
    if ckpt is None or not should_save(it, save_every, num_iterations):
        return
    with telemetry.span("checkpoint", step=it):
        _host_save(ckpt, it, pool, metrics, save_replay, device_state)


def _host_save(ckpt, it, pool, metrics, save_replay, device_state):
    import jax

    jax.block_until_ready(device_state)
    # The pool's action convention and the replay-saved flag ride the
    # tolerant metrics JSON (NOT the state tree: adding a leaf there
    # would structurally invalidate every pre-existing checkpoint under
    # orbax's exact-template restore) so host_resume can warn on a
    # convention flip and resume can build the matching template.
    metrics = {
        **(metrics or {}),
        "_pool_scale_actions": float(getattr(pool, "scales_actions", False)),
        "_replay_saved": float(save_replay),
    }
    ckpt.save(
        it, host_ckpt_state(pool, save_replay=save_replay, **device_state),
        metrics=metrics, force=True,
    )


def _warn_restore_mismatch(restored_pool: dict, pool, saved_scale) -> None:
    """The resume-contract warnings shared by the single-pool and
    async multi-pool restore paths: action-convention flips and
    normalization-contract flips must never degrade in silence.

    Normalization check: a checkpoint whose obs-normalizer accumulated
    real statistics came from a run that FED NORMALIZED observations to
    the networks. Resuming it into a raw-obs pool (e.g. after the
    off-policy default flipped to normalize_obs=False) silently puts
    the restored policy/critic off-distribution. (The flags themselves
    are not checkpointed, so the stats are the only available signal.)
    """
    try:
        saved_count = float(np.asarray(restored_pool["obs_rms"]["count"]))
    except (KeyError, TypeError):
        saved_count = 0.0
    if saved_scale is not None and bool(saved_scale) != getattr(
        pool, "scales_actions", False
    ):
        warnings.warn(
            "resuming a checkpoint trained under the "
            f"{'scaled' if saved_scale else 'clipped'}-action convention "
            "into a pool with scale_actions="
            f"{getattr(pool, 'scales_actions', False)} — the restored "
            "policy's actions will execute differently than they trained. "
            "Relaunch with the run's original --scale-actions setting.",
            stacklevel=3,
        )
    trained_normalized = saved_count > 1.0
    if trained_normalized != pool.normalizes_obs:
        was, now = (
            ("with obs normalization", "normalize_obs=False")
            if trained_normalized
            else ("on RAW observations", "normalize_obs=True")
        )
        warnings.warn(
            f"resuming a checkpoint trained {was} into a pool with {now} "
            "— the restored networks will act off-distribution (their "
            "observation scaling no longer matches the pool's). Rebuild "
            f"the pool with normalize_obs={trained_normalized} (or "
            "restart the run from scratch).",
            stacklevel=3,
        )


def host_resume(ckpt, template: dict, pool) -> tuple[Optional[dict], int]:
    """Restore the latest host checkpoint into `template`'s structure and
    push the pool state back; (None, 0) when nothing is saved yet.

    Resume semantics on host envs: learner/params/optimizer/PRNG/
    normalizer stats restore EXACTLY; the env simulator state does not
    (gymnasium can't serialize it), so the pool restarts fresh episodes —
    same contract as the reference genre's tf.train.Saver restarts.
    """
    step = ckpt.latest_step()
    if step is None:
        return None, 0
    restored = ckpt.restore(template, step)
    pool.set_state(restored["pool"])
    _warn_restore_mismatch(
        restored["pool"], pool,
        ckpt.restore_metrics(step).get("_pool_scale_actions"),
    )
    return restored, step


def async_host_ckpt_state(pools, **device_state) -> dict:
    """Checkpoint pytree for the ASYNC actor–learner drivers: the
    device state plus ALL A per-actor pools' normalizer states (each
    actor pool runs independent running stats — saving only one would
    resume A-1 actors with wrong observation scaling; ISSUE 9
    satellite). The learner thread snapshots pool stats while actor
    threads may be mid-block: each leaf read is atomic (numpy arrays
    rebound per update), so a snapshot can at worst be one batch-update
    stale per leaf — tolerable drift for running statistics, the same
    tolerance `host_resume` already grants the +1 reset batch."""
    return {
        **device_state,
        "pools": [np_tree(p.get_state()) for p in pools],
    }


def async_host_maybe_save(
    ckpt, it: int, save_every: int, num_iterations: int, pools,
    metrics: dict, data_plane: str = "host", **device_state,
) -> None:
    """Async-driver twin of `host_maybe_save` over the whole actor
    fleet's pools (`it` is 1-based consumed-block count). Device-plane
    runs (ISSUE 13) additionally carry the trajectory ring's quantizer
    stats in `device_state["ring_quant"]` — the stripped-ring contract:
    storage is transient collection data and never saved."""
    if ckpt is None or not should_save(it, save_every, num_iterations):
        return
    import jax

    with telemetry.span("checkpoint", step=it):
        jax.block_until_ready(device_state)
        metrics = {
            **(metrics or {}),
            "_pool_scale_actions": float(
                getattr(pools[0], "scales_actions", False)
            ),
            # Resume guard: the tree carries one pool state per actor,
            # so the fleet size must match (async_host_resume checks
            # this BEFORE orbax's opaque structure-mismatch error).
            "_async_actors": float(len(pools)),
            # Same guard for the data plane: a device-plane checkpoint
            # carries a ring_quant leaf the host plane's template lacks
            # (and vice versa) — fail with advice, not an orbax
            # structure error. 1.0 = device.
            "_data_plane_device": float(data_plane == "device"),
        }
        ckpt.save(
            it, async_host_ckpt_state(pools, **device_state),
            metrics=metrics, force=True,
        )


def async_host_resume(
    ckpt, template: dict, pools, data_plane: str = "host",
) -> tuple[Optional[dict], int]:
    """Restore the latest async checkpoint and push every actor pool's
    normalizer state back; (None, 0) when nothing is saved yet. The
    saved tree must carry the same number of pool states as the resuming
    fleet (`--async-actors` must not change across a resume — each
    pool's stats belong to its own actor's env shard), and the data
    plane must match the checkpoint's (the save trees differ)."""
    step = ckpt.latest_step()
    if step is None:
        return None, 0
    saved_metrics = ckpt.restore_metrics(step)
    saved_actors = saved_metrics.get("_async_actors")
    if saved_actors is not None and int(saved_actors) != len(pools):
        raise ValueError(
            f"checkpoint carries {int(saved_actors)} actor-pool states "
            f"but this run has {len(pools)} actors — resume with the "
            "original --async-actors count"
        )
    # Missing key = a checkpoint that predates the flag, which can only
    # be host-plane (the device plane shipped with the flag) — default
    # to 0.0 so a --data-plane device resume of a legacy checkpoint
    # gets THIS advice, not orbax's opaque structure-mismatch error.
    saved_plane = saved_metrics.get("_data_plane_device", 0.0)
    if bool(saved_plane) != (data_plane == "device"):
        saved_name = "device" if saved_plane else "host"
        raise ValueError(
            f"checkpoint was written by a --data-plane {saved_name} run "
            f"but this run uses --data-plane {data_plane} — the save "
            "trees differ (the device plane checkpoints its ring's "
            "quantizer stats); resume with the original flag"
        )
    restored = ckpt.restore(template, step)
    saved_pools = restored["pools"]
    if len(saved_pools) != len(pools):
        # Fallback for checkpoints predating the _async_actors metric.
        raise ValueError(
            f"checkpoint carries {len(saved_pools)} actor-pool states "
            f"but this run has {len(pools)} actors — resume with the "
            "original --async-actors count"
        )
    saved_scale = saved_metrics.get("_pool_scale_actions")
    for pool, saved in zip(pools, saved_pools):
        pool.set_state(saved)
        _warn_restore_mismatch(saved, pool, saved_scale)
    return restored, step


def off_policy_train_host(
    pool,
    cfg,
    num_iterations: int,
    *,
    init_learner: Callable,
    make_act_fn: Callable,
    make_ingest_update: Callable,
    seed: int = 0,
    log_every: int = 10,
    log_fn: Optional[Callable[[int, dict], None]] = None,
    eval_every: int = 0,
    make_greedy_act: Optional[Callable] = None,
    eval_envs: int = 4,
    eval_steps: int = 1000,
    ckpt=None,
    save_every: int = 0,
    resume: bool = False,
    overlap: bool = True,
    make_host_explore: Optional[Callable] = None,
    make_host_greedy: Optional[Callable] = None,
    save_replay: bool = True,
):
    """Shared host-env loop for the off-policy trainers (DDPG/TD3, SAC).

    Both algorithms drive a `HostEnvPool` identically — explore-act,
    stack a [K, E] block host-side, one transfer into the jitted
    ingest+update — and differ only in the factory callables:
      init_learner(obs_shape, action_dim, cfg, key) -> learner
      make_act_fn(action_dim, cfg) -> jitted (actor_params, obs, key,
                                              env_steps) -> action
      make_ingest_update(action_dim, cfg) -> jitted (learner, block,
                                              env_steps) -> (learner, metrics)
    The learner state must expose `.actor_params`. With `eval_every > 0`
    and `make_greedy_act(action_dim, cfg) -> (params, obs) -> action`, a
    frozen-stats eval pool runs a greedy episode sweep on that cadence
    and an `eval_return` metric rides the next log row.

    With `overlap` (default) and a `make_host_explore(spec, cfg) ->
    (np_params, obs, rng, env_steps) -> action` numpy mirror
    (models/host_actor.py), collection acts entirely on the host with
    params one update stale, so the dispatched device update runs WHILE
    the next rollout is collected — the host/device overlap of SURVEY
    §7.2 item 2. Without a mirror (or overlap=False) acting round-trips
    the device each pool step and blocks on the update. Returns
    (learner, history).
    """
    import jax
    import jax.numpy as jnp

    from actor_critic_tpu.algos.common import OffPolicyTransition

    key = jax.random.key(seed)
    key, lkey = jax.random.split(key)
    learner = init_learner(pool.spec.obs_shape, pool.spec.action_dim, cfg, lkey)
    act = make_act_fn(pool.spec.action_dim, cfg)
    ingest_update = make_ingest_update(pool.spec.action_dim, cfg)

    eval_pool = greedy = host_greedy = None
    if eval_every > 0 and make_greedy_act is not None:
        eval_pool = pool.eval_pool(eval_envs)
        greedy = jax.jit(make_greedy_act(pool.spec.action_dim, cfg))
        if make_host_greedy is not None:
            from actor_critic_tpu.models import host_actor

            if host_actor.supports_mirror(jax.device_get(learner.actor_params)):
                # Evals otherwise pay a device round-trip per step
                # (× up to eval_steps).
                host_greedy = make_host_greedy(pool.spec, cfg)

    env_steps = 0
    start_it = 0
    if ckpt is not None and resume:
        # The TEMPLATE must mirror what the checkpoint actually holds:
        # the saved `_replay_saved` metric (not this run's flag) decides
        # whether the learner tree carries the full ring or the one-slot
        # stub. Legacy checkpoints (no flag) saved the full ring.
        step = ckpt.latest_step()
        saved_replay = True
        if step is not None:
            saved_replay = bool(
                ckpt.restore_metrics(step).get("_replay_saved", 1.0)
            )
        template_learner = learner if saved_replay else strip_replay(learner)
        template = host_ckpt_state(
            pool, learner=template_learner, key=key,
            env_steps=np.asarray(0, np.int64),
        )
        restored, start_it = host_resume(ckpt, template, pool)
        if restored is not None:
            restored_learner = restored["learner"]
            if not saved_replay:
                warnings.warn(
                    "resuming a replay-free checkpoint (save_replay=False): "
                    "the buffer restarts EMPTY — updates pause until it "
                    "refills past one batch, then continue on fresh "
                    "experience only.",
                    stacklevel=2,
                )
                # Reattach this run's zeroed full-capacity ring; the
                # stub's cursors are stale by construction. The restored
                # QUANTIZER stats are kept — strip_replay saved them in
                # full, and fresh transitions must encode against the
                # standardization the restored critic trained under.
                restored_learner = restored_learner._replace(
                    replay=learner.replay._replace(
                        quant=restored_learner.replay.quant
                    )
                )
            learner = restored_learner
            key = restored["key"]
            env_steps = int(restored["env_steps"])

    # reset() AFTER set_state: it re-zeroes the reward-normalizer's running
    # returns (correct — episodes restart on resume) while the restored
    # obs-normalizer stats absorb the reset batch as one ordinary update.
    obs = pool.reset()
    E = pool.num_envs
    tracker = EpisodeTracker(E)
    history: list = []
    metrics: dict = {}
    # Loop-lived double-buffered block storage: the transfer/update of
    # block N reads buffers the collection of block N+1 cannot touch.
    buffers = BlockBuffers(cfg.steps_per_iter)

    host_act = host_params = None
    if overlap and make_host_explore is not None:
        from actor_critic_tpu.models import host_actor

        np_params = jax.device_get(learner.actor_params)
        if host_actor.supports_mirror(np_params):
            host_act = make_host_explore(pool.spec, cfg)
            host_params = np_params
            rng = np.random.default_rng(seed + 0x5EED)

    # run_report "Resources" replay row: static ring-capacity facts
    # (capacity, bytes/transition vs fp32, codec mix). Static on purpose
    # — a live `size` read from the sampler thread would sync the host
    # on a donated in-flight device scalar.
    from actor_critic_tpu.replay import quantize as _quantize
    from actor_critic_tpu.telemetry import sampler as _sampler

    _replay_info = dict(
        _quantize.capacity_report(
            learner.replay,
            _quantize.offpolicy_codecs(getattr(cfg, "replay_dtype", "fp32")),
        ),
        mode=getattr(cfg, "replay_dtype", "fp32"),
    )
    _replay_gauge = _sampler.register_gauge("replay", lambda: _replay_info)
    try:
        for it in range(start_it, num_iterations):
            # Iteration boundary for any armed on-demand profile window
            # (telemetry/profiler.py): a capture starts/ends here so it
            # covers whole iterations.
            telemetry.profiler_tick()
            # Per-iteration span: the phase spans inside (env_step /
            # host_to_device / update / eval / log / checkpoint) nest
            # under it in the trace, giving per-iteration attribution.
            with telemetry.span("iteration", it=it + 1):

                if host_act is not None:

                    def explore_act(o):
                        nonlocal env_steps
                        action = host_act(host_params, o, rng, env_steps)
                        env_steps += E
                        return action, {}

                else:

                    def explore_act(o):
                        nonlocal key, env_steps
                        key, akey = jax.random.split(key)
                        # jaxlint: disable=host-sync (deliberate: without a
                        # numpy mirror the pool needs concrete host actions
                        # every step — the documented non-overlap fallback)
                        action = np.asarray(
                            act(learner.actor_params, jnp.asarray(o), akey,
                                jnp.asarray(env_steps, jnp.int32))
                        )
                        env_steps += E
                        return action, {}

                obs, block = host_collect(
                    pool, obs, cfg.steps_per_iter, explore_act, tracker,
                    buffers=buffers,
                )
                with telemetry.span("host_to_device"):
                    # jaxlint: disable=transfer-discipline (deliberate:
                    # the host plane's per-block upload — the lockstep
                    # loop transfers each collected block once by
                    # design; --data-plane device removes it, and
                    # perfsan budgets the bytes)
                    traj = OffPolicyTransition(
                        obs=jnp.asarray(block["obs"]),
                        action=jnp.asarray(block["action"]),
                        reward=jnp.asarray(block["reward"]),
                        next_obs=jnp.asarray(block["final_obs"]),
                        terminated=jnp.asarray(block["terminated"]),
                        done=jnp.asarray(block["done"]),
                    )
                if host_act is not None:
                    # Acting params for the NEXT rollout: this update's INPUT
                    # params, fetched BEFORE the dispatch (ingest_update donates
                    # the learner) — concrete already (the previous update
                    # finished during this collection), so the fetch doesn't
                    # wait, and the update dispatched below computes on-device
                    # while the next rollout is collected.
                    # jaxlint: disable=transfer-discipline (deliberate:
                    # the mirror's acting-params refresh — concrete by
                    # the overlap argument above, so no wait)
                    host_params = jax.device_get(learner.actor_params)
                # The jitted call returns at ENQUEUE time (async dispatch);
                # the span measures host-side cost only — blocking here to
                # measure device wall would cost the host/device overlap.
                with telemetry.span("update", dispatch="async"):
                    # jaxlint: disable=transfer-discipline (scalar
                    # env_steps counter rides the dispatch — 4 bytes)
                    learner, metrics = ingest_update(
                        learner, traj, jnp.asarray(env_steps, jnp.int32)
                    )
                extra = {"env_steps": env_steps}
                if eval_pool is not None and (it + 1) % eval_every == 0:
                    # NB: a fresh name — `act` is the jitted explore fn that the
                    # non-mirror explore_act closure reads late-bound; rebinding
                    # it here would crash collection after the first eval.
                    if host_greedy is not None:
                        # Blocks on the in-flight update: eval sees CURRENT params.
                        # jaxlint: disable=transfer-discipline (eval
                        # cadence, not the hot collect loop)
                        ev_params = jax.device_get(learner.actor_params)
                        # jaxlint: disable=transfer-discipline (mirror
                        # eval — np.asarray touches no device value)
                        eval_act = lambda o: np.asarray(host_greedy(ev_params, o))  # noqa: E731
                    else:
                        # jaxlint: disable=transfer-discipline (eval
                        # cadence: greedy eval must hand gym concrete
                        # host actions, once per eval step)
                        eval_act = lambda o: np.asarray(  # noqa: E731
                            greedy(learner.actor_params, jnp.asarray(o))
                        )
                    with telemetry.span("eval"):
                        extra["eval_return"] = host_evaluate(
                            eval_pool, eval_act, max_steps=eval_steps
                        )
                maybe_log(
                    it, log_every, metrics, tracker, history, log_fn,
                    extra=extra,
                    num_iterations=num_iterations,
                    # Force-log eval rows AND the first post-resume iteration (a
                    # resumed long run must produce evidence immediately, same
                    # rationale as should_log's it==1 clause).
                    force="eval_return" in extra or it == start_it,
                )
                host_maybe_save(
                    ckpt, it + 1, save_every, num_iterations, pool, metrics,
                    save_replay=save_replay,
                    learner=learner, key=key,
                    # jaxlint: disable=host-sync (python int → np scalar for
                    # the checkpoint tree; no device value is touched)
                    env_steps=np.asarray(env_steps, np.int64),
                )
        if ckpt is not None:
            ckpt.wait()  # the final async save must be durable before return
    finally:
        _sampler.unregister_gauge(_replay_gauge)
    return learner, history


def off_policy_train_host_async(
    pools,
    cfg,
    num_iterations: int,
    *,
    init_learner: Callable,
    make_ingest_update: Callable,
    make_host_explore: Callable,
    make_host_greedy: Optional[Callable] = None,
    seed: int = 0,
    log_every: int = 10,
    log_fn: Optional[Callable[[int, dict], None]] = None,
    eval_every: int = 0,
    eval_envs: int = 4,
    eval_steps: int = 1000,
    queue_depth: int = 4,
    max_staleness: Optional[int] = None,
    data_plane: str = "host",
    plane_codec: str = "fp32",
    make_device_ingest_update: Optional[Callable] = None,
    publish_hook: Optional[Callable[[int, object], None]] = None,
):
    """Async actor–learner loop for the off-policy trainers (DDPG/TD3,
    SAC) — the ROADMAP item PR 6 left open: replay absorbs behavior-
    policy staleness natively (every consumed block just lands in the
    ring; updates sample uniformly regardless of which params collected
    a transition), so only the ingest hand-off needed wiring through
    `traj_queue.ActorService`.

    One actor thread per pool explores through the numpy mirror
    (`make_host_explore(spec, cfg)`, behavior params refreshed from the
    `PolicyPublisher` once per block) and pushes `[K, E_a]` transition
    blocks; this (learner) thread drains the queue and feeds each block
    to the jitted ingest+update program. `max_staleness` defaults to
    None — dropping stale blocks would throw away valid off-policy
    experience; the queue's drop-oldest back-pressure still bounds
    memory. Each actor warms up on uniform-random actions for its share
    (`warmup_steps / A`) of the fleet warmup budget: the mirror's gate
    compares against `cfg.warmup_steps`, so the actor feeds it its own
    step count scaled by the fleet size. The update gate sees the
    FLEET's total collected steps. `num_iterations` counts blocks
    consumed. Checkpointing is not wired for this mode (per-actor pools
    carry independent normalizer state; the PPO async driver grew the
    multi-pool save tree first — see ppo.train_host_async).

    `data_plane="device"` (ISSUE 13): actors stage encoded blocks in
    the HBM `data_plane.DeviceTrajRing` (codec per `plane_codec`) and
    `make_device_ingest_update(action_dim, cfg, ring_codecs)` — the
    per-algo factory ddpg/sac pass — builds the jitted program that
    gathers + decodes the slot, scatters it into the replay ring, and
    updates, with zero host→device transfers per consumed block.

    Returns (learner, history).
    """
    import threading

    import jax
    import jax.numpy as jnp

    from actor_critic_tpu.algos.common import OffPolicyTransition
    from actor_critic_tpu.algos.traj_queue import (
        ActorService,
        PolicyPublisher,
        TrajQueue,
        consume_block,
        validate_pools,
    )
    from actor_critic_tpu.models import host_actor

    spec, E_a = validate_pools(pools)
    A = len(pools)

    key = jax.random.key(seed)
    key, lkey = jax.random.split(key)
    learner = init_learner(spec.obs_shape, spec.action_dim, cfg, lkey)
    np_params = jax.device_get(learner.actor_params)
    if not host_actor.supports_mirror(np_params):
        raise ValueError(
            "async actor–learner mode needs the numpy actor mirror "
            "(MLP torso; models/host_actor.py)"
        )
    if data_plane not in ("host", "device"):
        raise ValueError(
            f"data_plane must be 'host' or 'device', got {data_plane!r}"
        )
    use_device_plane = data_plane == "device"
    if use_device_plane and make_device_ingest_update is None:
        raise ValueError(
            "data_plane='device' needs the algo's make_device_ingest_update "
            "factory (ddpg/sac pass it through train_host_async)"
        )
    host_explore = make_host_explore(spec, cfg)

    def actor_act_factory(actor_id: int):
        # Per-actor step counter, read/written only on that actor's
        # thread; scaled by A it approximates the fleet total, so the
        # mirror's `env_steps < warmup_steps` gate hands each actor its
        # 1/A share of the uniform-random warmup budget.
        counter = {"steps": 0}

        def make_act_fn(actor_params, rng):
            def act(o):
                action = host_explore(
                    actor_params, o, rng, counter["steps"] * A
                )
                counter["steps"] += np.asarray(o).shape[0]
                return action, {}

            return act

        return make_act_fn

    if use_device_plane:
        from actor_critic_tpu.data_plane import device_replay
        from actor_critic_tpu.data_plane import ring as dp_ring

        queue = dp_ring.DeviceTrajRing(
            depth=queue_depth,
            block_spec=device_replay.offpolicy_block_spec(spec, cfg, A),
            codec=plane_codec,
            max_staleness=max_staleness,
            policy="drop_oldest",
        )
        ingest_update = make_device_ingest_update(
            spec.action_dim, cfg, queue.codecs
        )
    else:
        queue = TrajQueue(
            depth=queue_depth, max_staleness=max_staleness,
            policy="drop_oldest",
        )
        ingest_update = make_ingest_update(spec.action_dim, cfg)
    publisher = PolicyPublisher(np_params, version=0)
    stop = threading.Event()
    actors = [
        ActorService(
            i, pool, queue, publisher, cfg.steps_per_iter,
            actor_act_factory(i),
            rng=np.random.default_rng(seed + 0x5EED + i * 7919),
            stop=stop,
        )
        for i, pool in enumerate(pools)
    ]

    eval_pool = host_greedy = None
    if eval_every > 0 and make_host_greedy is not None:
        eval_pool = pools[-1].eval_pool(eval_envs)
        host_greedy = make_host_greedy(spec, cfg)

    history: list = []
    metrics: dict = {}
    trackers = MergedEpisodeTracker([a.tracker for a in actors])
    try:
        for a in actors:
            a.start()
        for it in range(num_iterations):
            telemetry.profiler_tick()
            for a in actors:
                if a.error is not None:
                    raise RuntimeError(
                        f"actor {a.actor_id} died"
                    ) from a.error
            with telemetry.span("iteration", it=it + 1):
                queue.set_consumer_version(it)
                with telemetry.span("queue_wait", it=it + 1):
                    block = consume_block(queue, actors)
                # Behavior params for the actors' NEXT blocks: this
                # update's INPUT params, fetched BEFORE the donating
                # dispatch below (concrete — the previous update
                # finished during collection).
                # jaxlint: disable=transfer-discipline (deliberate: the
                # per-block behavior-params publish IS the async
                # contract — concrete by the overlap argument above)
                np_behavior = jax.device_get(learner.actor_params)
                publisher.publish(np_behavior, version=it)
                if publish_hook is not None:
                    # Serve-while-training (ISSUE 17): same snapshot
                    # cadence feeds the resident serving policy; the
                    # publisher copies its own leaves, so the hook may
                    # hand this tree to PolicyStore.swap.
                    publish_hook(it, np_behavior)
                staleness = max(it - block.version, 0)
                env_steps = sum(a.steps_collected for a in actors)
                if use_device_plane:
                    # Zero-transfer consume (ISSUE 13): the staged block
                    # already lives in HBM; the jitted ingest gathers +
                    # decodes it and scatters into the replay ring in
                    # one program — only the slot index crosses.
                    telemetry.instant("host_to_device", device_plane=True)
                    slot = np.int32(block.slot)
                    # jaxlint: disable=transfer-discipline (scalar
                    # env_steps counter — 4 bytes ride the dispatch)
                    steps = jnp.asarray(env_steps, jnp.int32)
                    with telemetry.span("update", dispatch="async"):
                        learner, metrics = queue.run(
                            lambda state: ingest_update(
                                learner, state, slot, steps
                            )
                        )
                    # After the dispatch: device execution order now
                    # reads the slot before any later overwrite.
                    queue.release(block)
                else:
                    with telemetry.span("host_to_device"):
                        # jnp.array, NOT asarray: the transfer must
                        # snapshot the slot before release (the PR 6
                        # contract).
                        # jaxlint: disable=transfer-discipline (the
                        # host plane's per-block upload by design; the
                        # device branch above removes it — perfsan
                        # budgets both planes)
                        traj = OffPolicyTransition(
                            obs=jnp.array(block.arrays["obs"]),
                            action=jnp.array(block.arrays["action"]),
                            reward=jnp.array(block.arrays["reward"]),
                            next_obs=jnp.array(block.arrays["final_obs"]),
                            terminated=jnp.array(block.arrays["terminated"]),
                            done=jnp.array(block.arrays["done"]),
                        )
                    queue.release(block)
                    with telemetry.span("update", dispatch="async"):
                        # jaxlint: disable=transfer-discipline (scalar
                        # env_steps counter — 4 bytes)
                        learner, metrics = ingest_update(
                            learner, traj, jnp.asarray(env_steps, jnp.int32)
                        )
                qs = queue.stats()
                extra = {
                    "env_steps": env_steps,
                    "consumed_env_steps": (it + 1) * cfg.steps_per_iter * E_a,
                    "block_actor": block.actor_id,
                    "block_staleness": staleness,
                    "queue_depth": qs["depth"],
                    "queue_drops_full": qs["drops_full"],
                    "queue_drops_stale": qs["drops_stale"],
                    "learner_idle_s": qs["learner_idle_s"],
                }
                if eval_pool is not None and (it + 1) % eval_every == 0:
                    # Blocks on the in-flight update: eval sees CURRENT
                    # params, like the lockstep drivers.
                    # jaxlint: disable=transfer-discipline (eval
                    # cadence, not the per-block consume path)
                    ev_params = jax.device_get(learner.actor_params)
                    with telemetry.span("eval"):
                        extra["eval_return"] = host_evaluate(
                            eval_pool,
                            # jaxlint: disable=host-sync (numpy mirror
                            # eval — no device value is touched)
                            lambda o: np.asarray(host_greedy(ev_params, o)),
                            max_steps=eval_steps,
                        )
                maybe_log(
                    it, log_every, metrics, trackers, history, log_fn,
                    extra=extra, num_iterations=num_iterations,
                    force="eval_return" in extra or it == 0,
                )
    finally:
        stop.set()
        for a in actors:
            a.join(timeout=30.0)
        queue.close()
        if eval_pool is not None:
            eval_pool.close()
    return learner, history


def fused_train_loop(
    make_train_step: Callable,
    init_state: Callable,
    env,
    cfg,
    num_iterations: int,
    seed: int = 0,
    state=None,
    log_every: int = 0,
    log_fn: Optional[Callable[[int, dict], None]] = None,
    scan_when_silent: bool = False,
    state_hook: Optional[Callable[[int, object], object]] = None,
):
    """Shared host loop around a fused (single-device) train step — the
    single body behind a2c/impala/ddpg/sac `.train`.

    With `scan_when_silent` and `log_every<=0` the whole loop is itself
    scanned on-device so the host dispatches O(1) programs (the a2c/
    impala fast path); otherwise each iteration is one donated jit call
    with optional periodic logging.

    `state_hook(it, state) -> state` runs on the HOST before each
    dispatch (it = 0-based upcoming iteration) — the between-dispatch
    rewrite seam the scenario-mixture curriculum uses to install new
    type-draw weights into the fleet state (envs/mixture.py
    `set_fleet_weights`; train.py's checkpointed path has the same seam
    in run_fused). Hooks must preserve every leaf's shape/dtype so the
    jitted step never retraces; setting one disables the scanned fast
    path (a host callback cannot run inside `lax.scan`).
    """
    import jax

    if state is None:
        state = init_state(env, cfg, jax.random.key(seed))
    step = make_train_step(env, cfg)

    if scan_when_silent and log_every <= 0 and state_hook is None:
        if num_iterations < 1:
            raise ValueError("num_iterations must be >= 1")

        # should_log policy: the FIRST and final iterations always log, so
        # the first update runs as its own dispatch (early evidence), then
        # the remaining n-1 are one scanned program — still O(1) dispatches.
        jit_step = jax.jit(step, donate_argnums=0)
        state, metrics = jit_step(state)
        if log_fn is not None:
            log_fn(1, {k: float(v) for k, v in metrics.items()})
        if num_iterations > 1:

            # donate_argnums matches jit_step above: `state` here is
            # jit_step's freshly produced output (rebound at its call),
            # so the scanned tail can reuse the buffers in place instead
            # of copy-preserving the full train state for one call
            # (found by donation-discipline, ISSUE 15).
            @partial(jax.jit, donate_argnums=0)
            def run(state):
                def body(s, _):
                    s, _m = step(s)
                    return s, None

                s, _ = jax.lax.scan(body, state, None, length=num_iterations - 2)
                # last of the remaining n-1 updates returns the metrics
                return step(s)

            state, metrics = run(state)
            if log_fn is not None:
                log_fn(num_iterations, {k: float(v) for k, v in metrics.items()})
        return state, metrics

    jit_step = jax.jit(step, donate_argnums=0)
    metrics: dict = {}
    for it in range(num_iterations):
        if state_hook is not None:
            state = state_hook(it, state)
        state, metrics = jit_step(state)
        if log_fn is not None and should_log(it + 1, log_every, num_iterations):
            # jaxlint: disable=host-sync (deliberate: the log-cadence
            # float() coercions are the loop's designed first sync point
            # — README "Observability")
            log_fn(it + 1, {k: float(v) for k, v in metrics.items()})
    return state, metrics


# Cadence policies live in utils/cadence.py (a leaf module, so
# utils/checkpoint.py can share them without importing algos); re-exported
# here because the loops and their tests address them via this module.
from actor_critic_tpu.utils.cadence import should_log  # noqa: E402, F401


def maybe_log(
    it: int,
    log_every: int,
    metrics: dict,
    tracker: EpisodeTracker,
    history: list,
    log_fn: Optional[Callable[[int, dict], None]],
    extra: Optional[dict] = None,
    num_iterations: int = 0,
    force: bool = False,
) -> None:
    """Append host-side metrics to `history` (and `log_fn`) on the shared
    `should_log` cadence (pass `num_iterations` so the final iteration is
    always logged; `force` for rows that must never drop, e.g. eval)."""
    if not (force or should_log(it + 1, log_every, num_iterations)):
        return
    # The float() coercions are the host loop's first sync point on the
    # dispatched update — the log span therefore absorbs any remaining
    # device wait (documented in README "Observability").
    with telemetry.span("log", it=it + 1):
        m = {k: float(v) for k, v in metrics.items()}
        m.update(tracker.report())
        if extra:
            m.update(extra)
        history.append((it + 1, m))
        if log_fn is not None:
            log_fn(it + 1, m)
