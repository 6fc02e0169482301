"""Unified training CLI (SURVEY.md §5.6).

One entry point for every algorithm/config the framework supports —
the TPU build's replacement for the reference genre's per-script
argparse mains (reference mount empty at survey, SURVEY.md §0):

    python train.py --preset a2c_cartpole
    python train.py --preset ppo_halfcheetah --set lr=1e-4 --iterations 200
    python train.py --algo sac --env jax:point_mass --set num_envs=16
    python train.py --preset impala_pong --ckpt-dir runs/pong --resume
    python train.py --list-presets

Environments: `jax:<name>` runs the fused on-device trainer (rollout +
update in one XLA program); `host:<gym id>` steps a gymnasium/MuJoCo
pool on the host with the learner on device. Metrics stream to a JSONL
file; checkpoints (orbax) make the run restart-idempotent.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

from actor_critic_tpu import telemetry


def build_env(spec: str, algo: str, cfg, seed: int, scale_actions=None,
              env_kwargs=None, workers: int = 1):
    """'jax:<name>' → (JaxEnv, fused=True); 'host:<id>' → (pool, False).

    scale_actions is tri-state: None keeps each env's own convention
    (host pools clip — the recorded-run behavior; jax:pendulum scales),
    True/False (--scale-actions / --no-scale-actions) forces it where
    the env supports the choice.

    env_kwargs (preset env_kwargs merged with --env-set) go to the env
    CONSTRUCTOR: the jax:* maker (e.g. pong's opp_skill/frame_skip/size)
    or gym.make for host pools. The native backend's envs take no
    construction knobs, so kwargs there are an error, not a silent drop."""
    kind, _, name = spec.partition(":")
    env_kwargs = dict(env_kwargs or {})
    if kind == "mixture":
        # 'mixture:cartpole*2,pendulum,acrobot,maze' — a heterogeneous
        # fleet of env TYPES stepping inside one fused program
        # (envs/mixture.py, ISSUE 11). The member list (with optional
        # per-type draw weights) is the spec; --env-set reaches the
        # mixture maker (randomize/action_bins/redraw_types/...).
        import inspect

        from actor_critic_tpu.envs import make_mixture

        valid = set(inspect.signature(make_mixture).parameters) - {"members"}
        unknown = sorted(set(env_kwargs) - valid)
        if unknown:
            raise SystemExit(
                f"bad --env-set for {spec}: unknown kwargs {unknown}; "
                f"valid: {sorted(valid)}"
            )
        try:
            return make_mixture(name, **env_kwargs), True
        except ValueError as e:
            raise SystemExit(f"bad mixture env {spec!r}: {e}") from e
    if kind == "jax":
        from actor_critic_tpu import envs as E

        makers = {
            "cartpole": E.make_cartpole,
            "pendulum": E.make_pendulum,
            "pong": E.make_pong,
            "two_state": E.make_two_state_mdp,
            "point_mass": E.make_point_mass,
            "bandit": E.make_bandit,
            "token_task": E.make_token_task,
        }
        if name not in makers:
            raise SystemExit(f"unknown jax env {name!r}; valid: {sorted(makers)}")
        if name == "pendulum":
            # One resolution for behavior AND the resume-guard record:
            # CLI flag wins, then --env-set/preset kwarg, then the env
            # default (scale) — effective_scale_actions is that order.
            env_kwargs["scale_actions"] = effective_scale_actions(
                spec, scale_actions, env_kwargs
            )
        # Validate kwargs against the maker's signature UP FRONT so the
        # friendly exit fires only for genuinely unknown knobs — a
        # TypeError raised inside a maker must keep its real traceback.
        import inspect

        valid = set(inspect.signature(makers[name]).parameters)
        unknown = sorted(set(env_kwargs) - valid)
        if unknown:
            raise SystemExit(
                f"bad --env-set for jax:{name}: unknown kwargs {unknown}; "
                f"valid: {sorted(valid)}"
            )
        return makers[name](**env_kwargs), True
    if kind in ("host", "native"):
        from actor_critic_tpu.envs.host_pool import HostEnvPool

        # Off-policy TD targets want raw reward scale, and off-policy
        # REPLAY wants raw observations too: the pool normalizes with
        # RUNNING stats, so replayed transitions stored early are scaled
        # differently than fresh ones, and the critic bootstraps across
        # inconsistent frames. On high-dim envs this destabilizes Q
        # (observed: SAC Humanoid-v5 Q/alpha runaway with normalization
        # on; raw obs is also the standard SAC/TD3 setup). On-policy PPO
        # consumes each batch immediately, so drifting stats are safe
        # and obs/reward normalization helps it.
        # 'native:<id>' steps the batch in the C++ engine (one C call per
        # step) instead of the Python SyncVectorEnv loop.
        on_policy = algo == "ppo"
        if kind == "native" and env_kwargs:
            raise SystemExit(
                f"--env-set is not supported for native:{name} (the C++ "
                "engine replicates gymnasium defaults exactly)"
            )
        if kind == "native" and workers > 1:
            raise SystemExit(
                "--workers applies to host:<id> pools only (the native "
                "engine already steps the whole batch in one C call)"
            )
        try:
            return (
                HostEnvPool(
                    name,
                    num_envs=cfg.num_envs,
                    seed=seed,
                    normalize_obs=on_policy,
                    normalize_reward=on_policy,
                    backend="gym" if kind == "host" else "native",
                    scale_actions=bool(scale_actions),
                    env_kwargs=env_kwargs,
                    workers=workers,
                ),
                False,
            )
        except TypeError as e:
            # gym.make raises TypeError on unknown constructor kwargs —
            # same friendly exit as the jax: path's maker check. Only
            # claim --env-set is at fault when kwargs were given AND the
            # message blames a keyword; other TypeErrors keep their
            # traceback.
            if env_kwargs and "keyword" in str(e):
                raise SystemExit(f"bad --env-set for {spec}: {e}") from e
            raise
    raise SystemExit(
        f"env must be jax:<name>, mixture:<members>, host:<gym id>, or "
        f"native:<id>, got {spec!r}"
    )


def effective_scale_actions(env_spec: str, scale_actions, env_kwargs=None):
    """Resolve the tri-state CLI flag to the convention the env will
    actually use, so the resume guard compares BEHAVIOR, not flag
    spelling: `jax:pendulum` defaults to scaling (build_env maps
    None→True there), so None and True are the same convention and a
    resume that makes the default explicit must not warn. The explicit
    CLI flag wins; an `--env-set scale_actions=...` kwarg comes next
    (mirroring build_env's setdefault order); then the env default.
    Envs with no continuous-action convention resolve to None."""
    if env_spec == "jax:pendulum":
        if scale_actions is not None:
            return bool(scale_actions)
        kw = (env_kwargs or {}).get("scale_actions")
        return True if kw is None else bool(kw)
    if env_spec.startswith(("host:", "native:")):
        # Host pools clip unless the flag forces scaling (build_env
        # passes bool(scale_actions), so None means clip).
        return bool(scale_actions)
    return None


def check_env_convention(ckpt_dir, env_spec: str, scale_actions, resume: bool,
                         env_kwargs=None):
    """Fused-path twin of the host path's `_pool_scale_actions` resume
    guard (algos/host_loop.py): record the run's EFFECTIVE action
    convention AND env-constructor kwargs in a sidecar JSON next to the
    checkpoints, and warn when a resume flips either — the restored
    policy would silently execute under another action convention
    (e.g. jax:pendulum ±2-scaled vs raw torques) or inside a
    different-difficulty env (e.g. pong opp_skill), contaminating the
    run's curve. Tolerant of pre-existing checkpoint dirs without the
    sidecar; a fresh (non-resume) run overwrites any stale sidecar left
    by a previous run in the same dir."""
    if not ckpt_dir:
        return
    import os
    import warnings

    env_kwargs = dict(env_kwargs or {})
    resolved = effective_scale_actions(env_spec, scale_actions, env_kwargs)
    # scale_actions is compared via `resolved` (which folds in the CLI
    # flag); leaving it in the kwargs dict would warn spuriously when one
    # run spells the same convention via --env-set and the other via the
    # flag.
    env_kwargs.pop("scale_actions", None)
    path = os.path.join(ckpt_dir, "env_convention.json")
    current = {
        "env": env_spec, "scale_actions": resolved, "env_kwargs": env_kwargs,
    }
    if resume and os.path.exists(path):
        with open(path) as f:
            saved = json.load(f)
        # Old sidecars recorded the raw tri-state flag; resolve it the
        # same way so None-vs-True on a scaling-default env stays quiet.
        saved_kwargs = saved.get("env_kwargs")
        saved_resolved = effective_scale_actions(
            saved.get("env", env_spec), saved.get("scale_actions"),
            saved_kwargs,
        )
        if saved_kwargs is not None:
            saved_kwargs = dict(saved_kwargs)
            saved_kwargs.pop("scale_actions", None)
        saved_env = saved.get("env")
        if saved_env is not None and saved_env != env_spec:
            warnings.warn(
                f"--resume into {env_spec!r} but this checkpoint dir "
                f"belongs to a {saved_env!r} run — the restored policy "
                "trained on a different environment. Use a fresh "
                "--ckpt-dir or the original env.",
                stacklevel=2,
            )
            # The convention/kwargs comparisons are meaningless across
            # different envs (and would emit nonsense follow-up advice
            # like "relaunch with the original flag") — the env warning
            # already says everything.
            return
        # Host pools already guard the scale flag through the checkpoint
        # metrics (host_loop._pool_scale_actions) — warning here too
        # would double-report the same flip; the sidecar adds env/kwargs
        # coverage there, and full coverage for fused envs.
        host = env_spec.startswith(("host:", "native:"))
        if not host and saved_resolved != resolved:
            warnings.warn(
                f"--resume with scale_actions={resolved!r} but this "
                f"run started with {saved_resolved!r} — the "
                "restored policy trained under the other action "
                "convention. Relaunch with the original flag.",
                stacklevel=2,
            )
        # Pre-env-kwargs sidecars (no key) are tolerated like legacy
        # dirs; a recorded mismatch is a different env, so warn.
        if saved_kwargs is not None and saved_kwargs != env_kwargs:
            warnings.warn(
                f"--resume with env_kwargs={env_kwargs!r} but this run "
                f"started with {saved_kwargs!r} — the restored policy "
                "would continue in a different environment. Relaunch "
                "with the original --env-set/preset.",
                stacklevel=2,
            )
        return
    os.makedirs(ckpt_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(current, f)


def fused_module(algo: str):
    from actor_critic_tpu.algos import a2c, ddpg, impala, ppo, sac

    return {
        "a2c": a2c, "ppo": ppo, "ddpg": ddpg, "td3": ddpg,
        "sac": sac, "impala": impala, "a3c": impala,
    }[algo]


def steps_per_iteration(algo: str, cfg) -> int:
    if hasattr(cfg, "rollout_steps"):
        return cfg.rollout_steps * cfg.num_envs
    return cfg.steps_per_iter * cfg.num_envs


def run_fused(env, preset, args, logger) -> dict:
    import jax
    import jax.numpy as jnp

    from actor_critic_tpu.envs import mixture
    from actor_critic_tpu.utils.checkpoint import Checkpointer, checkpointed_train

    mod = fused_module(preset.algo)
    cfg = preset.config
    with telemetry.span("init_state"):
        state = mod.init_state(env, cfg, jax.random.key(args.seed))
        if telemetry.current() is not None:
            # Traced runs wait here, so the device's share of the state's
            # making lands in this span and not in the first `update`.
            jax.block_until_ready(state)
    raw_step = mod.make_train_step(env, cfg)
    chunk = max(1, getattr(args, "chunk", 1))
    if chunk > 1:
        # Chunked dispatch: scan `k` train iterations inside ONE jitted
        # call, so per-dispatch overhead is paid once per chunk (how
        # much that is on the chip is not measured yet: ROADMAP S2).
        # Metrics are the final iteration's slice — the same
        # point-in-time semantics a per-iteration loop logs at chunk
        # boundaries. Shape-stabilized (utils/compile_cache.py): full
        # chunks share one program and EVERY partial chunk (resume
        # realignment, end tail) shares a second, n_valid-masked one —
        # arbitrary k never compiles a fresh program.
        from actor_critic_tpu.utils.compile_cache import make_chunked_step

        step = make_chunked_step(raw_step, chunk)

        # Cadences fire only at chunk boundaries; snap them UP to chunk
        # multiples so "every N" keeps meaning what it says.
        def _snap(x):
            if x is None or x <= 0 or x % chunk == 0:
                return x
            return ((x + chunk - 1) // chunk) * chunk

        for name in ("log_every", "eval_every", "save_every"):
            old = getattr(args, name, 0)
            new = _snap(old)
            if new != old:
                print(f"--chunk {chunk}: {name} {old} -> {new}", flush=True)
                setattr(args, name, new)
    else:
        step = jax.jit(raw_step, donate_argnums=0)
    spi = steps_per_iteration(preset.algo, cfg)

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt is not None and args.resume and ckpt.latest_step() is not None:
        print(f"resumed from iteration {ckpt.latest_step()}", flush=True)

    from actor_critic_tpu.algos.host_loop import should_log

    eval_fn = None
    typed_eval = None
    eval_matrix: dict = {}
    if getattr(args, "eval_every", 0) > 0:
        eval_fn = jax.jit(mod.make_eval_fn(env, cfg), static_argnums=(2, 3))
        eval_key = jax.random.key(args.seed + 1)
        if isinstance(env, mixture.MixtureEnv):
            # Per-type eval matrix (ISSUE 11): one jitted program whose
            # fleet is pinned to a TRACED type id — every member type
            # evaluates through the same executable. Last results ride
            # the sampler registry into /metrics + resources.jsonl
            # (rendered by scripts/run_report.py).
            typed_eval = jax.jit(
                mixture.make_typed_eval(env, mod.make_network(env, cfg)),
                static_argnums=(3, 4),
            )

    # Curriculum (ISSUE 11): the controller advances on eval progress;
    # the new weights are installed into the fleet state between
    # dispatches (same shapes/dtypes — never a retrace) and ride the
    # checkpoint, so a resumed run continues the schedule.
    curriculum_ctl = None
    pending_weights: list = []
    if getattr(args, "curriculum", ""):
        curriculum_ctl = mixture.CurriculumController(
            mixture.parse_curriculum(args.curriculum, env.member_names)
        )

    def eval_due(it):
        return eval_fn is not None and (
            it % args.eval_every == 0 or it == args.iterations
        )

    def log_due(it):
        # Eval cadence is INDEPENDENT of the logging cadence; an eval
        # iteration always emits a log row so the number is never lost.
        return should_log(it, args.log_every, args.iterations) or eval_due(it)

    def log_fn(it, metrics):
        extra = {}
        if eval_due(it):
            with telemetry.span("eval", it=it):
                extra["eval_return"] = float(eval_fn(state_box[0], eval_key))
                if typed_eval is not None:
                    for t, name in enumerate(env.member_names):
                        # jaxlint: disable=transfer-discipline (eval
                        # cadence: the per-type eval matrix runs
                        # |types| dispatches once per eval, not in the
                        # training step loop)
                        r = float(typed_eval(
                            state_box[0],
                            jax.random.fold_in(eval_key, t),
                            jnp.asarray(t, jnp.int32),
                        ))
                        extra[f"eval_return_{name}"] = round(r, 3)
                        eval_matrix.update(mixture.eval_matrix_row(name, r))
            if curriculum_ctl is not None:
                advanced = curriculum_ctl.update(extra["eval_return"])
                if advanced is not None:
                    stage, weights = advanced
                    pending_weights[:] = [(stage, weights)]
                    print(
                        f"curriculum: eval {extra['eval_return']:.1f} -> "
                        f"stage {stage}, weights {list(weights)}",
                        flush=True,
                    )
                extra["curriculum_stage"] = curriculum_ctl.stage
        if log_due(it):
            # Health monitors see the materialized row — AFTER the eval
            # merge (so eval_return reaches the divergence detector) and
            # only on the log cadence: fetching the row is the loop's one
            # device sync (a traced run has just waited for it under
            # `device_wait`, utils/checkpoint.py), and syncing every
            # dispatch would serialize host on device, the pipelining
            # this loop exists to preserve. One `device_get` for the whole
            # row: `float()` on a device scalar is a transfer of its own,
            # 0.3-0.7 ms each on a v5e.
            # Non-floatable values stringify, same tolerance as
            # JsonlLogger.log.
            row = {}
            for k, v in jax.device_get(metrics).items():
                try:
                    row[k] = float(v)
                except (TypeError, ValueError):
                    row[k] = str(v)
            row.update(extra, env_steps=it * spi)
            telemetry.observe(it, row)
            logger.log(it, row)

    # log_fn needs the CURRENT state for eval; checkpointed_train owns the
    # loop, so expose it via a one-cell box updated by a wrapped step.
    state_box = [state]
    ctl_synced = [curriculum_ctl is None]

    def step_tracking(s, *k):
        if not ctl_synced[0]:
            # First dispatch after a (possible) restore: re-align the
            # host-side curriculum counter from the stage the restored
            # fleet state carries, so resume continues the schedule.
            curriculum_ctl.sync(mixture.fleet_stage(s.rollout.env_state))
            ctl_synced[0] = True
        if pending_weights:
            stage, weights = pending_weights.pop()
            s = s._replace(rollout=s.rollout._replace(
                env_state=mixture.set_fleet_weights(
                    s.rollout.env_state, weights, stage
                )
            ))
        # jax:* envs fuse the rollout INTO the update program, so the
        # env_step phase has no separable host duration — record it as a
        # Chrome-trace instant so traces still carry the phase.
        telemetry.instant("env_step", fused=True)
        out, m = step(s, *k)
        state_box[0] = out
        return out, m

    gauge_key = None
    if typed_eval is not None:
        from actor_critic_tpu.telemetry import sampler

        gauge_key = sampler.register_gauge(
            "mixture_eval", lambda: dict(eval_matrix)
        )
    end_startup(args)
    try:
        state, metrics = checkpointed_train(
            step_tracking, state, args.iterations,
            ckpt=ckpt, save_every=args.save_every, log_fn=log_fn,
            resume=args.resume, stride=chunk, log_due=log_due,
        )
    finally:
        if gauge_key is not None:
            from actor_critic_tpu.telemetry import sampler

            sampler.unregister_gauge(gauge_key)
    if ckpt is not None:
        ckpt.close()
    return {k: float(v) for k, v in metrics.items()}


def build_actor_pools(preset, args, actors: int) -> list:
    """One HostEnvPool per async actor (E/A envs each, disjoint seeds,
    the worker fleet split across actors) — the fleet the ISSUE 6
    actor–learner services collect from."""
    from actor_critic_tpu.envs.host_pool import HostEnvPool

    kind, _, name = preset.env.partition(":")
    if kind not in ("host", "native"):
        raise SystemExit(
            "--async-actors decouples HOST collection from the learner; "
            "jax:* envs fuse rollouts into the update program and have "
            "nothing to decouple"
        )
    if preset.algo not in ("ppo", "ddpg", "td3", "sac"):
        raise SystemExit(
            f"--async-actors drives the host trainers (ppo/ddpg/td3/"
            f"sac); {preset.algo} has no host loop to decouple"
        )
    # Same normalization policy as the lockstep pools (build_env): PPO
    # wants running obs/reward normalization; the off-policy algos must
    # store RAW transitions (drifting stats re-scale replayed frames).
    on_policy = preset.algo == "ppo"
    cfg = preset.config
    if actors > cfg.num_envs or cfg.num_envs % actors != 0:
        raise SystemExit(
            f"num_envs={cfg.num_envs} must split evenly across "
            f"--async-actors={actors} (one fixed [K, E/A] block shape "
            "keeps the learner on a single compiled program)"
        )
    workers_each = max(1, args.workers // actors)
    # Under --distributed every HOST builds its own fleet from the same
    # --seed: without a rank stride the fleets would replay identical
    # env reset streams and the global sync batch would carry
    # cross-host duplicate trajectories (launch_multihost.py uses the
    # same (rank·A + i) stride).
    rank = args.process_id if args.distributed else 0
    return [
        HostEnvPool(
            name,
            num_envs=cfg.num_envs // actors,
            # Large per-actor seed stride: pools seed their envs
            # [seed .. seed+E), so adjacent offsets would duplicate
            # trajectories across actors.
            seed=args.seed + (rank * actors + i) * 100003,
            normalize_obs=on_policy,
            normalize_reward=on_policy,
            backend="gym" if kind == "host" else "native",
            scale_actions=bool(args.scale_actions),
            env_kwargs=preset.env_kwargs,
            workers=workers_each,
        )
        for i in range(actors)
    ]


def run_multihost(pools, preset, args, logger) -> dict:
    """One process of the distributed actor–learner fleet (ISSUE 9):
    local actor services feed the local queue; the learner either joins
    the global all-reduce (sync) or gossips params peer-to-peer
    (--gossip). Launch one such process per host — or use
    scripts/launch_multihost.py for a CPU local cluster."""
    import jax

    from actor_critic_tpu.parallel import multihost

    rank = jax.process_index() if args.coordinator else args.process_id
    world = args.num_processes
    multihost.host_lane(rank)
    last: dict = {}

    def log_fn(it, m):
        telemetry.observe(it, m)
        last.clear()
        last.update(m)
        logger.log(it, m)

    end_startup(args)
    _, _, summary = multihost.train_multihost(
        pools, preset.config, args.iterations,
        rank=rank, world=world,
        mode="gossip" if args.gossip else "sync",
        seed=args.seed, log_every=args.log_every, log_fn=log_fn,
        queue_depth=args.queue_depth,
        max_staleness=resolve_staleness(args, "ppo"),
        updates_per_block=args.updates_per_block,
        correction=args.async_correction,
        gossip=multihost.GossipConfig(
            every=args.gossip_every, weight=args.gossip_weight,
        ),
        mailbox_dir=args.mailbox_dir or None,
    )
    last.update({f"multihost_{k}": v for k, v in summary.items()
                 if isinstance(v, (int, float, bool))})
    return last


def resolve_staleness(args, algo: str):
    """--max-staleness tri-state: explicit S >= 0 is a bound, -1 is
    unbounded, absent picks the per-algo default (8 for PPO, unbounded
    for the off-policy algos — replay absorbs staleness)."""
    if args.max_staleness is None:
        return 8 if algo == "ppo" else None
    return args.max_staleness if args.max_staleness >= 0 else None


def start_serving_sidecar(preset, spec, args):
    """Serve-while-training (ISSUE 17): a resident policy-serving
    gateway whose single 'learner' policy tracks the training run.

    Built BEFORE training starts so every act bucket is compiled while
    the env pools are still spawning — the publish hook then only ever
    hot-swaps params through the `checkpoint.uncommit` route (frozen
    host snapshot re-placed as uncommitted device buffers: same program,
    0 recompiles, perfsan's committed serving budget). Versioning:
    the init placeholder registers at version 0; block `it`'s publish
    swaps to version `it + 1`, so /v1/act's `version` field is strictly
    monotone and equals blocks-consumed + 1.

    Returns `(gateway, publish_hook)`; the caller owns gateway.close().
    """
    from actor_critic_tpu import serving

    buckets = tuple(
        int(b) for b in args.serve_buckets.split(",") if b.strip()
    )
    engine = serving.PolicyEngine(
        spec, preset.config, algo=preset.algo, buckets=buckets,
        seed=args.seed,
    )
    store = serving.PolicyStore()
    template = serving.init_params(
        spec, preset.config, preset.algo, seed=args.seed
    )
    store.register("learner", engine, template, default=True)
    n_warm = engine.warm(template)
    gateway = serving.ServeGateway(store, port=args.serve_port)
    print(
        f"serving learner on http://127.0.0.1:{gateway.port} "
        f"(warm: {n_warm} act buckets)",
        flush=True,
    )

    def publish_hook(it: int, np_params) -> None:
        # The publisher freezes its own copy, so handing the same tree
        # to the store is safe; swap numguards + re-places per policy.
        store.swap("learner", np_params, version=it + 1)

    return gateway, publish_hook


def run_host_async(pools, preset, args, logger) -> dict:
    from actor_critic_tpu.algos import ddpg, ppo, sac

    last: dict = {}

    def log_fn(it, m):
        telemetry.observe(it, m)
        last.clear()
        last.update(m)
        logger.log(it, m)

    from actor_critic_tpu.utils.checkpoint import Checkpointer

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt is not None and args.resume and ckpt.latest_step() is not None:
        print(f"resuming from block {ckpt.latest_step()}", flush=True)
    gateway, publish_hook = None, None
    if args.serve_port is not None:
        gateway, publish_hook = start_serving_sidecar(
            preset, pools[0].spec, args
        )
    end_startup(args)
    try:
        if preset.algo == "ppo":
            ppo.train_host_async(
                pools, preset.config, num_iterations=args.iterations,
                seed=args.seed, log_every=args.log_every, log_fn=log_fn,
                eval_every=args.eval_every, eval_envs=args.eval_envs,
                eval_steps=args.eval_steps,
                updates_per_block=args.updates_per_block,
                queue_depth=args.queue_depth,
                max_staleness=resolve_staleness(args, "ppo"),
                correction=args.async_correction,
                data_plane=args.data_plane,
                plane_codec=args.data_plane_codec,
                ckpt=ckpt, save_every=args.save_every, resume=args.resume,
                publish_hook=publish_hook,
            )
        else:
            # Off-policy (ddpg/td3/sac): replay absorbs behavior
            # staleness, so there is no correction knob and the
            # staleness bound defaults OFF (-1 keeps it off; >= 0 sets
            # a bound anyway).
            mod = ddpg if preset.algo in ("ddpg", "td3") else sac
            mod.train_host_async(
                pools, preset.config, num_iterations=args.iterations,
                seed=args.seed, log_every=args.log_every, log_fn=log_fn,
                eval_every=args.eval_every, eval_envs=args.eval_envs,
                eval_steps=args.eval_steps,
                queue_depth=args.queue_depth,
                max_staleness=resolve_staleness(args, preset.algo),
                data_plane=args.data_plane,
                plane_codec=args.data_plane_codec,
                publish_hook=publish_hook,
            )
    finally:
        if gateway is not None:
            gateway.close()
        if ckpt is not None:
            ckpt.close()
    return last


def run_host(pool, preset, args, logger) -> dict:
    from actor_critic_tpu.algos import ddpg, ppo, sac
    from actor_critic_tpu.utils.checkpoint import Checkpointer

    last: dict = {}

    def log_fn(it, m):
        telemetry.observe(it, m)
        last.clear()
        last.update(m)
        logger.log(it, m)

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt is not None and args.resume and ckpt.latest_step() is not None:
        print(f"resuming from iteration {ckpt.latest_step()}", flush=True)
    common = dict(
        num_iterations=args.iterations, seed=args.seed,
        log_every=args.log_every, log_fn=log_fn,
        eval_every=getattr(args, "eval_every", 0),
        eval_envs=getattr(args, "eval_envs", 4),
        eval_steps=getattr(args, "eval_steps", 1000),
        ckpt=ckpt, save_every=args.save_every, resume=args.resume,
        overlap=not args.no_overlap,
    )
    offpolicy = dict(common, save_replay=not args.no_save_replay)
    end_startup(args)
    try:
        if preset.algo == "ppo":
            ppo.train_host(pool, preset.config, **common)
        elif preset.algo in ("ddpg", "td3"):
            ddpg.train_host(pool, preset.config, **offpolicy)
        elif preset.algo == "sac":
            sac.train_host(pool, preset.config, **offpolicy)
        else:
            raise SystemExit(
                f"{preset.algo} needs a pure-JAX env (fused trainer); "
                "pick env jax:<name>"
            )
        if not last and ckpt is not None:
            # Resume found the run already complete: no iteration ran, so
            # no log row fired — recover the final metrics saved alongside
            # the checkpoint instead of returning an empty summary.
            # Underscore-prefixed keys are checkpoint-internal bookkeeping
            # (e.g. _pool_scale_actions), not metrics.
            last = {
                k: v for k, v in ckpt.restore_metrics().items()
                if not k.startswith("_")
            }
    finally:
        if ckpt is not None:
            ckpt.close()
    return last


def main(argv=None) -> int:
    # NB: when ADDING an option that takes a VALUE, also add it to
    # `takes_value()` in scripts/run_resumable.sh — the wrapper parses
    # this argv shape to tell its own --fresh flag from option values.
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    p.add_argument("--preset", help="named preset (see --list-presets)")
    p.add_argument("--algo", help="a2c|ppo|ddpg|td3|sac|impala|a3c")
    p.add_argument("--env", help="jax:<name> or host:<gym id>")
    p.add_argument("--iterations", type=int, help="train-step iterations")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--set", action="append", default=[], metavar="KEY=VALUE",
        help="config override (repeatable), e.g. --set lr=1e-4 --set hidden=64,64",
    )
    p.add_argument(
        "--env-set", action="append", default=[], metavar="KEY=VALUE",
        help="env-constructor kwarg (repeatable), e.g. --env-set "
        "opp_skill=0.5 --env-set frame_skip=4; merges over the preset's "
        "env_kwargs",
    )
    p.add_argument(
        "--curriculum", default="", metavar="SPEC",
        help="mixture envs (fused, needs --eval-every): re-weight the "
        "type/scenario draw distribution as learner eval progress "
        "crosses thresholds — 'THR:w0,w1,..;THR:w0,w1,..', one stage "
        "per semicolon entry, weights in member order (envs/mixture.py "
        "grammar). Forces redraw_types=True on the mixture; the stage "
        "and weights ride the env state inside the checkpoint, so "
        "--resume continues the schedule.",
    )
    p.add_argument("--metrics", default="metrics.jsonl", help="JSONL output path")
    p.add_argument(
        "--telemetry-dir",
        help="unified run telemetry: write spans.jsonl (Chrome-trace "
        "phase events; render with scripts/run_report.py --trace or open "
        "in Perfetto), resources.jsonl (RSS / device memory / XLA "
        "recompiles), and events.jsonl (health + lifecycle events) under "
        "this directory. Phase instrumentation is always on and "
        "near-free; this flag only adds the file sinks + the resource "
        "sampler thread.",
    )
    p.add_argument(
        "--telemetry-port", type=int, default=None, metavar="PORT",
        help="live run introspection: serve GET /metrics (Prometheus "
        "text: RSS, device memory, XLA recompiles, sampler gauges, last "
        "training row, steps/s), /healthz (watchdog staleness + open "
        "span; 503 when stalled), and /profile?iters=N (arm an "
        "on-demand jax.profiler capture) on 127.0.0.1:PORT from a "
        "daemon thread (telemetry/exporter.py). 0 picks an ephemeral "
        "port (printed at startup). Requires --telemetry-dir (profile "
        "captures land there). SIGUSR2 also arms a capture.",
    )
    p.add_argument(
        "--telemetry-bind", default="127.0.0.1", metavar="HOST",
        help="bind address for the --telemetry-port exporter (default "
        "127.0.0.1). Non-loopback binds expose unauthenticated run "
        "internals, so they are refused unless --distributed (where "
        "the fleet aggregator scrapes peers over the network).",
    )
    p.add_argument(
        "--telemetry-sample-s", type=float, default=5.0, metavar="SECS",
        help="cadence of the telemetry resource sampler thread "
        "(resources.jsonl rows; default 5 s). Only meaningful with "
        "--telemetry-dir. NB: the shard pool's utilization gauge "
        "recomputes over windows of at least 1 s, so sub-second "
        "cadences repeat its previous value between recomputes.",
    )
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument(
        "--chunk", type=int, default=1,
        help="fused envs only: train iterations scanned per device "
        "dispatch (amortizes per-dispatch overhead; log/eval/save "
        "cadences snap up to multiples of this). The watchdog sees one "
        "heartbeat per chunk, so --stall-timeout must comfortably "
        "exceed one chunk's wall time",
    )
    p.add_argument(
        "--eval-every", type=int, default=0,
        help="greedy-eval cadence in iterations (0 = off)",
    )
    p.add_argument(
        "--eval-envs", type=int, default=4,
        help="host trainers: env count of the frozen-stats eval pool",
    )
    p.add_argument(
        "--eval-steps", type=int, default=1000,
        help="host trainers: max steps per eval sweep (first episode only)",
    )
    p.add_argument(
        "--workers", type=int, default=1,
        help="host pools: worker processes the env batch shards across "
        "(envs/shard_pool.py; shared-memory step exchange, per-shard "
        "seeding identical to the in-process pool). 1 = in-process "
        "SyncVectorEnv, today's exact semantics",
    )
    p.add_argument(
        "--async-actors", type=int, default=0, metavar="A",
        help="host PPO only: decouple collection from the learner "
        "(algos/traj_queue.py) — A actor threads each drive their own "
        "pool of num_envs/A envs and push [K, E/A] blocks into a "
        "bounded trajectory queue; the learner drains continuously and "
        "corrects behavior-policy staleness per --async-correction. "
        "0 (default) = today's lockstep pipeline. Checkpointing is not "
        "yet supported in this mode.",
    )
    p.add_argument(
        "--updates-per-block", type=int, default=1, metavar="M",
        help="async mode: epoch/minibatch passes the learner reuses "
        "each consumed block for (IMPACT-style sample reuse; the "
        "clipped surrogate + V-trace targets keep reuse sound)",
    )
    p.add_argument(
        "--max-staleness", type=int, default=None, metavar="S",
        help="async mode: drop blocks whose behavior-policy version "
        "lags the learner by more than S at consumption (back-pressure "
        "drops the OLDEST data rather than blocking actors); -1 = "
        "unbounded. Default: 8 for PPO (on-policy freshness matters), "
        "unbounded for ddpg/td3/sac (replay absorbs staleness — a "
        "stale block is still valid off-policy experience)",
    )
    p.add_argument(
        "--queue-depth", type=int, default=4, metavar="D",
        help="async mode: trajectory-queue capacity in blocks (a full "
        "queue recycles its oldest block's slot for the incoming one)",
    )
    p.add_argument(
        "--data-plane", choices=("host", "device"), default="host",
        help="async mode: where trajectory blocks live between actor "
        "and learner (actor_critic_tpu/data_plane/). 'host' (default) "
        "is the PR 6 numpy TrajQueue — one host→device transfer per "
        "consumed block on the learner thread; 'device' stages encoded "
        "blocks in a donated HBM ring at collection time (actor-side "
        "put of already-encoded bytes) and the learner gathers+decodes "
        "INSIDE its jitted update — zero steady-state host→device "
        "transfers per consumed block. Never flip it on a resumed run "
        "(the save trees differ).",
    )
    p.add_argument(
        "--data-plane-codec", choices=("fp32", "f16", "int8"),
        default="fp32",
        help="device data plane: per-key block codec "
        "(data_plane/codecs.py). fp32 = raw (bitwise-equal to the host "
        "plane at depth 1); f16 halves observation bytes; int8 "
        "standardizes obs + rewards to calibrated int8 and packs the "
        "flags (~4x smaller enqueue on obs-dominated blocks). Behavior "
        "log-probs/values/actions always stay raw — quantizing them "
        "would bias the V-trace correction itself.",
    )
    p.add_argument(
        "--serve-port", type=int, default=None, metavar="PORT",
        help="async mode: serve-while-training — bind a resident "
        "policy-serving gateway (serving/) on PORT (0 = OS-assigned, "
        "printed) whose 'learner' policy hot-swaps to every published "
        "learner snapshot: /v1/act answers with the CURRENT training "
        "params, version = blocks consumed + 1. Swaps ride the "
        "checkpoint.uncommit route — steady-state serving never "
        "recompiles",
    )
    p.add_argument(
        "--serve-buckets", default="1,4,16", metavar="B,B,..",
        help="--serve-port: act bucket sizes for the resident gateway "
        "(default 1,4,16 — smaller than scripts/serve.py's ladder; the "
        "sidecar warms before training starts, so startup cost is "
        "on the training critical path)",
    )
    p.add_argument(
        "--async-correction", choices=("vtrace", "none"), default="vtrace",
        help="async mode: staleness correction — 'vtrace' (clipped "
        "importance-weighted targets under the learner's params, "
        "default) or 'none' (plain GAE under the recorded behavior "
        "values; tolerates small staleness, A3C-style)",
    )
    p.add_argument(
        "--distributed", action="store_true",
        help="multi-host learner (parallel/multihost.py): this process "
        "is one host of a jax.distributed fleet — its actor fleet "
        "(--async-actors, host PPO only) feeds a local queue and the "
        "learner data-shards update batches across the global device "
        "mesh (or gossips params with --gossip). Requires --coordinator "
        "+ --num-processes + --process-id (or --gossip with a shared "
        "--mailbox-dir). For a CPU local cluster use "
        "scripts/launch_multihost.py instead.",
    )
    p.add_argument(
        "--coordinator", metavar="HOST:PORT", default="",
        help="jax.distributed coordinator address (rank 0's host). "
        "Needed for the sync all-reduce mode; optional under --gossip "
        "(peer-to-peer exchange never enters a collective).",
    )
    p.add_argument("--num-processes", type=int, default=1,
                   help="fleet size under --distributed")
    p.add_argument("--process-id", type=int, default=0,
                   help="this host's rank under --distributed")
    p.add_argument(
        "--gossip", action="store_true",
        help="distributed mode: exchange parameters peer-to-peer on a "
        "rotating ring schedule (no global barrier — a straggler host "
        "degrades fleet throughput instead of stalling it) instead of "
        "the synchronous all-reduce learner",
    )
    p.add_argument("--gossip-every", type=int, default=1, metavar="N",
                   help="consumed blocks between gossip exchanges")
    p.add_argument("--gossip-weight", type=float, default=0.5, metavar="W",
                   help="peer mixing weight in [0, 1]: params <- "
                   "(1-W) own + W peer")
    p.add_argument("--mailbox-dir", default="",
                   help="shared directory for the gossip param mailbox "
                   "(required for --gossip with more than one host)")
    p.add_argument(
        "--replay-dtype", choices=("fp32", "mixed", "int8"), default=None,
        help="off-policy algos (ddpg/td3/sac): replay-ring storage codec "
        "(replay/quantize.py). 'mixed' stores obs/rewards as int8 behind "
        "running mean/scale standardization with actions kept fp32 "
        "(~3x transitions per HBM byte); 'int8' also quantizes the "
        "bounded actions (~4x, aggressive); default fp32. Equivalent to "
        "--set replay_dtype=...; never flip it on a resumed run whose "
        "checkpoint carries a full ring (the template dtype must match).",
    )
    p.add_argument(
        "--update-dtype", choices=("fp32", "bf16"), default=None,
        help="update-compute precision (ISSUE 19). 'bf16' runs the "
        "network torso/head matmuls in bfloat16 with params, optimizer "
        "state, and every loss reduction kept fp32 (explicit fp32 "
        "accumulators; the heads cast outputs up before the loss); "
        "default fp32. Equivalent to --set bf16_compute=true. Eval "
        "parity vs fp32 is gated per algo in tests/test_bf16.py.",
    )
    p.add_argument("--quiet", action="store_true", help="no stdout metric echo")
    p.add_argument(
        "--no-overlap", action="store_true",
        help="host envs: disable the numpy actor mirror / async device "
        "update overlap (A/B baseline; models/host_actor.py)",
    )
    p.add_argument(
        "--scale-actions", action=argparse.BooleanOptionalAction,
        default=None,
        help="continuous envs: affine-map policy actions from [-1,1] "
        "onto the env's action bounds instead of clipping — keeps "
        "replayed == executed actions on narrow-bound envs like "
        "Humanoid-v5 (±0.4). Default: each env's own convention (host "
        "pools clip; jax:pendulum scales). Never flip this on a resumed "
        "run: the restored networks trained under the other convention.",
    )
    p.add_argument(
        "--compile-cache-dir", default=None, metavar="DIR",
        help="persistent XLA compilation cache (utils/compile_cache.py): "
        "compiled programs are written here and later processes (e.g. "
        "run_resumable.sh retry legs) deserialize instead of recompiling. "
        "JAX_COMPILATION_CACHE_DIR, when set, names the directory and "
        "wins over this flag; otherwise DIR, and by default "
        "<checkout>/.jax_cache. 'none' enables no cache.",
    )
    p.add_argument(
        "--warmup", action=argparse.BooleanOptionalAction, default=True,
        help="AOT-compile every registered jitted entry point (abstract "
        "shapes from the env spec + config) on a background thread while "
        "the env pool spawns/resets and the checkpoint restores, so "
        "time-to-first-step hides compile instead of serializing on it "
        "(utils/compile_cache.py warmup registry).",
    )
    p.add_argument("--ckpt-dir", help="orbax checkpoint dir")
    p.add_argument("--save-every", type=int, default=100)
    p.add_argument(
        "--no-save-replay", action="store_true",
        help="off-policy host runs: exclude the replay ring from "
        "checkpoints (a Humanoid-scale ring is ~3 GB per save). Resuming "
        "such a checkpoint restarts with an EMPTY buffer: updates pause "
        "until it refills past one batch, then continue on fresh "
        "experience only.",
    )
    p.add_argument("--resume", action="store_true", help="resume from --ckpt-dir")
    p.add_argument(
        "--stall-timeout", type=float, default=0,
        help="seconds without training progress before the process exits "
        "42 (device presumed wedged) so a retry loop can --resume; "
        "0 = off. Pair with --ckpt-dir/--save-every.",
    )
    p.add_argument("--list-presets", action="store_true")
    args = p.parse_args(argv)
    # `startup` runs from here to the loop's first step: the function that
    # runs the loop closes it (`end_startup`), and any other way out of
    # main closes it below, so no span is left open.
    args.startup = telemetry.span("startup")
    args.startup.__enter__()
    try:
        return run_main(args)
    finally:
        end_startup(args)


def end_startup(args) -> None:
    """Close main's `startup` span, once: the loop's first step is next."""
    span, args.startup = getattr(args, "startup", None), None
    if span is not None:
        span.__exit__(None, None, None)


def run_main(args) -> int:
    """main's body once the arguments are parsed."""
    if args.telemetry_port is not None and not args.telemetry_dir:
        raise SystemExit(
            "--telemetry-port requires --telemetry-dir (the exporter "
            "serves the session's sinks and /profile captures land in "
            "that directory)"
        )
    if args.telemetry_sample_s <= 0:
        raise SystemExit("--telemetry-sample-s must be > 0")
    from actor_critic_tpu.telemetry.exporter import validate_bind

    try:
        validate_bind(args.telemetry_bind, distributed=args.distributed)
    except ValueError as e:
        raise SystemExit(str(e))

    from actor_critic_tpu.config import (
        PRESETS, parse_env_set_args, parse_set_args, resolve,
    )
    from actor_critic_tpu.utils.cadence import finite_or_none
    from actor_critic_tpu.utils.logging import JsonlLogger

    if args.list_presets:
        for name, pre in PRESETS.items():
            print(f"{name:18s} {pre.algo:7s} {pre.env:22s} {pre.description}")
        return 0

    preset = resolve(
        args.preset, args.algo, args.env, parse_set_args(args.set),
        env_overrides=parse_env_set_args(args.env_set),
    )
    if args.replay_dtype is not None:
        if not hasattr(preset.config, "replay_dtype"):
            raise SystemExit(
                f"--replay-dtype applies to the off-policy algos "
                f"(ddpg/td3/sac) with an HBM replay ring; {preset.algo} "
                "has no replay storage"
            )
        preset = dataclasses.replace(
            preset,
            config=dataclasses.replace(
                preset.config, replay_dtype=args.replay_dtype
            ),
        )
    if args.update_dtype is not None:
        if not hasattr(preset.config, "bf16_compute"):
            raise SystemExit(
                f"--update-dtype has no effect on {preset.algo}: its "
                "config carries no bf16_compute switch"
            )
        preset = dataclasses.replace(
            preset,
            config=dataclasses.replace(
                preset.config, bf16_compute=(args.update_dtype == "bf16")
            ),
        )
    if args.iterations is None:
        args.iterations = preset.iterations

    if args.curriculum:
        # Every doomed --curriculum combination exits before any env or
        # device work: the schedule drives a fused mixture fleet and
        # advances on the eval cadence.
        if not preset.env.startswith("mixture:"):
            raise SystemExit(
                "--curriculum re-weights a mixture fleet's type draw "
                "(--env mixture:<members>); it has no effect on "
                f"{preset.env!r}"
            )
        if args.eval_every <= 0:
            raise SystemExit(
                "--curriculum advances on learner eval progress — pass "
                "--eval-every N"
            )
        from actor_critic_tpu.envs import mixture as _mixture
        from actor_critic_tpu.envs import parse_mixture_spec

        try:
            names = tuple(
                n for n, _ in
                parse_mixture_spec(preset.env.partition(":")[2])
            )
            _mixture.parse_curriculum(args.curriculum, names)
        except ValueError as e:
            raise SystemExit(f"bad --curriculum: {e}") from e
        # Type re-draws are what the weights act on; an explicit
        # --env-set redraw_types=false wins (and makes the schedule a
        # weights-recording no-op, which the user asked for).
        preset.env_kwargs.setdefault("redraw_types", True)

    if args.data_plane == "device":
        # The data plane is the actor→learner hand-off: without actor
        # services there is no queue to relocate, and the multi-host
        # learner shard_maps HOST arrays into the global batch — exit
        # with advice before any env or device work.
        if args.async_actors <= 0:
            raise SystemExit(
                "--data-plane device relocates the async actor–learner "
                "hand-off into HBM — pass --async-actors N (the lockstep "
                "pipeline has no trajectory queue to relocate)"
            )
        if args.distributed:
            raise SystemExit(
                "--data-plane device is single-host for now: the "
                "--distributed sync learner builds its global batch from "
                "host arrays (make_array_from_process_local_data) — drop "
                "--distributed or use --data-plane host"
            )

    if args.serve_port is not None:
        # Serve-while-training rides the async publish cadence: the
        # lockstep/fused paths have no PolicyPublisher to hook.
        if args.async_actors <= 0:
            raise SystemExit(
                "--serve-port hooks the async learner's per-block "
                "publish (PolicyPublisher) — pass --async-actors N"
            )
        if args.distributed:
            raise SystemExit(
                "--serve-port is single-host (the resident gateway "
                "swaps from THIS process's publish hook); a fleet "
                "serves through scripts/serve.py --distributed + "
                "scripts/serve_fleet.py instead"
            )

    if args.distributed:
        # Every doomed flag combination exits HERE, before the blocking
        # coordinator handshake below (a misconfigured fleet member
        # hanging at jax.distributed.initialize is far worse than a
        # SystemExit). Resolving the preset first costs only module
        # imports — the XLA backend stays uninitialized until pools /
        # params / warmup touch it, which all happen after.
        if args.async_actors <= 0:
            raise SystemExit(
                "--distributed drives the async actor–learner stack: "
                "each host runs its own actor fleet — pass "
                "--async-actors N (host PPO)"
            )
        if preset.algo != "ppo":
            raise SystemExit(
                "--distributed drives the PPO multi-host learner "
                "(parallel/multihost.py); the off-policy async drivers "
                "are single-host — drop --distributed or use --algo ppo"
            )
        if not args.gossip and not args.coordinator:
            raise SystemExit(
                "--distributed sync mode needs --coordinator HOST:PORT "
                "(+ --num-processes/--process-id); or pass --gossip for "
                "the peer-to-peer mode"
            )
        if not args.gossip and args.async_correction != "vtrace":
            raise SystemExit(
                "--distributed sync mode shard_maps the V-trace-"
                "corrected update; --async-correction none is not "
                "supported there (gossip mode and single-host async "
                "accept it)"
            )
        if args.gossip and args.num_processes > 1 and not args.mailbox_dir:
            raise SystemExit(
                "--gossip with more than one host needs a shared "
                "--mailbox-dir"
            )
        if args.coordinator:
            # BEFORE anything initializes the XLA backend (the warmup
            # thread, pool construction, param init all would).
            from actor_critic_tpu.parallel.multihost import distributed_init

            distributed_init(
                args.coordinator, args.num_processes, args.process_id
            )
        # Rank affinity for the shared artifact paths: every host of
        # the fleet runs this same main() with the same flags, so an
        # unsuffixed --telemetry-dir/--metrics would interleave N
        # hosts' appends into ONE spans.jsonl/metrics.jsonl (torn lines
        # on a shared filesystem; scrambled rows even locally). Same
        # host<rank>/ convention as scripts/launch_multihost.py.
        rank = args.process_id
        if args.telemetry_dir:
            args.telemetry_dir = os.path.join(
                args.telemetry_dir, f"host{rank}"
            )
        root, ext = os.path.splitext(args.metrics)
        args.metrics = f"{root}.host{rank}{ext}"

    print(
        f"algo={preset.algo} env={preset.env} iterations={args.iterations} "
        f"config={dataclasses.asdict(preset.config)} "
        f"env_kwargs={preset.env_kwargs}",
        flush=True,
    )
    from actor_critic_tpu.utils import compile_cache

    cache_dir = compile_cache.resolve_cache_dir(args.compile_cache_dir)
    if cache_dir is not None:
        # Before the first trace/compile of the process: every program —
        # including the warmup thread's — must land in (or hit) the
        # on-disk cache so resumed legs start near-instantly.
        compile_cache.enable_persistent_cache(cache_dir)
        print(f"compile cache: {cache_dir}", flush=True)
    if args.async_actors > 0:
        if (args.ckpt_dir or args.resume) and (
            preset.algo != "ppo" or args.distributed
        ):
            raise SystemExit(
                "--async-actors checkpointing is wired for single-host "
                "PPO only (the save tree carries every actor pool's "
                "normalizer state — ppo.train_host_async); off-policy "
                "async and --distributed runs don't support "
                "--ckpt-dir/--resume yet"
            )
        if args.no_overlap:
            print(
                "--no-overlap is meaningless with --async-actors (actors "
                "always act through the numpy mirror); ignored",
                flush=True,
            )
    pools = None
    telemetry_session = None
    watchdog = None
    try:
        # The session comes before the environment is built, so that no
        # start-up span closes before it exists, and after the
        # distributed handshake and the cache configuration, which must
        # come before the backend starts.
        if args.telemetry_dir:
            telemetry_session = telemetry.TelemetrySession(
                args.telemetry_dir,
                run_info={
                    "algo": preset.algo,
                    "env": preset.env,
                    "iterations": args.iterations,
                    "seed": args.seed,
                    "config": dataclasses.asdict(preset.config),
                },
                resource_interval_s=args.telemetry_sample_s,
                serve_port=args.telemetry_port,
                serve_host=args.telemetry_bind,
            )
            telemetry.set_current(telemetry_session)
            if telemetry_session.exporter is not None:
                print(
                    f"telemetry exporter: {telemetry_session.exporter.url}"
                    "/metrics /healthz /profile?iters=N",
                    flush=True,
                )
            # `kill -USR2 <pid>` arms an on-demand profile capture even when
            # no --telemetry-port was given.
            from actor_critic_tpu.telemetry.profiler import install_sigusr2

            install_sigusr2()
        with telemetry.span("init_env"):
            if args.async_actors > 0:
                pools = build_actor_pools(preset, args, args.async_actors)
                env, fused = pools[0], False
            else:
                env, fused = build_env(
                    preset.env, preset.algo, preset.config, args.seed,
                    scale_actions=args.scale_actions,
                    env_kwargs=preset.env_kwargs, workers=args.workers,
                )
        if fused and args.workers > 1:
            print("--workers applies to host pools only; ignored for jax:* "
                  "envs (their rollouts are fused on-device)", flush=True)
        # Host pools carry their ACTION convention in the checkpoint metrics
        # too (host_loop's _pool_scale_actions), but env_kwargs exist only
        # here — the sidecar guards both paths against resuming into a
        # different env (kwargs) or convention (fused envs).
        check_env_convention(
            args.ckpt_dir, preset.env, args.scale_actions, args.resume,
            env_kwargs=preset.env_kwargs,
        )

        if args.warmup and cache_dir is None:
            # AOT-compiled executables are never installed into the jit
            # dispatch cache (JAX AOT contract) — without the persistent
            # cache to carry them to the loop's own jit objects, warmup
            # would just compile everything twice on a contended host.
            print(
                "AOT warmup skipped: requires the persistent compile cache "
                "(--compile-cache-dir none turned it off)",
                flush=True,
            )
        elif args.warmup:
            # Background AOT warmup: compile every registered entry point
            # (abstract arg shapes from spec + config) while the host side
            # resets pools / restores checkpoints. XLA compilation releases
            # the GIL, so this genuinely overlaps; each compile lands in the
            # persistent cache, so the loop's own first dispatch re-traces
            # and hits instead of compiling.
            ctx = compile_cache.WarmupContext(
                algo=preset.algo, fused=fused, spec=env.spec,
                cfg=preset.config, env=env if fused else None,
                chunk=max(1, args.chunk) if fused else 1,
                iterations=args.iterations, eval_every=args.eval_every,
                eval_envs=args.eval_envs, overlap=not args.no_overlap,
                resume=args.resume,
                async_actors=args.async_actors,
                async_correction=args.async_correction,
                data_plane=args.data_plane,
                plane_codec=args.data_plane_codec,
                queue_depth=args.queue_depth,
            )
            plan = compile_cache.plan_warmup(ctx)
            if plan:
                print(
                    f"AOT warmup: {len(plan)} entry point(s) compiling in "
                    "the background: " + ", ".join(n for n, _ in plan),
                    flush=True,
                )
                compile_cache.WarmupRunner(plan).start()

        if args.stall_timeout > 0:
            from actor_critic_tpu.utils.watchdog import StallWatchdog

            if getattr(args, "chunk", 1) > 1:
                # One heartbeat per chunk: a timeout shorter than a chunk's
                # wall time would misread normal progress as a stall and
                # kill/resume in a loop that never clears the chunk.
                print(
                    f"watchdog with --chunk {args.chunk}: --stall-timeout "
                    f"{args.stall_timeout:g}s must exceed one chunk's wall "
                    "time or the run will be killed mid-chunk", flush=True,
                )
            watchdog = StallWatchdog(args.stall_timeout).start()
        t0 = time.time()
        with JsonlLogger(args.metrics, echo=not args.quiet) as logger:
            if fused:
                final = run_fused(env, preset, args, logger)
            else:
                if getattr(args, "chunk", 1) > 1:
                    print("--chunk applies to fused (jax:*) envs only; "
                          "ignored for host pools", flush=True)
                if pools is not None and args.distributed:
                    final = run_multihost(pools, preset, args, logger)
                elif pools is not None:
                    final = run_host_async(pools, preset, args, logger)
                else:
                    final = run_host(env, preset, args, logger)
    finally:
        if watchdog is not None:
            watchdog.stop()
        if telemetry_session is not None:
            telemetry_session.close()
        if pools is not None:
            for p_ in pools:
                p_.close()
    wall = time.time() - t0
    print(
        json.dumps(
            {
                "algo": preset.algo,
                "env": preset.env,
                "iterations": args.iterations,
                # Async mode consumes [K, E/A] blocks: env_steps here is
                # what the LEARNER consumed (actor-side collection,
                # drops included, rides the metrics rows).
                "env_steps": args.iterations
                * steps_per_iteration(preset.algo, preset.config)
                // max(1, args.async_actors),
                "wall_s": round(wall, 2),
                # NaN/Inf → null: the summary line must stay strict JSON
                **{
                    k: (None if (f := finite_or_none(v)) is None else round(f, 5))
                    for k, v in final.items()
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
