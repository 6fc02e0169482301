#!/usr/bin/env python
"""Policy-serving gateway CLI (ISSUE 10): micro-batched act() over HTTP.

    # random-init PPO CartPole policy on an ephemeral port (demo/bench)
    python scripts/serve.py --preset ppo_cartpole --random-init --port 0

    # two resident checkpoints, hot-swappable via POST /v1/swap
    python scripts/serve.py --algo ppo --env jax:cartpole \
        --policy champ=runs/champ --policy canary=runs/canary \
        --default champ --port 8000 --buckets 1,4,16,64 --max-wait-us 2000

Checkpoints are params-only trees written by
`serving.export_policy_params` (a training run exports its actor/policy
params; the full trainer save tree carries optimizer/env state a server
has no use for). Startup: the serving warmup planner AOT-compiles every
act bucket on a background thread into the persistent compile cache
(`compile_cache.resolve_cache_dir`), then each architecture is warmed with one
concrete dispatch per bucket BEFORE the gateway binds — steady-state
serving is 0-recompile. `--port 0` binds an OS-assigned port and prints
the actual one (the load generator and CI never race for a fixed port).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def spec_for(env: str, env_kwargs: dict):
    """EnvSpec for an env selector without building a training pool:
    jax:<name> reads the maker's spec (cheap — no device rollout state);
    host:<id> builds a 1-env pool just long enough to read the spaces."""
    from actor_critic_tpu import envs as E

    if env.startswith("jax:"):
        makers = {
            "cartpole": E.make_cartpole,
            "pendulum": E.make_pendulum,
            "pong": E.make_pong,
            "point_mass": E.make_point_mass,
            "bandit": E.make_bandit,
            "two_state_mdp": E.make_two_state_mdp,
        }
        name = env[4:]
        if name not in makers:
            raise SystemExit(
                f"unknown jax env {name!r}; valid: {sorted(makers)}"
            )
        return makers[name](**env_kwargs).spec
    if env.startswith("host:"):
        from actor_critic_tpu.envs.host_pool import HostEnvPool

        pool = HostEnvPool(env[5:], 1, seed=0, workers=1)
        try:
            return pool.spec
        finally:
            pool.close()
    raise SystemExit(f"env must be jax:<name> or host:<gym id>, got {env!r}")


def parse_policies(pairs: list[str]) -> dict[str, str]:
    out: dict[str, str] = {}
    for pair in pairs:
        if "=" not in pair:
            raise SystemExit(f"--policy wants ID=CKPT_DIR, got {pair!r}")
        pid, path = pair.split("=", 1)
        out[pid] = path
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[1].strip())
    p.add_argument("--preset", help="config preset (see train.py --list)")
    p.add_argument("--algo", help="algo when not using --preset")
    p.add_argument("--env", help="env selector when not using --preset")
    p.add_argument(
        "--set", action="append", default=[], metavar="K=V",
        help="config overrides (train.py --set semantics)",
    )
    p.add_argument(
        "--env-set", action="append", default=[], metavar="K=V",
        help="env maker kwargs (train.py --env-set semantics)",
    )
    p.add_argument(
        "--policy", action="append", default=[], metavar="ID=CKPT_DIR",
        help="resident policy from a params-only checkpoint (repeatable)",
    )
    p.add_argument(
        "--default", default=None, metavar="ID",
        help="default policy id (default: first --policy / the random one)",
    )
    p.add_argument(
        "--random-init", action="store_true",
        help="add a freshly-initialized 'default' policy (demo/bench)",
    )
    p.add_argument(
        "--port", type=int, default=8000,
        help="gateway port; 0 binds an OS-assigned ephemeral port "
        "(default 8000)",
    )
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument(
        "--buckets", default="1,2,4,8,16,32,64",
        help="act bucket sizes, comma list (default 1,2,...,64)",
    )
    p.add_argument(
        "--max-wait-us", action="append", default=[], metavar="[ID=]US",
        help="micro-batch window: max µs the dispatcher holds a flush "
        "while rows accumulate (p99 vs occupancy knob; default 2000). "
        "Repeatable; ID=US sets a per-policy window that rides the "
        "policy handle across hot-swaps (the SLO-class batching tier)",
    )
    p.add_argument(
        "--queue-limit", type=int, default=256,
        help="bounded request queue capacity; overflow answers 503",
    )
    p.add_argument(
        "--max-inflight", type=int, default=1,
        help="overlapping in-flight dispatches: >1 packs flush N+1 "
        "while flush N is on device (default 1 — classic single-"
        "dispatcher loop)",
    )
    p.add_argument(
        "--shed-burn-threshold", type=float, default=None,
        help="admission control: shed (503) new requests to an SLO-"
        "classed policy whose burn rate is at/over this once the queue "
        "passes half capacity, instead of queueing certain violations "
        "(default off; 1.0 = shed once the policy eats budget at "
        "exactly the budget rate)",
    )
    p.add_argument(
        "--sample", action="store_true",
        help="serve sampled (stochastic) actions instead of greedy "
        "(PPO only)",
    )
    p.add_argument(
        "--backend", choices=("xla", "mirror", "auto"), default="xla",
        help="acting backend: 'mirror' serves MLP policies through the "
        "numpy host mirror (models/host_actor) — no XLA dispatch, the "
        "right trade on CPU-only serving hosts; 'auto' measures batch-1 "
        "dispatch walls of both at startup and picks the faster",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--telemetry-dir", default=None,
        help="attach a TelemetrySession: /metrics serves the full "
        "exporter exposition and the serving gauge is sampled to disk",
    )
    p.add_argument(
        "--telemetry-bind", default="127.0.0.1", metavar="HOST",
        help="bind address for the session's telemetry exporter "
        "(default 127.0.0.1; non-loopback refused unless --distributed "
        "— /metrics has no auth)",
    )
    p.add_argument(
        "--slo-ms", action="append", default=[], metavar="[ID=]MS",
        help="per-policy latency SLO class in ms (repeatable; plain MS "
        "applies to every policy without its own). Rides the policy "
        "handle across hot-swaps; /metrics exports slo_burn per policy",
    )
    p.add_argument(
        "--compile-cache-dir", default=None,
        help="persistent XLA compile cache (warm restarts skip bucket "
        "compiles entirely): JAX_COMPILATION_CACHE_DIR when set, else "
        "this DIR, else <checkout>/.jax_cache; 'none' enables no cache",
    )
    p.add_argument(
        "--no-warmup", action="store_true",
        help="skip startup bucket compilation (first flushes compile)",
    )
    p.add_argument(
        "--distributed", action="store_true",
        help="this gateway serves one host of a gossip fleet: /healthz "
        "surfaces fleet membership (rank, world, per-peer mailbox age) "
        "read from --mailbox-dir and answers 503 when a peer's last "
        "publish is older than --stale-after-s",
    )
    p.add_argument(
        "--mailbox-dir", default=None,
        help="the fleet's shared gossip mailbox directory "
        "(train.py/launch_multihost --mailbox-dir)",
    )
    p.add_argument("--rank", type=int, default=0,
                   help="this host's fleet rank (default 0)")
    p.add_argument("--world", type=int, default=None,
                   help="fleet size (required with --distributed)")
    p.add_argument(
        "--stale-after-s", type=float, default=30.0,
        help="peer mailbox age bound before /healthz degrades to 503 "
        "(default 30)",
    )
    p.add_argument(
        "--sync-mailbox", default=None, metavar="DIR",
        help="replica-to-replica policy propagation (ISSUE 17): poll "
        "this mailbox directory for published (version, params) "
        "snapshots and hot-swap them into --sync-policy — version "
        "updates reach every replica without a restart. Independent "
        "of --distributed/--mailbox-dir (that one is fleet HEALTH; "
        "this one is the params feed)",
    )
    p.add_argument(
        "--sync-policy", default=None, metavar="ID",
        help="--sync-mailbox: resident policy the snapshots swap into "
        "(default: the default policy)",
    )
    p.add_argument(
        "--sync-rank", type=int, default=0, metavar="R",
        help="--sync-mailbox: publisher's mailbox rank to read "
        "(default 0)",
    )
    p.add_argument(
        "--sync-poll-s", type=float, default=0.25, metavar="S",
        help="--sync-mailbox: poll interval in seconds (default 0.25)",
    )
    args = p.parse_args(argv)

    if args.distributed and (args.mailbox_dir is None or args.world is None):
        raise SystemExit(
            "--distributed wants --mailbox-dir and --world (the fleet "
            "this gateway is a member of)"
        )
    from actor_critic_tpu.telemetry.exporter import validate_bind

    try:
        validate_bind(args.telemetry_bind, distributed=args.distributed)
    except ValueError as e:
        raise SystemExit(str(e))

    def parse_classed(items: list[str], flag: str, unit: str):
        default = None
        by_id: dict[str, float] = {}
        for item in items:
            try:
                if "=" in item:
                    pid, v = item.split("=", 1)
                    by_id[pid] = float(v)
                else:
                    default = float(item)
            except ValueError:
                raise SystemExit(f"{flag} wants [ID=]{unit}, got {item!r}")
        return default, by_id

    slo_default, slo_by_id = parse_classed(args.slo_ms, "--slo-ms", "MS")
    # The GLOBAL window feeds the batcher; per-policy ones ride handles.
    wait_default, wait_by_id = parse_classed(
        args.max_wait_us, "--max-wait-us", "US"
    )
    if wait_default is None:
        wait_default = 2000.0

    from actor_critic_tpu import config as config_mod
    from actor_critic_tpu import serving
    from actor_critic_tpu.utils import compile_cache

    preset = config_mod.resolve(
        args.preset, args.algo, args.env,
        config_mod.parse_set_args(args.set),
        config_mod.parse_env_set_args(args.env_set),
    )
    buckets = tuple(int(b) for b in args.buckets.split(",") if b.strip())
    spec = spec_for(preset.env, preset.env_kwargs)

    cache_dir = compile_cache.resolve_cache_dir(args.compile_cache_dir)
    if cache_dir is not None:
        compile_cache.enable_persistent_cache(cache_dir)

    session = None
    if args.telemetry_dir:
        from actor_critic_tpu import telemetry

        session = telemetry.TelemetrySession(
            args.telemetry_dir,
            run_info={"mode": "serve", "algo": preset.algo,
                      "env": preset.env, "buckets": list(buckets)},
            # Exporter sidecar on --telemetry-bind: the fleet
            # aggregation path (/fleetz on any member) scrapes THIS
            # per-rank endpoint, announced below under --distributed.
            serve_port=0,
            serve_host=args.telemetry_bind,
        )
        telemetry.set_current(session)

    runner = None
    if not args.no_warmup and args.backend in ("xla", "auto"):
        ctx = compile_cache.WarmupContext(
            algo=preset.algo, fused=False, spec=spec, cfg=preset.config,
            serving_buckets=buckets, serving_sample=args.sample,
        )
        runner = compile_cache.start_warmup(ctx)

    engine = serving.PolicyEngine(
        spec, preset.config, algo=preset.algo, buckets=buckets,
        sample=args.sample, seed=args.seed, backend=args.backend,
    )
    store = serving.PolicyStore()
    policies = parse_policies(args.policy)
    if not policies and not args.random_init:
        raise SystemExit("no policies: pass --policy ID=CKPT_DIR or "
                         "--random-init")
    resident = set(policies) | ({"default"} if args.random_init else set())
    if args.default is not None and args.default not in resident:
        raise SystemExit(
            f"--default {args.default!r} names no policy; resident: "
            f"{sorted(resident)}"
        )
    template = serving.init_params(spec, preset.config, preset.algo,
                                   seed=args.seed)
    if args.backend == "auto":
        # Fix the backend from measured batch-1 walls BEFORE any
        # policy installs (prepare_params needs a concrete backend).
        # The init template shares the checkpoints' architecture, so
        # the measurement transfers.
        choice = engine.resolve_backend(template)
        print(f"auto backend: {choice} ({engine.auto_choice})", flush=True)
    for pid, ckpt_dir in policies.items():
        params = serving.restore_policy_params(ckpt_dir, template)
        store.register(pid, engine, params, default=(pid == args.default),
                       slo_ms=slo_by_id.get(pid, slo_default),
                       max_wait_us=wait_by_id.get(pid))
        print(f"policy {pid!r} <- {ckpt_dir}", flush=True)
    if args.random_init:
        # Without --default the FIRST registration keeps the route (a
        # loaded checkpoint, when any was given): the random policy
        # must never silently steal traffic from a real one.
        store.register("default", engine, template,
                       default=(args.default == "default"),
                       slo_ms=slo_by_id.get("default", slo_default),
                       max_wait_us=wait_by_id.get("default"))
        print("policy 'default' <- random init", flush=True)
    for flag, by_id in (("--slo-ms", slo_by_id),
                        ("--max-wait-us", wait_by_id)):
        unknown = set(by_id) - set(store.ids())
        if unknown:
            raise SystemExit(
                f"{flag} names no resident policy: {sorted(unknown)}"
            )

    if runner is not None:
        runner.wait(timeout=120)
    if not args.no_warmup:
        # One concrete dispatch per bucket so the live jit cache is hot
        # (hits the persistent-cache entries the planner just wrote);
        # 0 on the mirror backend, where nothing compiles.
        n_warm = engine.warm(store.get(store.default_id).params)
        print(f"warm: {n_warm} act buckets compiled", flush=True)

    fleet = None
    aggregator = None
    if args.distributed:
        from actor_critic_tpu.parallel.multihost import FleetMonitor
        from actor_critic_tpu.telemetry.fleet import (
            FleetAggregator,
            announce_endpoint,
        )

        fleet = FleetMonitor(
            args.mailbox_dir, args.rank, args.world,
            stale_after_s=args.stale_after_s,
        )
        # Fleet metrics plane (ISSUE 16): announce this rank's exporter
        # into the shared mailbox and serve merged /fleetz views from
        # every member's discovered endpoint.
        if session is not None and session.exporter_port is not None:
            announce_endpoint(
                args.mailbox_dir, args.rank,
                f"http://{args.telemetry_bind}:{session.exporter_port}",
            )
        aggregator = FleetAggregator(mailbox_dir=args.mailbox_dir)

    syncer = None
    if args.sync_mailbox:
        sync_pid = args.sync_policy or store.default_id
        if sync_pid not in store.ids():
            raise SystemExit(
                f"--sync-policy {sync_pid!r} names no resident policy; "
                f"resident: {sorted(store.ids())}"
            )
        syncer = serving.MailboxPolicySyncer(
            store, sync_pid, args.sync_mailbox, rank=args.sync_rank,
            template=template, poll_s=args.sync_poll_s,
        ).start()
        print(
            f"policy sync: {sync_pid!r} <- {args.sync_mailbox} "
            f"(rank {args.sync_rank}, every {args.sync_poll_s:g}s)",
            flush=True,
        )

    gateway = serving.ServeGateway(
        store, port=args.port, host=args.host, session=session,
        max_wait_us=wait_default, queue_limit=args.queue_limit,
        fleet=fleet, aggregator=aggregator,
        max_inflight=args.max_inflight,
        shed_burn_threshold=args.shed_burn_threshold,
    )
    # The ACTUAL bound port — with --port 0 this is the OS-assigned one.
    routes = "/v1/swap /v1/policies /metrics /healthz" + (
        " /fleetz /fleetz/metrics" if aggregator is not None else ""
    )
    print(
        f"serving gateway: {gateway.url}/v1/act "
        f"(policies: {sorted(store.ids())}, default {store.default_id!r}; "
        f"also {routes})",
        flush=True,
    )
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("shutting down", flush=True)
    finally:
        gateway.close()
        if syncer is not None:
            syncer.close()
        if session is not None:
            session.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
