#!/usr/bin/env python
"""Local multi-process launcher for the distributed actor–learner
(`parallel/multihost.py`, ISSUE 9).

Spawns N worker processes against a localhost `jax.distributed`
coordinator — the CPU-drivable stand-in for a TPU pod launch — runs the
per-process learner in sync (global all-reduce) or gossip (peer-to-peer
ring) mode, and aggregates fleet throughput. One JSON line on stdout.

    python scripts/launch_multihost.py --processes 2              # sync
    python scripts/launch_multihost.py --processes 4 --mode gossip
    python scripts/launch_multihost.py --processes 2 --straggler-rank 0 \
        --straggler-extra-s 0.006                # inject a slow host
    python scripts/launch_multihost.py --smoke   # tier-1 2-process check

Envs are the sleep-padded CartPole testbed (`envs/sleep_pad.py`): real
dynamics under a simulator-shaped wall cost, so process-level overlap
shows on any host.
`--straggler-rank R` pads rank R's envs further: sync mode stalls the
fleet at the all-reduce barrier; gossip mode degrades only R's own
contribution — the straggler-does-not-stall acceptance row.

On a real pod, run one `train.py --distributed --coordinator ...`
process per host instead; this launcher exists so tier-1 covers the
stack with no TPU present.

Exit codes: 0 ok; 1 a worker failed or a consistency check tripped.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# worker (one per process)
# ---------------------------------------------------------------------------


def run_worker(args) -> int:
    # Backend-affecting setup BEFORE any jax backend init.
    from actor_critic_tpu.parallel import multihost

    if args.mode == "sync":
        multihost.distributed_init(
            coordinator=f"127.0.0.1:{args.port}",
            num_processes=args.processes,
            process_id=args.rank,
        )
    import numpy as np

    from actor_critic_tpu import telemetry
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.algos.host_loop import host_evaluate
    from actor_critic_tpu.envs.host_pool import HostEnvPool
    from actor_critic_tpu.envs.sleep_pad import QUALIFIED_CARTPOLE_ID
    from actor_critic_tpu.models import host_actor

    session = None
    if args.telemetry_dir:
        host_dir = os.path.join(args.telemetry_dir, f"host{args.rank}")
        session = telemetry.TelemetrySession(
            host_dir,
            run_info={
                "multihost_rank": args.rank, "mode": args.mode,
                "seed": args.seed,
            },
            serve_port=0,
        )
        telemetry.set_current(session)
        multihost.host_lane(args.rank)
        if args.mailbox_dir:
            # Announce this rank's exporter into the gossip mailbox so
            # any process sharing it (rank 0's rollup below, a serving
            # gateway's /fleetz) can discover and scrape the fleet.
            from actor_critic_tpu.telemetry import fleet as tfleet

            tfleet.announce_endpoint(
                args.mailbox_dir, args.rank,
                f"http://127.0.0.1:{session.exporter_port}",
            )

    sleep_s = args.sleep_s
    if args.rank == args.straggler_rank:
        sleep_s += args.straggler_extra_s
    cfg = ppo.PPOConfig(
        num_envs=args.num_envs,
        rollout_steps=args.rollout_steps,
        epochs=args.epochs,
        num_minibatches=args.minibatches,
        lr=args.lr,
        hidden=(32,),
        entropy_coef=0.001,
    )
    E_a = args.num_envs // args.actors
    pools = [
        HostEnvPool(
            QUALIFIED_CARTPOLE_ID, E_a,
            seed=args.seed + (args.rank * args.actors + i) * 100_003,
            env_kwargs={"sleep_s": sleep_s},
        )
        for i in range(args.actors)
    ]
    try:
        np_params, history, summary = multihost.train_multihost(
            pools, cfg,
            args.iterations if args.duration_s <= 0 else 1_000_000,
            duration_s=args.duration_s if args.duration_s > 0 else None,
            rank=args.rank, world=args.processes, mode=args.mode,
            seed=args.seed, log_every=0,
            queue_depth=args.queue_depth, max_staleness=args.max_staleness,
            gossip=multihost.GossipConfig(
                every=args.gossip_every, weight=args.gossip_weight,
            ),
            mailbox_dir=args.mailbox_dir or None,
        )
        eval_return = None
        if args.eval_steps > 0:
            greedy = host_actor.make_ppo_host_greedy(pools[-1].spec, cfg)
            eval_pool = pools[-1].eval_pool(4)
            try:
                eval_return = host_evaluate(
                    eval_pool,
                    lambda o: np.asarray(greedy(np_params, o)),
                    max_steps=args.eval_steps,
                )
            finally:
                eval_pool.close()
        summary["eval_return"] = eval_return
        last = history[-1][1] if history else {}
        summary["last_loss"] = last.get("loss")
        if session is not None and args.mailbox_dir and args.rank == 0:
            # Fleet rollup (ISSUE 16): rank 0 scrapes every announced
            # exporter once before exiting. Best-effort — peers that
            # already exited degrade to `unreachable` entries, never a
            # worker failure.
            try:
                from actor_critic_tpu.telemetry import fleet as tfleet

                fz = tfleet.FleetAggregator(
                    mailbox_dir=args.mailbox_dir, timeout_s=2.0
                ).fleetz()
                summary["fleet"] = {
                    "size": fz["fleet_size"],
                    "reachable": fz["reachable"],
                    "counters": fz["counters"],
                }
            except Exception:
                pass
        print(json.dumps(summary), flush=True)
        return 0
    finally:
        for p in pools:
            p.close()
        if session is not None:
            session.close()


# ---------------------------------------------------------------------------
# parent: spawn a cluster, aggregate
# ---------------------------------------------------------------------------


def worker_env() -> dict:
    """CPU-pinned child environment: the cluster's processes are CPU
    children by design, so they never contend for a chip the parent's
    machine may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


def run_cluster(
    processes: int,
    mode: str,
    *,
    iterations: int = 30,
    duration_s: float = 0.0,
    rollout_steps: int = 16,
    num_envs: int = 4,
    actors: int = 1,
    sleep_s: float = 0.002,
    straggler_rank: int = -1,
    straggler_extra_s: float = 0.0,
    gossip_every: int = 1,
    gossip_weight: float = 0.5,
    seed: int = 0,
    eval_steps: int = 0,
    telemetry_dir: str = "",
    timeout_s: float = 600.0,
) -> dict:
    """One N-process local-cluster run; returns the aggregated fleet
    record (raises on worker failure)."""
    port = free_port()
    env = worker_env()
    with tempfile.TemporaryDirectory(prefix="mh_mailbox_") as mailbox:
        cmd_base = [
            sys.executable, os.path.abspath(__file__), "--worker",
            "--processes", str(processes), "--mode", mode,
            "--port", str(port), "--mailbox-dir", mailbox,
            "--iterations", str(iterations),
            "--duration-s", str(duration_s),
            "--rollout-steps", str(rollout_steps),
            "--num-envs", str(num_envs), "--actors", str(actors),
            "--sleep-s", str(sleep_s),
            "--straggler-rank", str(straggler_rank),
            "--straggler-extra-s", str(straggler_extra_s),
            "--gossip-every", str(gossip_every),
            "--gossip-weight", str(gossip_weight),
            "--seed", str(seed), "--eval-steps", str(eval_steps),
            "--telemetry-dir", telemetry_dir,
        ]
        t0 = time.perf_counter()
        procs = [
            subprocess.Popen(
                cmd_base + ["--rank", str(rank)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env,
            )
            for rank in range(processes)
        ]
        # Drain every worker CONCURRENTLY: with sequential communicate()
        # a later rank filling its 64 KiB stderr pipe would block before
        # its next collective, stall the fleet at the barrier, and burn
        # the whole timeout with no diagnostics.
        import threading

        outs: list = [None] * processes

        def drain(i: int, p) -> None:
            try:
                out, err = p.communicate(timeout=timeout_s)
                outs[i] = (p.returncode, out, err)
            except subprocess.TimeoutExpired:
                p.kill()
                out, err = p.communicate()
                outs[i] = (None, out, err)

        threads = [
            threading.Thread(target=drain, args=(i, p), daemon=True)
            for i, p in enumerate(procs)
        ]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=timeout_s + 30)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        wall = time.perf_counter() - t0
    summaries = []
    for rank, entry in enumerate(outs):
        if entry is None:
            raise RuntimeError(f"worker {rank} never finished draining")
        rc, out, err = entry
        if rc is None:
            tail = (err or out or "").strip().splitlines()
            raise RuntimeError(
                f"worker {rank} exceeded {timeout_s:.0f}s and was killed: "
                + ("\n".join(tail[-8:]) if tail else "no output")
            )
        line = next(
            (ln for ln in reversed(out.strip().splitlines())
             if ln.startswith("{")),
            None,
        )
        if rc != 0 or line is None:
            tail = (err or out).strip().splitlines()
            raise RuntimeError(
                f"worker {rank} failed rc={rc}: "
                + ("\n".join(tail[-12:]) if tail else "no output")
            )
        summaries.append(json.loads(line))
    total = sum(s["consumed_env_steps"] for s in summaries)
    slowest = max(s["wall_s"] for s in summaries)
    record = {
        "processes": processes,
        "mode": mode,
        "aggregate_steps_per_s": round(total / slowest, 1) if slowest else 0.0,
        "consumed_env_steps": total,
        "fleet_wall_s": round(slowest, 2),
        "launcher_wall_s": round(wall, 2),
        "version_consistent": all(
            s.get("version_consistent", True) for s in summaries
        ),
        "fingerprint_consistent": all(
            s.get("fingerprint_consistent", True) for s in summaries
        ),
        "per_rank_steps_per_s": [
            s["consumed_steps_per_s"] for s in summaries
        ],
        "gossip_mixes": sum(s.get("gossip_mixes", 0) for s in summaries),
        "gossip_lag_max": max(
            (s.get("gossip_lag_max", 0) for s in summaries), default=0
        ),
        "eval_returns": [s.get("eval_return") for s in summaries],
    }
    if straggler_rank >= 0:
        record["straggler"] = {
            "rank": straggler_rank, "extra_s": straggler_extra_s,
        }
    if telemetry_dir:
        merged = merge_host_traces(telemetry_dir, processes)
        if merged:
            record["trace"] = merged
    return record


def merge_host_traces(telemetry_dir: str, processes: int) -> str:
    """Merge the per-host spans.jsonl files into ONE Chrome-trace JSONL
    (`<telemetry-dir>/fleet_spans.jsonl`): every host keeps its own pid
    lane (named host<rank> by `multihost.host_lane`), and each host's
    span timestamps are shifted onto a common axis using the clock_sync
    metadata its tracer recorded (per-process ts is zeroed at tracer
    creation; the unix epoch anchor is the shared clock)."""
    hosts = []
    for rank in range(processes):
        path = os.path.join(telemetry_dir, f"host{rank}", "spans.jsonl")
        if not os.path.exists(path):
            continue
        events = []
        epoch0 = None
        with open(path) as f:
            for ln in f:
                try:
                    evt = json.loads(ln)
                except json.JSONDecodeError:
                    continue
                if evt.get("name") == "clock_sync":
                    epoch0 = evt.get("args", {}).get("unix_epoch_at_ts0")
                events.append(evt)
        if epoch0 is not None:
            hosts.append((epoch0, events))
    if not hosts:
        return ""
    base = min(e for e, _ in hosts)
    out_path = os.path.join(telemetry_dir, "fleet_spans.jsonl")
    with open(out_path, "w") as f:
        for epoch0, events in hosts:
            shift_us = (epoch0 - base) * 1e6
            for evt in events:
                if "ts" in evt:
                    evt = dict(evt, ts=round(evt["ts"] + shift_us, 1))
                f.write(json.dumps(evt) + "\n")
    return out_path


# ---------------------------------------------------------------------------
# smoke driver
# ---------------------------------------------------------------------------


def run_smoke(args) -> int:
    """Tier-1 gate: a 2-process sync cluster must come up on localhost,
    train a few blocks, and agree bit-exactly on the broadcast version
    counter and the replicated-params fingerprint."""
    rec = run_cluster(
        2, "sync", iterations=args.iterations or 5, rollout_steps=8,
        num_envs=2, actors=1, sleep_s=0.0, seed=args.seed,
        timeout_s=args.run_timeout,
    )
    ok = rec["version_consistent"] and rec["fingerprint_consistent"]
    print(json.dumps({"smoke": "multihost_sync_2proc", "ok": ok, **rec}))
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    p.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--rank", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--port", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument(
        "--processes", type=int, default=2,
        help="cluster size (local processes, one learner each)",
    )
    p.add_argument(
        "--mode", choices=("sync", "gossip"), default="sync",
        help="sync = global-mesh all-reduce learner (a straggler stalls "
        "the fleet); gossip = independent learners + ring param exchange "
        "(a straggler degrades only itself)",
    )
    p.add_argument("--iterations", type=int, default=0,
                   help="blocks consumed per learner (0 = mode default)")
    p.add_argument(
        "--duration-s", type=float, default=0.0,
        help="wall-bounded run: consume as many blocks as fit in this "
        "window instead of a fixed count (a straggler shows up as "
        "blocks NOT consumed). Sync fleets "
        "all-reduce the stop vote so every host exits together.",
    )
    p.add_argument("--rollout-steps", type=int, default=16)
    p.add_argument("--num-envs", type=int, default=4,
                   help="envs per process (split across --actors)")
    p.add_argument("--actors", type=int, default=1,
                   help="actor threads per process")
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--minibatches", type=int, default=2)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument(
        "--sleep-s", type=float, default=0.002,
        help="per-env-step wall pad (simulator-shaped cost; see "
        "envs/sleep_pad.py)",
    )
    p.add_argument(
        "--straggler-rank", type=int, default=-1,
        help="rank whose envs get --straggler-extra-s more pad (-1 off)",
    )
    p.add_argument("--straggler-extra-s", type=float, default=0.006)
    p.add_argument("--gossip-every", type=int, default=1,
                   help="consumed blocks between gossip exchanges")
    p.add_argument("--gossip-weight", type=float, default=0.5,
                   help="peer mixing weight in [0, 1]")
    p.add_argument("--queue-depth", type=int, default=4)
    p.add_argument("--max-staleness", type=int, default=8)
    p.add_argument("--mailbox-dir", default="",
                   help="shared gossip mailbox dir (auto tempdir when "
                   "launched by this script)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--eval-steps", type=int, default=0,
                   help="final greedy eval sweep per worker (0 = off)")
    p.add_argument("--telemetry-dir", default="",
                   help="per-host telemetry under <dir>/host<rank>; the "
                   "parent merges spans into <dir>/fleet_spans.jsonl")
    p.add_argument("--run-timeout", type=float, default=600.0,
                   help="per-cluster-run kill budget (seconds)")
    p.add_argument("--smoke", action="store_true",
                   help="tier-1 2-process sync smoke (exit 1 on failure)")
    args = p.parse_args(argv)

    if args.worker:
        if args.max_staleness < 0:
            args.max_staleness = None
        return run_worker(args)
    if args.smoke:
        return run_smoke(args)
    rec = run_cluster(
        args.processes, args.mode,
        iterations=args.iterations or 30,
        duration_s=args.duration_s,
        rollout_steps=args.rollout_steps, num_envs=args.num_envs,
        actors=args.actors,
        sleep_s=args.sleep_s,
        straggler_rank=args.straggler_rank,
        straggler_extra_s=(
            args.straggler_extra_s if args.straggler_rank >= 0 else 0.0
        ),
        gossip_every=args.gossip_every, gossip_weight=args.gossip_weight,
        seed=args.seed, eval_steps=args.eval_steps,
        telemetry_dir=args.telemetry_dir, timeout_s=args.run_timeout,
    )
    print(json.dumps(rec))
    return 0 if rec["version_consistent"] else 1


if __name__ == "__main__":
    sys.exit(main())
