"""fleetsan: deterministic multi-process chaos sanitizer for the
mailbox/gossip/gateway stack (ISSUE 12 runtime half).

racesan (ISSUE 7) made THREAD interleavings seeded and replayable;
this module lifts the same scheduler contract to PROCESS granularity.
A seeded `ChaosScheduler` steps a fleet of simulated hosts — each one
driving the REAL protocol objects: `write_params`/`read_params` file
transport, `FileMailboxWriter.poll_once` (the production consume
logic, thread never started), `ParamMailbox`, `gossip_peer`,
`mix_params`, and the serving `PolicyStore.swap` path — one atomic
action at a time, interleaving publishes at their crash points and
injecting faults from a seeded menu:

- **SIGKILL mid-publish** — the victim writes its tmp file and dies
  before the rename (the exact window `os.replace` protects);
- **restart-and-rejoin** — a dead rank comes back, resumes its version
  clock from its own published file, and must diffuse through the ring
  again within a bounded number of rounds (`time-to-recover`, measured
  per schedule in rounds — the process-level injector below measures
  it in seconds);
- **torn/truncated mailbox files** — a victim's published snapshot is
  truncated to a seeded byte count (fs loss / non-atomic writer):
  consumers must tolerate (read -> None, retry next poll) and the next
  publish must repair;
- **reordered delivery** — a stale complete snapshot is re-placed over
  a newer one (a delayed NFS write): per-peer version clocks must
  refuse to regress;
- **duplicate snapshots** — the same version re-delivered: latest-wins
  must hand it to the learner at most once.

Every parse of every mailbox file is checked at every interleave
point: payloads encode `(rank, version)` into a uniform fill
(`_encode`), so a torn-but-parsing file, a cross-rank tempfile
collision (rank A's path carrying rank B's payload), and a version
regression are all detected AT THE READ, deterministically, not by an
unlucky preemption. Reverted-snippet modes reproduce the bug classes:
`writer="direct"` (no tmp+rename — caught on EVERY schedule: the
checker reads the half-written file at the interleave point),
`writer="shared_tmp"` (a tmp name shared across ranks — the collision
interleaving is found within a few seeds and replays bit-identically),
and `poller="naive"` (consume without per-peer clocks — the reorder
injector regresses the gateway's resident policy on every schedule).

A given seed replays bit-identically (`report["trace"]` records the
scheduling decisions); `quick_profile` is the fixed-seed sweep
`scripts/tier1.sh` runs between racesan and pytest, and
`run_process_chaos` is the REAL-process injector (spawn a gossip
fleet, SIGKILL a rank mid-run, restart it, measure wall-clock
time-to-recover) that `multihost_scaling`'s fault-injection bench
block reuses as its driver.
"""

from __future__ import annotations

import io
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Iterable, Optional

import numpy as np

_SHAPE = (2, 2)


class FleetSanError(RuntimeError):
    """A detected protocol violation, or a schedule that failed to
    recover within its liveness bound."""


def _encode(rank: int, version: int) -> float:
    """The uniform fill value of rank's version-v snapshot: payloads
    are a FUNCTION of (rank, version), so any parse can be verified
    without side-channel state — a foreign payload (tempfile
    collision) or torn-but-parsing tree mismatches immediately."""
    return float(rank * 1000 + version)


def _payload(rank: int, version: int) -> dict:
    return {"w": np.full(_SHAPE, _encode(rank, version), np.float32)}


def _npz_bytes(version: int, payload: dict) -> bytes:
    buf = io.BytesIO()
    np.savez(
        buf,
        **{f"leaf{i}": v for i, v in enumerate(payload.values())},
        version=np.asarray(int(version), np.int64),
    )
    return buf.getvalue()


# ---------------------------------------------------------------------------
# simulated hosts (real protocol objects, scripted learner)
# ---------------------------------------------------------------------------


class _SimHost:
    """One rank of the simulated fleet: a scripted learner loop over
    the REAL mailbox objects. `actions()` yields one atomic action at a
    time; the scheduler interleaves hosts between actions — publishes
    are split at their crash/interleave points."""

    def __init__(
        self,
        rank: int,
        world: int,
        mailbox_dir: str,
        writer: str = "atomic",
        on_publish: Optional[Callable[["_SimHost"], None]] = None,
    ):
        self.on_publish = on_publish or (lambda host: None)
        from actor_critic_tpu.parallel.multihost import (
            FileMailboxWriter,
            ParamMailbox,
            read_params,
        )

        self.rank = int(rank)
        self.world = int(world)
        self.dir = mailbox_dir
        self.writer = writer
        self.template = _payload(rank, 0)
        self.mailbox = ParamMailbox()
        # Thread NEVER started: the scheduler drives poll_once directly
        # (racesan's contract lifted to the process level — the real
        # consume logic, deterministic schedule).
        self.poller = FileMailboxWriter(
            mailbox_dir, rank, world, template=self.template,
            mailbox=self.mailbox, stop=threading.Event(),
        )
        # Restart-and-rejoin: resume the version clock from our own
        # published file, exactly as a restarted process would.
        own = read_params(mailbox_dir, rank, self.template)
        self.version = own[0] if own is not None else 0
        self.taken: dict[int, int] = {}  # per-peer consume clock
        self.takes = 0
        self.deposits = 0

    # -- publish variants (each yields at its interleave points) ----------

    def _publish_atomic(self):
        from actor_critic_tpu.parallel.multihost import write_params

        write_params(self.dir, self.rank, self.version, _payload(
            self.rank, self.version
        ))
        self.on_publish(self)
        yield "publish"

    def _publish_direct(self):
        """REVERTED writer: the consumed path written in place, torn at
        the interleave point — the checker reads the half-written file
        there on every schedule."""
        from actor_critic_tpu.parallel.multihost import params_file

        path = params_file(self.dir, self.rank)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        data = _npz_bytes(self.version, _payload(self.rank, self.version))
        # jaxlint: disable=mailbox-protocol (deliberate: this IS the
        # reverted non-atomic writer under test — the checker must
        # catch it at the interleave point)
        with open(path, "wb") as f:
            f.write(data[: len(data) // 2])
        yield "publish:half"
        with open(path, "ab") as f:
            f.write(data[len(data) // 2:])
        self.on_publish(self)
        yield "publish:done"

    def _publish_shared_tmp(self):
        """REVERTED writer: one tmp name for the whole mailbox — two
        ranks publishing concurrently interleave into it and rename
        each other's payloads into place."""
        from actor_critic_tpu.parallel.multihost import params_file

        path = params_file(self.dir, self.rank)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = os.path.join(self.dir, "pending.tmp")
        data = _npz_bytes(self.version, _payload(self.rank, self.version))
        # jaxlint: disable=mailbox-protocol (deliberate: the shared —
        # non-process-unique — tmp name IS the collision under test)
        with open(tmp, "wb") as f:
            f.write(data)
        yield "publish:tmp"
        try:
            os.replace(tmp, path)
        except FileNotFoundError:
            # The OTHER manifestation of the collision: a concurrent
            # rank renamed our shared tmp into ITS path — our payload
            # is now published under a foreign rank.
            raise FleetSanError(
                f"rank {self.rank}: shared tmp vanished mid-publish — "
                "a concurrent rank renamed it into its own path "
                "(tempfile collision: tmp names must be "
                "process-unique)"
            )
        self.on_publish(self)
        yield "publish:done"

    def publish_kill(self):
        """SIGKILL mid-publish: the tmp lands, the rename never runs —
        the stale tmp must be harmless and the published file must
        still hold the previous complete snapshot."""
        from actor_critic_tpu.parallel.multihost import params_file

        path = params_file(self.dir, self.rank)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        # jaxlint: disable=mailbox-protocol (deliberate: SIGKILL lands
        # here in the simulation — no fsync/rename ever runs)
        with open(tmp, "wb") as f:
            f.write(_npz_bytes(self.version, _payload(
                self.rank, self.version
            )))

    # -- one learner round -------------------------------------------------

    def actions(self, verify: Callable[["_SimHost", tuple], None]):
        self.version += 1
        if self.writer == "atomic":
            yield from self._publish_atomic()
        elif self.writer == "direct":
            yield from self._publish_direct()
        elif self.writer == "shared_tmp":
            yield from self._publish_shared_tmp()
        else:
            raise ValueError(f"unknown writer mode {self.writer!r}")
        self.poller.set_round(self.version)
        if self.poller.poll_once():
            self.deposits += 1
        yield "poll"
        out = self.mailbox.take()
        if out is not None:
            verify(self, out)
            self.takes += 1
        yield "take"


# ---------------------------------------------------------------------------
# the chaos scheduler
# ---------------------------------------------------------------------------


class ChaosScheduler:
    """Seeded process-granularity scheduler: per global round every
    live host contributes its action generator, the controller
    contributes fault actions, and the RNG picks who advances next —
    so a given seed replays its interleaving (and its faults)
    bit-identically. No wall clock anywhere: time-to-recover is
    measured in rounds."""

    def __init__(self, seed: int):
        self.seed = int(seed)
        self.rng = random.Random(self.seed)
        self.trace: list[tuple] = []

    def interleave(self, gens: dict[str, Any], round_: int) -> None:
        """Advance the named generators one action at a time in seeded
        order until all are exhausted. Operates on `gens` IN PLACE so a
        fault action can remove another participant mid-round (a
        SIGKILLed host must stop at its current action, not keep
        executing to generator exhaustion as a zombie)."""
        while gens:
            name = sorted(gens)[self.rng.randrange(len(gens))]
            try:
                tag = next(gens[name])
                self.trace.append((round_, name, tag))
            except StopIteration:
                gens.pop(name, None)


# ---------------------------------------------------------------------------
# fleet exerciser
# ---------------------------------------------------------------------------


def exercise_fleet(
    seed: int,
    world: int = 3,
    rounds: int = 10,
    writer: str = "atomic",
    faults: bool = True,
    recover_bound: int = 12,
) -> dict:
    """One seeded chaos schedule over a simulated gossip fleet of
    `world` ranks sharing a real on-disk mailbox. Detection raises
    FleetSanError; a clean schedule returns the report (trace included
    — bit-identical per seed)."""
    from actor_critic_tpu.parallel.multihost import params_file, read_params

    sched = ChaosScheduler(seed)
    report: dict = {
        "seed": seed, "world": world, "rounds": rounds, "writer": writer,
        "takes": 0, "deposits": 0, "faults": [], "kills": 0,
        "recover_rounds": [], "violations": 0,
    }
    with tempfile.TemporaryDirectory(prefix="fleetsan_") as mailbox:
        template = _payload(0, 0)
        # rank -> newest version fully published (set by the publish
        # actions themselves, so a file torn AFTER a publish can never
        # be re-marked complete by round bookkeeping); rank -> True
        # while an injected fault legitimately tore the file.
        complete: dict[int, int] = {}
        injector_torn: dict[int, bool] = {r: False for r in range(world)}

        def on_publish(host: "_SimHost") -> None:
            complete[host.rank] = host.version
            injector_torn[host.rank] = False

        hosts: dict[int, Optional[_SimHost]] = {
            r: _SimHost(r, world, mailbox, writer=writer,
                        on_publish=on_publish)
            for r in range(world)
        }
        # pending recoveries: rank -> (restart_round, version_at_kill)
        pending: dict[int, tuple[int, int]] = {}
        dead: dict[int, tuple[int, int]] = {}  # rank -> (revive_round, v)
        saved: dict[int, bytes] = {}  # reorder/duplicate ammunition

        def verify(host: _SimHost, out: tuple) -> None:
            version, peer, params = out
            if version <= host.taken.get(peer, -1):
                report["violations"] += 1
                raise FleetSanError(
                    f"seed {seed}: host {host.rank} took version "
                    f"{version} from peer {peer} after "
                    f"{host.taken[peer]} — per-peer monotonicity "
                    "violated (reordered/duplicate delivery reached "
                    "the learner)"
                )
            host.taken[peer] = version
            w = np.asarray(params["w"])
            uniform = bool(np.all(w == w.flat[0]))
            if not uniform or float(w.flat[0]) != _encode(peer, version):
                report["violations"] += 1
                raise FleetSanError(
                    f"seed {seed}: host {host.rank} took a corrupt "
                    f"snapshot claiming (peer={peer}, v={version}): "
                    f"uniform={uniform}, value={float(w.flat[0])!r}, "
                    f"expected {_encode(peer, version)} — torn write "
                    "or cross-rank tempfile collision"
                )
            # The mixing math itself must preserve uniformity.
            from actor_critic_tpu.parallel.multihost import mix_params

            mixed = mix_params(_payload(host.rank, host.version), params, 0.5)
            mw = np.asarray(mixed["w"])
            if not bool(np.all(mw == mw.flat[0])):
                report["violations"] += 1
                raise FleetSanError(
                    f"seed {seed}: mix_params broke uniformity"
                )
            # Recovery bookkeeping: fresh post-restart news from a
            # previously killed rank closes its pending window.
            if peer in pending and version > pending[peer][1]:
                restart_round, _ = pending.pop(peer)
                report["recover_rounds"].append(
                    max(round_now[0] - restart_round, 0)
                )

        def check_files() -> Iterable[str]:
            """The torn-publish detector, run at EVERY interleave
            point: a rank that completed a publish must always present
            a parseable snapshot whose payload matches its claimed
            (rank, version) — unless an injected fault (not the writer
            under test) tore the file."""
            for r in range(world):
                if injector_torn[r]:
                    continue
                if r not in complete:
                    continue
                out = read_params(mailbox, r, template)
                if out is None:
                    report["violations"] += 1
                    raise FleetSanError(
                        f"seed {seed}: rank {r}'s mailbox file is "
                        f"unreadable although version {complete[r]} "
                        "was fully published — the writer tore the "
                        "consumed path (atomic write→fsync→rename "
                        "violated)"
                    )
                version, tree = out
                w = np.asarray(tree["w"])
                if not bool(np.all(w == w.flat[0])) or float(
                    w.flat[0]
                ) != _encode(r, version):
                    report["violations"] += 1
                    raise FleetSanError(
                        f"seed {seed}: rank {r}'s mailbox file claims "
                        f"version {version} but carries value "
                        f"{float(w.flat[0])!r} (expected "
                        f"{_encode(r, version)}) — a foreign rank's "
                        "payload was renamed into place (tempfile "
                        "collision)"
                    )
            return ()

        def checked(gen):
            """Wrap a host generator so the file checker runs at every
            one of its interleave points."""
            for tag in gen:
                check_files()
                yield tag

        def chaos_actions(round_: int):
            """The controller's seeded faults for this round."""
            if not faults:
                return
            live = [r for r, h in hosts.items() if h is not None]
            roll = sched.rng.random()
            if roll < 0.25 and len(live) > 1 and not dead and not pending:
                victim = live[sched.rng.randrange(len(live))]
                host = hosts[victim]
                host.version += 1
                host.publish_kill()  # tmp written, rename never runs
                hosts[victim] = None
                # SIGKILL is immediate: the victim's action generator
                # must not keep running this round as a zombie (it
                # could complete a FULL publish after "dying", masking
                # stale-tmp/stuck-peer regressions and zeroing the
                # measured recovery window).
                round_gens.pop(f"host{victim}", None)
                dead[victim] = (
                    round_ + 1 + sched.rng.randrange(2),
                    host.version - 1,
                )
                report["kills"] += 1
                report["faults"].append((round_, "kill", victim))
                yield f"kill:host{victim}"
            elif roll < 0.45 and complete:
                ranks = sorted(complete)
                victim = ranks[sched.rng.randrange(len(ranks))]
                path = params_file(mailbox, victim)
                try:
                    size = os.path.getsize(path)
                    with open(path, "r+b") as f:
                        f.truncate(sched.rng.randrange(1, max(size, 2)))
                    injector_torn[victim] = True
                    complete.pop(victim, None)
                    report["faults"].append((round_, "torn", victim))
                    yield f"torn:host{victim}"
                except OSError:
                    pass
            elif roll < 0.60 and complete:
                # Save a complete snapshot now; re-placing it later is
                # the reorder/duplicate delivery fault.
                ranks = sorted(complete)
                victim = ranks[sched.rng.randrange(len(ranks))]
                path = params_file(mailbox, victim)
                try:
                    with open(path, "rb") as f:
                        saved[victim] = f.read()
                    report["faults"].append((round_, "save", victim))
                    yield f"save:host{victim}"
                except OSError:
                    pass
            elif roll < 0.80 and saved:
                ranks = sorted(saved)
                victim = ranks[sched.rng.randrange(len(ranks))]
                path = params_file(mailbox, victim)
                tmp = f"{path}.tmp.reorder"
                # jaxlint: disable=mailbox-protocol (deliberate fault
                # injection: re-placing a stale complete snapshot IS
                # the reordered-delivery fault, not a publish)
                with open(tmp, "wb") as f:
                    f.write(saved[victim])
                # jaxlint: disable=mailbox-protocol (injector rename)
                os.replace(tmp, path)
                report["faults"].append((round_, "replay", victim))
                yield f"replay:host{victim}"

        round_now = [0]
        # This round's interleave set — shared with chaos_actions so a
        # kill can remove the victim's generator mid-round.
        round_gens: dict[str, Any] = {}
        total_rounds = rounds + recover_bound
        for round_ in range(total_rounds):
            round_now[0] = round_
            # Revive due ranks: restart-and-rejoin. The version clock
            # resumes from the host's own published file, floored at
            # its pre-kill value (the consumed-block clock rides the
            # local checkpoint in production — a torn/stale mailbox
            # file must not rewind it below what peers already saw, or
            # their per-peer clocks mute the rejoiner).
            for r, (due, v_at_kill) in sorted(dead.items()):
                if round_ >= due:
                    h = _SimHost(r, world, mailbox, writer=writer,
                                 on_publish=on_publish)
                    h.version = max(h.version, v_at_kill)
                    hosts[r] = h
                    pending[r] = (round_, v_at_kill)
                    dead.pop(r)
                    sched.trace.append((round_, "chaos", f"restart:host{r}"))
            if round_ >= rounds and not pending and not dead:
                break  # drain phase over: every kill recovered
            round_gens.clear()
            round_gens.update({
                f"host{r}": checked(h.actions(verify))
                for r, h in hosts.items()
                if h is not None
            })
            if round_ < rounds:
                round_gens["chaos"] = chaos_actions(round_)
            sched.interleave(round_gens, round_)
        if pending:
            raise FleetSanError(
                f"seed {seed}: rank(s) {sorted(pending)} restarted but "
                f"their fresh snapshots never reached a peer within "
                f"{recover_bound} drain rounds — ring diffusion broken "
                "(time-to-recover unbounded)"
            )
        report["takes"] = sum(
            h.takes for h in hosts.values() if h is not None
        )
        report["deposits"] = sum(
            h.deposits for h in hosts.values() if h is not None
        )
    report["trace"] = list(sched.trace)
    report["trace_len"] = len(sched.trace)
    return report


# ---------------------------------------------------------------------------
# gateway swap exerciser
# ---------------------------------------------------------------------------


class _StubSwapEngine:
    """jax-free engine stand-in for the gateway swap path (racesan's
    _StubServingEngine shape): prepare_params snapshots + freezes, so
    the store's install contract matches production."""

    max_rows = 8

    def prepare_params(self, params: Any) -> Any:
        out = {k: np.array(v) for k, v in params.items()}
        for v in out.values():
            v.flags.writeable = False
        return out

    def act(self, params: Any, obs: np.ndarray) -> np.ndarray:
        return np.asarray(obs)[:, 0] * params["w"].flat[0]


def exercise_gateway(
    seed: int,
    versions: int = 8,
    poller: str = "guarded",
) -> dict:
    """One seeded chaos schedule over the serve-while-training swap
    path: a publisher rank publishes `(version, params)` snapshots
    through the real file mailbox; a gateway-side consumer polls them
    (through the REAL `FileMailboxWriter.poll_once` + `ParamMailbox`
    when `poller="guarded"`) and installs fresh versions into a real
    `PolicyStore` via `swap`. The controller injects torn files and
    reordered/duplicate deliveries. Invariants: the resident policy's
    version never regresses, and its params always match the version
    they claim. `poller="naive"` is the REVERTED consumer — raw
    read-then-swap with no per-peer clock — which the reorder injector
    regresses on every schedule."""
    from actor_critic_tpu.parallel.multihost import (
        FileMailboxWriter,
        ParamMailbox,
        params_file,
        read_params,
        write_params,
    )
    from actor_critic_tpu.serving.policy_store import PolicyStore

    if poller not in ("guarded", "naive"):
        raise ValueError(f"unknown poller mode {poller!r}")
    sched = ChaosScheduler(seed)
    report = {
        "seed": seed, "poller": poller, "swaps": 0, "published": 0,
        "faults": [], "violations": 0,
    }
    with tempfile.TemporaryDirectory(prefix="fleetsan_gw_") as mailbox:
        template = _payload(0, 0)
        store = PolicyStore()
        engine = _StubSwapEngine()
        store.register("default", engine, _payload(0, 0))
        pmailbox = ParamMailbox()
        consumer = FileMailboxWriter(
            mailbox, rank=1, world=2, template=template,
            mailbox=pmailbox, stop=threading.Event(),
        )
        saved: dict[int, bytes] = {}
        last_version = [0]

        def install(version: int, params: Any) -> None:
            handle = store.swap("default", params, version=version)
            if handle.version < last_version[0]:
                report["violations"] += 1
                raise FleetSanError(
                    f"seed {seed}: gateway swapped BACK from version "
                    f"{last_version[0]} to {handle.version} — a "
                    "reordered/duplicate snapshot regressed the "
                    "resident policy (per-peer version clock missing "
                    "at the consume site)"
                )
            w = np.asarray(handle.params["w"])
            if not bool(np.all(w == w.flat[0])) or float(
                w.flat[0]
            ) != _encode(0, version):
                report["violations"] += 1
                raise FleetSanError(
                    f"seed {seed}: resident policy at version "
                    f"{version} carries value {float(w.flat[0])!r}, "
                    f"expected {_encode(0, version)} — torn install"
                )
            last_version[0] = handle.version
            report["swaps"] += 1

        def poll_step() -> None:
            """ONE consumer poll — runs after EVERY scheduler action,
            so publishes, faults, and installs genuinely interleave."""
            if poller == "guarded":
                if consumer.poll_once():
                    out = pmailbox.take()
                    if out is not None:
                        version, _peer, params = out
                        install(version, params)
            else:
                # REVERTED consumer: no per-peer clock, no mailbox
                # dedupe — whatever the file says right now is swapped
                # in; a replayed stale snapshot regresses the store.
                out = read_params(mailbox, 0, template)
                if out is not None:
                    install(*out)

        def publisher():
            for v in range(1, versions + 1):
                write_params(mailbox, 0, v, _payload(0, v))
                report["published"] = v
                yield f"publish:{v}"

        def chaos():
            """Scripted fault sequence with seeded placement: save an
            early complete snapshot, optionally tear the live file
            mid-stream, then REPLAY the stale save after the final
            publish — so every schedule exercises the regression path
            (the guarded consumer refuses it; the naive one swaps it
            in and is caught)."""
            for _ in range(versions * 4):
                if report["published"] >= 2:
                    break
                yield "idle"
            path = params_file(mailbox, 0)
            with open(path, "rb") as f:
                saved[0] = f.read()
            report["faults"].append("save")
            yield "save"
            if sched.rng.random() < 0.5:
                size = os.path.getsize(path)
                with open(path, "r+b") as f:
                    f.truncate(sched.rng.randrange(1, max(size, 2)))
                report["faults"].append("torn")
                yield "torn"
            for _ in range(versions * 4):
                if report["published"] >= versions:
                    break
                yield "idle"
            tmp = f"{path}.tmp.reorder"
            # jaxlint: disable=mailbox-protocol (deliberate fault
            # injection: the reordered-delivery fault, not a publish)
            with open(tmp, "wb") as f:
                f.write(saved[0])
            # jaxlint: disable=mailbox-protocol (injector rename)
            os.replace(tmp, path)
            report["faults"].append("replay")
            yield "replay"
            # Duplicate delivery: the same stale bytes once more.
            # jaxlint: disable=mailbox-protocol (duplicate injector)
            with open(tmp, "wb") as f:
                f.write(saved[0])
            # jaxlint: disable=mailbox-protocol (injector rename)
            os.replace(tmp, path)
            report["faults"].append("duplicate")
            yield "duplicate"

        gens: dict[str, Any] = {"publisher": publisher(), "chaos": chaos()}
        live = dict(gens)
        while live:
            name = sorted(live)[sched.rng.randrange(len(live))]
            try:
                tag = next(live[name])
                sched.trace.append((0, name, tag))
            except StopIteration:
                del live[name]
                continue
            poll_step()
            sched.trace.append((0, "gateway", "poll"))
        # Drain: a torn/stale final file is repaired by re-publishing
        # the newest version (what the next training step would do),
        # bounded so a broken consumer cannot spin forever.
        for _ in range(versions * 20):
            if last_version[0] >= versions:
                break
            write_params(mailbox, 0, versions, _payload(0, versions))
            poll_step()
        if last_version[0] < versions:
            raise FleetSanError(
                f"seed {seed}: gateway never converged to version "
                f"{versions} (stuck at {last_version[0]}) — the swap "
                "path lost the newest snapshot"
            )
    report["trace"] = list(sched.trace)
    report["trace_len"] = len(sched.trace)
    return report


# ---------------------------------------------------------------------------
# replica-fleet swap exerciser (ISSUE 17 leg b)
# ---------------------------------------------------------------------------


def exercise_replica_fleet(
    seed: int,
    versions: int = 8,
    replicas: int = 3,
) -> dict:
    """One seeded replica-kill-mid-swap schedule over the horizontal
    scale-out propagation path: a trainer rank publishes `(version,
    params)` snapshots through the real file mailbox while N replica
    gateways consume them through the REAL
    `serving.fleet_proxy.MailboxPolicySyncer.poll_once` (sync thread
    never started — the scheduler owns every interleave point) into N
    real `PolicyStore`s. The controller injects the gateway exerciser's
    fault menu (torn live file, stale replay, duplicate delivery) PLUS
    a replica SIGKILL at a seeded point in the swap pipeline — possibly
    between a publish and the victim's consume of it — and a later
    restart with a cold store and a reset version clock (exactly what a
    respawned serve.py process has).

    Invariants, checked after EVERY scheduler action on the polled
    replica: (1) the resident policy's params always self-verify
    against the version they claim (`_encode` — a torn policy is never
    served), (2) each replica's resident version never regresses within
    one process lifetime (the syncer's per-publisher clock; a restart
    legitimately resets it), (3) every replica — including the
    killed-and-restarted one — converges to the final published
    version within the bounded drain."""
    from actor_critic_tpu.parallel.multihost import params_file, write_params
    from actor_critic_tpu.serving.fleet_proxy import MailboxPolicySyncer
    from actor_critic_tpu.serving.policy_store import PolicyStore

    sched = ChaosScheduler(seed)
    report = {
        "seed": seed, "replicas": replicas, "swaps": 0, "published": 0,
        "kills": 0, "faults": [], "violations": 0,
    }
    with tempfile.TemporaryDirectory(prefix="fleetsan_rf_") as mailbox:
        template = _payload(0, 0)

        def make_replica() -> dict:
            store = PolicyStore()
            store.register("default", _StubSwapEngine(), _payload(0, 0))
            return {
                "store": store,
                "syncer": MailboxPolicySyncer(
                    store, "default", mailbox, rank=0, template=template
                ),
                # Newest resident version THIS process lifetime: the
                # monotonicity witness (reset by a legitimate restart).
                "last": 0,
            }

        fleet = {i: make_replica() for i in range(replicas)}

        def check(idx: int, rep: dict) -> None:
            handle = rep["store"].get("default")
            if handle.version < rep["last"]:
                report["violations"] += 1
                raise FleetSanError(
                    f"seed {seed}: replica {idx} regressed from version "
                    f"{rep['last']} to {handle.version} — a reordered/"
                    "duplicate snapshot got past the syncer's version "
                    "clock"
                )
            w = np.asarray(handle.params["w"])
            if handle.version > 0 and (
                not bool(np.all(w == w.flat[0]))
                or float(w.flat[0]) != _encode(0, handle.version)
            ):
                report["violations"] += 1
                raise FleetSanError(
                    f"seed {seed}: replica {idx} serves version "
                    f"{handle.version} with value {float(w.flat[0])!r}, "
                    f"expected {_encode(0, handle.version)} — a torn "
                    "policy reached the store"
                )
            rep["last"] = handle.version

        def poll(idx: int) -> None:
            rep = fleet.get(idx)
            if rep is None:  # killed — nothing to poll
                return
            if rep["syncer"].poll_once():
                report["swaps"] += 1
            check(idx, rep)

        def publisher():
            for v in range(1, versions + 1):
                write_params(mailbox, 0, v, _payload(0, v))
                report["published"] = v
                yield f"publish:{v}"

        saved: dict[int, bytes] = {}

        def chaos():
            # Same seeded menu as exercise_gateway: save early, maybe
            # tear the live file, replay + duplicate the stale save
            # after the final publish.
            for _ in range(versions * 4):
                if report["published"] >= 2:
                    break
                yield "idle"
            path = params_file(mailbox, 0)
            with open(path, "rb") as f:
                saved[0] = f.read()
            report["faults"].append("save")
            yield "save"
            if sched.rng.random() < 0.5:
                size = os.path.getsize(path)
                with open(path, "r+b") as f:
                    f.truncate(sched.rng.randrange(1, max(size, 2)))
                report["faults"].append("torn")
                yield "torn"
            for _ in range(versions * 4):
                if report["published"] >= versions:
                    break
                yield "idle"
            tmp = f"{path}.tmp.reorder"
            # jaxlint: disable=mailbox-protocol (reorder injector)
            with open(tmp, "wb") as f:
                f.write(saved[0])
            # jaxlint: disable=mailbox-protocol (injector rename)
            os.replace(tmp, path)
            report["faults"].append("replay")
            yield "replay"

        def killer():
            """SIGKILL one replica at a seeded point mid-schedule and
            restart it a seeded number of rounds later: the restart is
            a COLD process (fresh store at version 0, syncer clock
            reset), so if the mailbox currently holds the chaos
            injector's stale replay, the rejoiner legitimately swaps it
            in — and must still converge to the newest version at
            drain."""
            victim = sched.rng.randrange(replicas)
            for _ in range(sched.rng.randrange(1, versions * 2)):
                yield "idle"
            fleet.pop(victim, None)
            report["kills"] += 1
            report["faults"].append(f"kill:{victim}")
            yield f"kill:{victim}"
            for _ in range(sched.rng.randrange(1, versions)):
                yield "idle"
            fleet[victim] = make_replica()
            report["faults"].append(f"restart:{victim}")
            yield f"restart:{victim}"

        gens: dict[str, Any] = {
            "publisher": publisher(), "chaos": chaos(), "killer": killer(),
        }
        live = dict(gens)
        while live:
            name = sorted(live)[sched.rng.randrange(len(live))]
            try:
                tag = next(live[name])
                sched.trace.append((0, name, tag))
            except StopIteration:
                del live[name]
                continue
            # ONE seeded replica polls per action — replica consumes
            # genuinely interleave with publishes, faults, and kills.
            idx = sched.rng.randrange(replicas)
            poll(idx)
            sched.trace.append((0, f"replica{idx}", "poll"))
        # Drain: repair the (possibly stale/torn) final file the way
        # the next training publish would, and poll every survivor
        # until the whole fleet converges — bounded.
        for _ in range(versions * 20):
            if all(r["last"] >= versions for r in fleet.values()):
                break
            write_params(mailbox, 0, versions, _payload(0, versions))
            for idx in sorted(fleet):
                poll(idx)
        laggards = {
            i: r["last"] for i, r in fleet.items() if r["last"] < versions
        }
        if laggards:
            raise FleetSanError(
                f"seed {seed}: replicas never converged to version "
                f"{versions}: {laggards} — the propagation path lost "
                "the newest snapshot"
            )
    report["trace"] = list(sched.trace)
    report["trace_len"] = len(sched.trace)
    return report


# ---------------------------------------------------------------------------
# sweep + the tier-1 quick profile
# ---------------------------------------------------------------------------


def exercise_sweep(
    seeds: Iterable[int], scenario: Callable[[int], dict]
) -> dict:
    reports = [scenario(seed) for seed in seeds]
    return {
        "schedules": len(reports),
        "takes": sum(r.get("takes", 0) for r in reports),
        "deposits": sum(r.get("deposits", 0) for r in reports),
        "swaps": sum(r.get("swaps", 0) for r in reports),
        "kills": sum(r.get("kills", 0) for r in reports),
        "faults": sum(len(r.get("faults", ())) for r in reports),
        "recover_rounds_max": max(
            (x for r in reports for x in r.get("recover_rounds", ())),
            default=0,
        ),
        "violations": sum(r.get("violations", 0) for r in reports),
    }


def quick_profile(schedules: int = 40, seed0: int = 0) -> dict:
    """The tier-1 fast profile: `schedules` seeded chaos schedules
    split between the gossip-fleet unit (atomic writer, full fault
    menu, recovery bounded) and the gateway swap unit (guarded poller)
    — every schedule must sweep clean. ~40 schedules run in a few
    seconds on one CPU core (tiny trees, tmpfs-speed files)."""
    half = max(schedules // 2, 1)
    fleet = exercise_sweep(
        range(seed0, seed0 + half),
        lambda s: exercise_fleet(s, writer="atomic", faults=True),
    )
    gateway = exercise_sweep(
        range(seed0, seed0 + (schedules - half)),
        lambda s: exercise_gateway(s, poller="guarded"),
    )
    return {
        "schedules": fleet["schedules"] + gateway["schedules"],
        "fleet": fleet,
        "gateway": gateway,
        "violations": fleet["violations"] + gateway["violations"],
    }


# ---------------------------------------------------------------------------
# the real-process injector (the bench driver)
# ---------------------------------------------------------------------------


def _worker_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


def run_process_chaos(
    world: int = 2,
    duration_s: float = 8.0,
    kill_after_s: float = 3.0,
    restart_after_s: float = 0.5,
    kill_rank: int = 1,
    timeout_s: float = 180.0,
    seed: int = 0,
    telemetry_dir: Optional[str] = None,
) -> dict:
    """SIGKILL a REAL gossip worker mid-run and measure wall-clock
    time-to-recover: spawn `world` gossip-mode processes of
    `scripts/launch_multihost.py` against a shared mailbox, SIGKILL
    rank `kill_rank` at `kill_after_s` (mid-publish in expectation —
    gossip publishes every consumed block), restart it after
    `restart_after_s`, and time until its FIRST post-restart snapshot
    lands in the mailbox (the ring has fresh news from the killed rank
    again). This is the `multihost_scaling` bench's fault-injection
    driver; the simulated exercisers above cover the same protocol
    deterministically in tier-1."""
    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    launcher = os.path.join(repo, "scripts", "launch_multihost.py")
    env = _worker_env()

    def spawn(rank: int, dur: float, mailbox: str):
        cmd = [
            sys.executable, launcher, "--worker",
            "--rank", str(rank), "--processes", str(world),
            "--mode", "gossip", "--mailbox-dir", mailbox,
            "--duration-s", str(dur), "--iterations", "0",
            "--rollout-steps", "8", "--num-envs", "2", "--actors", "1",
            "--sleep-s", "0.004", "--epochs", "1", "--minibatches", "1",
            "--seed", str(seed),
        ]
        if telemetry_dir:
            cmd += ["--telemetry-dir", telemetry_dir]
        return subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env,
        )

    mailbox = tempfile.mkdtemp(prefix="fleetsan_chaos_")
    record: dict = {
        "world": world, "killed_rank": kill_rank,
        "kill_after_s": kill_after_s, "restart_after_s": restart_after_s,
        "duration_s": duration_s,
    }
    procs = {}
    try:
        t0 = time.monotonic()
        for r in range(world):
            procs[r] = spawn(r, duration_s, mailbox)
        if telemetry_dir:
            # Start the kill clock only once the victim is actually
            # recording: worker startup (jax import + session
            # construction) can dwarf kill_after_s on a cold cache, and
            # SIGKILLing before the flight ring exists would prove
            # nothing about crash recording.
            from actor_critic_tpu.telemetry import flight

            ring = os.path.join(
                telemetry_dir, f"host{kill_rank}", flight.RING_FILENAME
            )
            ready_deadline = time.monotonic() + timeout_s
            while time.monotonic() < ready_deadline:
                if flight.harvest(ring):
                    break
                if procs[kill_rank].poll() is not None:
                    break  # died at startup; surfaced by harvest below
                time.sleep(0.05)
        time.sleep(kill_after_s)
        victim = procs[kill_rank]
        victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=30)
        t_kill = time.monotonic()
        record["killed_at_s"] = round(t_kill - t0, 3)
        if telemetry_dir:
            # Post-mortem flight harvest (ISSUE 16) — BEFORE the
            # restart, which recreates (zeroes) the same rank's ring.
            # The victim got no chance to flush anything: every record
            # here survived SIGKILL purely via the mmap'd ring.
            from actor_critic_tpu.telemetry import flight

            ring = os.path.join(
                telemetry_dir, f"host{kill_rank}", flight.RING_FILENAME
            )
            flight_records = flight.harvest(ring)
            if not flight_records:
                raise FleetSanError(
                    f"SIGKILL'd rank {kill_rank} left no harvestable "
                    f"flight-ring records at {ring} — the crash "
                    "recorder lost the victim's final seconds"
                )
            record["flight_dump"] = flight.write_dump(
                os.path.join(
                    telemetry_dir, f"host{kill_rank}",
                    "flight_dump_sigkill_harvest.json",
                ),
                flight_records,
                reason="sigkill_harvest",
                meta={"rank": kill_rank, "seed": seed, "world": world},
            )
            record["flight_records"] = len(flight_records)
        time.sleep(restart_after_s)
        from actor_critic_tpu.parallel.multihost import params_file

        path = params_file(mailbox, kill_rank)
        try:
            mtime_before = os.stat(path).st_mtime
        except OSError:
            mtime_before = 0.0
        remaining = max(duration_s - (time.monotonic() - t0), 2.0)
        procs[kill_rank] = spawn(kill_rank, remaining, mailbox)
        t_rec = None
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                if os.stat(path).st_mtime > mtime_before:
                    t_rec = time.monotonic()
                    break
            except OSError:
                pass
            time.sleep(0.05)
        if t_rec is None:
            raise FleetSanError(
                f"killed rank {kill_rank} never republished within "
                f"{timeout_s:.0f}s of restart — rejoin broken"
            )
        record["time_to_recover_s"] = round(t_rec - t_kill, 3)
        summaries = {}
        for r, p in sorted(procs.items()):
            try:
                out, err = p.communicate(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                p.kill()
                out, err = p.communicate()
                raise FleetSanError(
                    f"worker {r} hung past {timeout_s:.0f}s after the "
                    "chaos window"
                )
            line = next(
                (
                    ln
                    for ln in reversed(out.strip().splitlines())
                    if ln.startswith("{")
                ),
                None,
            )
            if p.returncode != 0 or line is None:
                tail = (err or out).strip().splitlines()[-8:]
                raise FleetSanError(
                    f"worker {r} failed rc={p.returncode}: "
                    + "\n".join(tail)
                )
            import json as _json

            summaries[str(r)] = _json.loads(line)
        record["survivor_gossip_mixes"] = sum(
            s.get("gossip_mixes", 0)
            for r, s in summaries.items()
            if int(r) != kill_rank
        )
        record["restarted_consumed_blocks"] = summaries[
            str(kill_rank)
        ].get("consumed_blocks", 0)
        record["ok"] = (
            record["survivor_gossip_mixes"] > 0
            and record["restarted_consumed_blocks"] > 0
        )
        return record
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        shutil.rmtree(mailbox, ignore_errors=True)
