"""Multi-host distributed actor–learner (ISSUE 9 tentpole).

Every parallel layer below this one (`parallel/dp.py`, `mesh.py`,
`seqpar.py`) stops at a single process. This module stands the PR 6
async actor–learner stack up under `jax.distributed`: each process runs
its own shard-pool actor fleet feeding its local `TrajQueue`, and the
per-process learner scales in one of two modes —

- **sync** (Accelerated Methods for Deep RL, arxiv 1803.02811): the
  V-trace-corrected update (`ppo.make_async_update_fn`) is shard_map-ed
  over the GLOBAL device mesh, each process contributing its local
  `[T, E_a]` block as one dp shard of a global `[T, P*E_a]` batch
  (`jax.make_array_from_process_local_data`), with params/optimizer
  replicated — the per-minibatch gradient pmean the update already does
  becomes the cross-process all-reduce, exactly how `parallel/dp.py`
  scales the fused step across local devices. The update is therefore a
  global barrier: the behavior-version counter advances in lockstep on
  every host (verified each iteration by an all-reduced counter +
  replicated-params fingerprint — `make_consistency_check`), so
  `max_staleness` keeps its fleet-wide meaning. A straggler host stalls
  the fleet — that is the measured cost the gossip mode removes.

- **gossip** (Gossip-based Actor-Learner Architectures, arxiv
  1906.04585): per-host learners update INDEPENDENTLY (no collective,
  no barrier) and exchange parameters peer-to-peer on a rotating ring
  schedule through a filesystem param mailbox: every `gossip_every`
  consumed blocks a host atomically publishes its `(version, params)`
  snapshot under `mailbox_dir/host<rank>/` and mixes in the latest
  snapshot a background `FileMailboxWriter` thread deposited from the
  scheduled peer (`gossip_peer` rotates the ring so weights diffuse
  through the whole fleet in O(P) rounds). `gossip_weight` is the
  mixing knob: `params ← (1-w)·own + w·peer`. A straggler host only
  serves stale params to its peers — the fleet never waits on it.

Version accounting across hosts: versions stay plain monotonic ints =
blocks consumed (the PR 6 contract). In sync mode the global barrier
makes every host's counter identical; in gossip mode each host counts
its own consumption and the peer lag (`gossip_lag`) is surfaced per
mix, so staleness is measured, never hidden.

The in-memory `ParamMailbox` carries the same frozen-snapshot contract
as `PolicyPublisher.publish` (ISSUE 7): `deposit` stores a read-only
copy, so the writer thread keeps no writable alias of what the learner
consumes and a racing in-place write crashes at its own site
(`analysis/racesan.exercise_mailbox` gates the pair in tier-1).

Everything is drivable on CPU: `distributed_init` turns on the gloo
CPU collectives implementation, and `scripts/launch_multihost.py`
spawns an N-process local cluster against a localhost coordinator — the
tier-1 smoke and the `multihost_scaling` bench run with no TPU present.
"""

# jaxlint: hot-module

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Any, Callable, Optional

import numpy as np

from actor_critic_tpu.algos.traj_queue import _snapshot_frozen
from actor_critic_tpu.parallel.mesh import DP_AXIS, multihost_init
from actor_critic_tpu.utils import numguard


def distributed_init(
    coordinator: str,
    num_processes: int,
    process_id: int,
) -> None:
    """`jax.distributed.initialize` against an explicit coordinator,
    with the CPU backend's cross-process collectives enabled first
    (XLA:CPU refuses multi-process computations without an explicit
    collectives implementation; gloo is the in-tree one). Must run
    before anything initializes the XLA backend — same contract as
    `mesh.multihost_init`, which this wraps."""
    import jax

    if os.environ.get("JAX_PLATFORMS", "").strip() in ("cpu", ""):
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:
            pass  # non-CPU backends (TPU pods) bring their own transport
    multihost_init(
        coordinator=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def global_mesh():
    """One-axis dp mesh over EVERY process's devices (the cross-process
    analogue of `mesh.make_mesh`)."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()), (DP_AXIS,))


def host_lane(rank: int) -> None:
    """Name this process's Perfetto lane `host<rank>` in the installed
    telemetry session (the PR 3 trace relay renders one lane per pid;
    the rank label is what makes a fleet trace readable)."""
    from actor_critic_tpu import telemetry

    sess = telemetry.current()
    if sess is not None:
        sess.tracer.name_process(os.getpid(), f"host{rank}")


# ---------------------------------------------------------------------------
# param mailbox: in-memory (latest-wins, frozen snapshots) + file transport
# ---------------------------------------------------------------------------


class ParamMailbox:
    """Thread-safe latest-wins store of one peer `(version, params)`
    snapshot — the per-host mailbox of the gossip exchange.

    Same frozen-snapshot contract as `PolicyPublisher.publish`
    (ISSUE 7): `deposit` copies the numpy leaves and flips
    `writeable = False`, so the depositing thread retains no writable
    alias of what the learner consumes, and an in-place write into a
    consumed tree crashes at the write site. `take` hands out the
    latest snapshot at most once (None until a newer deposit lands);
    `peek` reads without consuming.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._params: Any = None
        self._version = -1
        self._peer = -1
        self._taken = True
        self._deposits = 0
        # peer rank -> newest version accepted from THAT peer: versions
        # are per-peer consumption clocks and are NOT comparable across
        # peers — a slow host's version 5 can be fresher news than a
        # fast host's version 50, so the staleness drop must guard
        # per-peer regression only or the ring would permanently mute
        # every host slower than the fastest ever seen.
        self._peer_versions: dict[int, int] = {}

    def deposit(self, params: Any, version: int, peer: int) -> bool:
        """Store a frozen snapshot; a version the SAME peer already
        reached (<= its newest seen) is dropped so the learner never
        mixes that peer backwards — a different peer (the ring rotated)
        always wins. Returns True when the deposit became the mailbox's
        latest."""
        snapshot = _snapshot_frozen(params)  # copy OUTSIDE the lock
        with self._lock:
            if version <= self._peer_versions.get(int(peer), -1):
                return False
            self._peer_versions[int(peer)] = int(version)
            self._params = snapshot
            self._version = int(version)
            self._peer = int(peer)
            self._taken = False
            self._deposits += 1
            return True

    def take(self) -> Optional[tuple[int, int, Any]]:
        """(version, peer, frozen params) if a deposit landed since the
        last take, else None — the learner's once-per-gossip-round
        consume."""
        with self._lock:
            if self._taken or self._params is None:
                return None
            self._taken = True
            return self._version, self._peer, self._params

    def peek(self) -> Optional[tuple[int, int, Any]]:
        with self._lock:
            if self._params is None:
                return None
            return self._version, self._peer, self._params

    def stats(self) -> dict:
        with self._lock:
            return {
                "version": self._version,
                "peer": self._peer,
                "deposits": self._deposits,
            }


def params_file(mailbox_dir: str, rank: int) -> str:
    return os.path.join(mailbox_dir, f"host{rank}", "params.npz")


def write_params(mailbox_dir: str, rank: int, version: int, params: Any) -> str:
    """Atomically publish this host's `(version, params)` snapshot:
    flattened leaves into an .npz written next to the target, fsynced,
    and `os.replace`-d into place, so a peer reading concurrently sees
    either the previous complete snapshot or this one — never a torn
    file (and, post-crash, never a rename that outlived its data
    blocks). Latest-wins by construction (one file per host); the tmp
    name carries the pid so restarted/colliding writers in a shared
    directory can never interleave into one file."""
    import jax

    path = params_file(mailbox_dir, rank)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    leaves = jax.tree.leaves(params)
    payload = {f"leaf{i}": np.asarray(v) for i, v in enumerate(leaves)}
    # Finiteness gate (ISSUE 14): a nan/inf snapshot published here
    # diffuses through the gossip ring to EVERY peer within world-1
    # rounds and poisons each learner's mix_params — the one place a
    # single host's divergence becomes a fleet-wide one. Refuse the
    # publish; the mailbox keeps this host's previous good snapshot.
    numguard.check_finite(payload, "mailbox publish", name="params")
    payload["version"] = np.asarray(int(version), np.int64)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        np.savez(f, **payload)
        # fsync BEFORE the rename: without it a crash can leave the
        # rename durable while the data blocks are not — a zero-length
        # "complete" snapshot, the one torn shape atomic-rename alone
        # does not exclude.
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def _load_snapshot(path: str):
    """`(version, leaves)` of a published snapshot file, or None when
    it is absent, the read raced the very first publish's creation, or
    the file is torn/partial (a crashed or non-atomic writer): torn
    reads are retried on the next poll, never fatal. The ONE place the
    torn-file exception set lives — NB `np.load` raises
    `zipfile.BadZipFile` (NOT an OSError) on a truncated archive and
    `EOFError` on an empty one; the reverted PR 12 reader missed both
    and the mailbox writer thread died on the first torn snapshot."""
    import zipfile

    try:
        with np.load(path) as z:
            version = int(z["version"])
            leaves = [z[f"leaf{i}"] for i in range(len(z.files) - 1)]
    except (OSError, KeyError, ValueError, EOFError, zipfile.BadZipFile):
        return None
    return version, leaves


def read_params(mailbox_dir: str, rank: int, template: Any):
    """Latest published `(version, params)` of `rank`, rebuilt into
    `template`'s tree structure; None when absent/torn (the
    `_load_snapshot` tolerance contract)."""
    import jax

    out = _load_snapshot(params_file(mailbox_dir, rank))
    if out is None:
        return None
    version, leaves = out
    return version, jax.tree.unflatten(jax.tree.structure(template), leaves)


def read_version(mailbox_dir: str, rank: int) -> Optional[int]:
    """Version field alone of `rank`'s published snapshot — no params
    template needed, so observers (FleetMonitor, an LB health probe)
    can read a fleet's mailbox without knowing its tree structure;
    None when absent/torn (the `_load_snapshot` tolerance contract)."""
    out = _load_snapshot(params_file(mailbox_dir, rank))
    return None if out is None else out[0]


def gossip_peer(rank: int, world: int, round_: int) -> int:
    """Rotating ring schedule: at round r every host reads from the
    peer `1 + r mod (world-1)` ranks ahead, so over world-1 consecutive
    rounds each host hears from EVERY other host — parameters diffuse
    through the whole fleet without any global step."""
    if world < 2:
        raise ValueError("gossip needs at least 2 hosts")
    return (rank + 1 + round_ % (world - 1)) % world


def mix_params(own: Any, peer: Any, weight: float) -> Any:
    """Per-leaf convex mix `(1-w)·own + w·peer` (numpy trees; the
    gossip-averaging step of arxiv 1906.04585, weight = the mixing
    knob). Leaf dtypes are preserved."""
    import jax

    w = float(weight)
    return jax.tree.map(
        lambda a, b: ((1.0 - w) * a + w * b).astype(np.asarray(a).dtype),
        own, peer,
    )


class FileMailboxWriter:
    """The mailbox writer thread: polls the ring-scheduled peer's
    published snapshot file and deposits fresh versions into the local
    `ParamMailbox`. Polling runs OFF the learner thread so a slow/cold
    filesystem read never blocks an update; the learner only flips the
    current round (`set_round`) and takes deposits.

    The thread model (`analysis/thread_model.py`) learns this spawn as
    the `mailbox` role; the deposit path is lock-guarded inside
    ParamMailbox and the snapshot it stores is frozen, so the writer
    retains no writable alias (racesan's `exercise_mailbox` covers the
    publish/consume pair).
    """

    def __init__(
        self,
        mailbox_dir: str,
        rank: int,
        world: int,
        template: Any,
        mailbox: ParamMailbox,
        stop: threading.Event,
        poll_s: float = 0.05,
    ):
        self._dir = mailbox_dir
        self._rank = int(rank)
        self._world = int(world)
        self._template = template
        self._mailbox = mailbox
        self._stop = stop
        self._poll_s = float(poll_s)
        # jaxlint: thread-owned=caller (plain int rebound by the learner
        # thread via set_round; the writer thread only reads it and
        # tolerates a one-poll-stale round — it would just re-read the
        # previous peer's file once)
        self._round = 0
        # jaxlint: thread-owned=mailbox (single writer: poll_once is
        # only ever called from the mailbox thread's _run loop — or, in
        # fleetsan, from the scheduler with the thread never started —
        # and nothing else reads the per-peer clock)
        self._seen: dict[int, int] = {}
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name=f"mailbox-{rank}", daemon=True
        )

    def set_round(self, round_: int) -> None:
        """Advance the ring schedule (called by the learner at gossip
        boundaries; plain atomic rebind)."""
        self._round = int(round_)

    def start(self) -> "FileMailboxWriter":
        self._thread.start()
        return self

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)

    def poll_once(self) -> bool:
        """ONE poll of the ring-scheduled peer: read its published
        snapshot, drop versions that peer already reached (versions are
        per-peer clocks — `self._seen` tracks the newest PER RANK so
        the ring rotating onto a slower peer still deposits its
        lower-numbered fresh news), deposit the rest. Returns True when
        a deposit landed. Factored out of the thread loop so fleetsan
        can drive the REAL consume logic under a deterministic
        scheduler (no thread, no wall-clock)."""
        peer = gossip_peer(self._rank, self._world, self._round)
        out = read_params(self._dir, peer, self._template)
        if out is None:
            return False
        version, params = out
        if version <= self._seen.get(peer, -1):
            return False
        if self._mailbox.deposit(params, version, peer):
            self._seen[peer] = version
            return True
        return False

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                self.poll_once()
                self._stop.wait(self._poll_s)
        except BaseException as e:  # surfaced by the learner loop
            self.error = e


class FleetMonitor:
    """Fleet-membership observability over the gossip mailbox (ROADMAP
    elastic-ops item (d), ISSUE 12 satellite): rank, world size, and
    per-peer last-publish age read from the shared `mailbox_dir` — the
    same files the exchange itself uses, so "this peer went quiet" is
    measured at the transport, not inferred. `snapshot()` feeds
    `/healthz` (serving gateway `--distributed`): a peer whose mailbox
    age exceeds `stale_after_s` (or that never published) marks the
    fleet degraded and the endpoint answers 503.

    Ages come from `os.stat` mtime — no parse, so a torn file still
    reports an age; the version field rides via `read_version` when
    the file parses (torn/absent -> None, the `read_params` tolerance
    contract — no params template needed)."""

    def __init__(
        self,
        mailbox_dir: str,
        rank: int,
        world: int,
        stale_after_s: float = 30.0,
    ):
        self.mailbox_dir = mailbox_dir
        self.rank = int(rank)
        self.world = int(world)
        self.stale_after_s = float(stale_after_s)

    def snapshot(self) -> dict:
        """{rank, world, stale_after_s, peers: {rank: {age_s, version,
        published}}, stale: [ranks], ok}. Peers = every OTHER rank of
        the fleet; `ok` iff none is stale."""
        now = time.time()
        peers: dict[str, dict] = {}
        stale: list[int] = []
        for peer in range(self.world):
            if peer == self.rank:
                continue
            path = params_file(self.mailbox_dir, peer)
            entry: dict = {"published": False, "age_s": None, "version": None}
            try:
                entry["age_s"] = round(now - os.stat(path).st_mtime, 3)
                entry["published"] = True
            except OSError:
                pass
            if entry["published"]:
                entry["version"] = read_version(self.mailbox_dir, peer)
            if not entry["published"] or entry["age_s"] > self.stale_after_s:
                stale.append(peer)
            peers[str(peer)] = entry
        return {
            "rank": self.rank,
            "world": self.world,
            "stale_after_s": self.stale_after_s,
            "peers": peers,
            "stale": stale,
            "ok": not stale,
        }


# ---------------------------------------------------------------------------
# sync mode: global-mesh data-parallel update + consistency check
# ---------------------------------------------------------------------------


def _block_spec(ndim: int):
    """PartitionSpec of one [T, E, ...] block array under the global dp
    mesh: the env axis (axis 1) is the shard axis — the cross-process
    extension of `dp.py`'s P("dp") leading-axis convention, shifted one
    axis because host blocks are time-major."""
    from jax.sharding import PartitionSpec as P

    return P(*(None, DP_AXIS) + (None,) * (ndim - 2))


def make_multihost_update_step(
    env_spec,
    cfg,
    mesh,
    correction: str = "vtrace",
    rho_bar: float = 1.0,
    c_bar: float = 1.0,
):
    """The sync-mode learner program: `ppo.make_async_update_fn` with
    `axis_name=DP_AXIS`, shard_map-ed over the global mesh and jitted.

    Call it through `stage_global` arrays: params/opt/key replicated,
    block arrays dp-sharded on their env axis (each process contributes
    its own `[T, E_a]` block; the global batch is `[T, P*E_a]`). The
    per-minibatch gradient pmean inside `ppo_update` lowers to the
    cross-process all-reduce — the DCN analogue of `dp.py`'s ICI one.
    The raw uint32 key data is passed replicated and wrapped in-program
    (typed PRNG keys don't ride `make_array_from_process_local_data`).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from actor_critic_tpu.algos import ppo

    update_fn = ppo.make_async_update_fn(
        env_spec, cfg, can_truncate=True, correction=correction,
        rho_bar=rho_bar, c_bar=c_bar, axis_name=DP_AXIS,
    )

    def local_step(
        params, opt_state, key_data, obs, action, log_prob, value, reward,
        done, terminated, final_obs, last_obs, progress,
    ):
        key = jax.random.wrap_key_data(key_data)
        return update_fn(
            params, opt_state, obs, action, log_prob, value, reward, done,
            terminated, final_obs, last_obs, key, progress=progress,
        )

    def specs_of(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def build(params, opt_state, key_data, arrays, progress):
        in_specs = (
            specs_of(params, P()),
            specs_of(opt_state, P()),
            P(),                                    # key data (replicated)
            _block_spec(arrays["obs"].ndim),
            _block_spec(arrays["action"].ndim),
            _block_spec(2), _block_spec(2),         # log_prob, value
            _block_spec(2), _block_spec(2),         # reward, done
            _block_spec(2),                         # terminated
            _block_spec(arrays["final_obs"].ndim),
            P(*(DP_AXIS,) + (None,) * (arrays["last_obs"].ndim - 1)),
            P(),                                    # progress scalar
        )
        out_specs = (specs_of(params, P()), specs_of(opt_state, P()), P())
        fn = jax.shard_map(
            local_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(fn)

    # One program per run: specs depend only on static shapes, so build
    # lazily on first call and reuse (the blocks are PR 4 fixed-shape
    # buckets — steady state compiles nothing new).
    cache: dict = {}

    def update(params, opt_state, key_data, arrays, progress):
        if "fn" not in cache:
            cache["fn"] = build(params, opt_state, key_data, arrays, progress)
        return cache["fn"](
            params, opt_state, key_data, arrays["obs"], arrays["action"],
            arrays["log_prob"], arrays["value"], arrays["reward"],
            arrays["done"], arrays["terminated"], arrays["final_obs"],
            arrays["last_obs"], progress,
        )

    return update


def stage_global(mesh, arrays: dict[str, np.ndarray]) -> dict:
    """Per-process local block arrays → global dp-sharded arrays (env
    axis split across processes). The inputs must already be snapshots
    (the learner np.array-copies queue slots before staging)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = {}
    for name, value in arrays.items():
        if name == "last_obs":
            spec = P(*(DP_AXIS,) + (None,) * (value.ndim - 1))
        else:
            spec = _block_spec(value.ndim)
        out[name] = jax.make_array_from_process_local_data(
            NamedSharding(mesh, spec), value
        )
    return out


def replicate_global(mesh, tree):
    """Identical per-process host trees → one replicated global array
    tree (initial params/opt staging; afterwards the update's outputs
    stay resident as replicated global arrays)."""
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    return multihost_utils.host_local_array_to_global_array(tree, mesh, P())


def fetch_local(tree):
    """Per-process numpy view of a REPLICATED global array tree (each
    process holds a full copy as its addressable shard)."""
    import jax

    return jax.tree.map(
        lambda x: np.asarray(x.addressable_data(0)), tree
    )


def make_consistency_check(mesh) -> Callable[..., tuple]:
    """ONE jitted collective over a small per-process vector
    `(version, fingerprint, stop_vote)`; returns
    `(version_sum, fp_max, fp_min, vote_sum)` for the whole fleet.

    - `version_sum == n_devices * local_version` is the
      broadcast-counter check: the counter is a small integer, so the
      float32 psum is EXACT for any fleet size (no rounding below
      2^24) and equality holds iff every host carries the same value.
    - The fingerprint compares via `fp_max == fp_min == local` — a
      pmax/pmin pair instead of a sum, because summing N identical
      floats rounds for non-power-of-two N while min==max equality is
      bit-exact for ANY fleet size.
    - A nonzero `vote_sum` is the fleet-agreed stop signal: every host
      computes the same sum, so duration-bounded sync runs all break
      after the SAME iteration — no host is left alone at the next
      collective.

    The local contribution is staged with one row per LOCAL device
    (identical rows), so the dp-sharded placement works on hosts with
    any number of addressable devices (a pod host's 4/8 chips), not
    just the 1-device CPU cluster.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    def reduce_fn(x):  # local [rows, 3]
        vsum = jax.lax.psum(x[0, 0], DP_AXIS)
        fp_max = jax.lax.pmax(x[0, 1], DP_AXIS)
        fp_min = jax.lax.pmin(x[0, 1], DP_AXIS)
        votes = jax.lax.psum(x[0, 2], DP_AXIS)
        return jnp.stack([vsum, fp_max, fp_min, votes])

    fn = jax.jit(
        jax.shard_map(
            reduce_fn,
            mesh=mesh, in_specs=P(DP_AXIS, None), out_specs=P(),
            check_vma=False,
        )
    )
    sharding = NamedSharding(mesh, P(DP_AXIS, None))
    local_rows = max(1, len(jax.local_devices()))

    def check(version: float, fingerprint: float, vote: float) -> tuple:
        row = np.asarray([[version, fingerprint, vote]], np.float32)
        arr = jax.make_array_from_process_local_data(
            sharding, np.repeat(row, local_rows, axis=0)
        )
        out = np.asarray(fn(arr).addressable_data(0)).reshape(-1)
        return float(out[0]), float(out[1]), float(out[2]), float(out[3])

    return check


def params_fingerprint(tree) -> float:
    """Order-stable scalar digest of a numpy params tree (sum of leaf
    sums; replicated trees produce bit-identical floats on every host,
    so a psum equality check catches any divergence)."""
    import jax

    return float(
        sum(np.sum(np.asarray(leaf, np.float64)) for leaf in jax.tree.leaves(tree))
    )


# ---------------------------------------------------------------------------
# the per-process driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GossipConfig:
    """Gossip-mode knobs (ignored in sync mode)."""

    every: int = 1        # consumed blocks between exchanges
    weight: float = 0.5   # peer mixing weight in [0, 1]
    poll_s: float = 0.05  # mailbox writer poll cadence


def train_multihost(
    pools,
    cfg,
    num_iterations: int,
    *,
    rank: int,
    world: int,
    mode: str = "sync",
    duration_s: Optional[float] = None,
    seed: int = 0,
    log_every: int = 10,
    log_fn: Optional[Callable[[int, dict], None]] = None,
    queue_depth: int = 4,
    max_staleness: Optional[int] = 8,
    updates_per_block: int = 1,
    correction: str = "vtrace",
    rho_bar: float = 1.0,
    c_bar: float = 1.0,
    gossip: GossipConfig = GossipConfig(),
    mailbox_dir: Optional[str] = None,
):
    """One process's share of the distributed actor–learner fleet.

    Each process runs `len(pools)` `ActorService` threads feeding its
    local `TrajQueue` (identical to `ppo.train_host_async`'s host side)
    and one learner consuming blocks per `mode` (module docstring).
    `seed` must be IDENTICAL across processes — initial params derive
    from it, and sync mode's replicated state assumes equal starts;
    actor RNG streams are decorrelated per (rank, actor) internally.

    With `duration_s` set the run is WALL-bounded instead of
    count-bounded (`num_iterations` becomes a hard cap, pass a large
    one): each learner consumes as many blocks as it can inside the
    window — the measurement mode of the `multihost_scaling` bench,
    where a straggler's effect shows up as blocks NOT consumed. In sync
    mode the stop decision is itself all-reduced (a vote riding the
    per-iteration consistency check), so every host exits after the
    same iteration and nobody strands at the next collective; gossip
    hosts stop on their own clock (no barrier to strand at).

    Sync mode requires `jax.distributed` initialized with `world`
    processes (`distributed_init`); gossip mode needs only
    `mailbox_dir` (a directory shared by all hosts — peer-to-peer
    exchange never enters a collective). Returns
    `(np_params, history, summary)`; history rows carry the queue/
    staleness gauges plus `version_sum`/`fingerprint_ok` (sync) or
    `gossip_mixes`/`gossip_lag` (gossip).
    """
    import jax

    from actor_critic_tpu import telemetry
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.algos.host_loop import (
        MergedEpisodeTracker,
        maybe_log,
    )
    from actor_critic_tpu.algos.traj_queue import (
        ActorService,
        PolicyPublisher,
        TrajQueue,
        consume_block,
        validate_pools,
    )
    from actor_critic_tpu.models import host_actor

    if mode not in ("sync", "gossip"):
        raise ValueError(f"unknown mode {mode!r}")
    if mode == "sync" and correction != "vtrace":
        raise ValueError(
            "sync mode shard_maps the V-trace-corrected update "
            "(make_async_update_fn); correction='none' is only "
            "available in gossip mode or the single-host async driver"
        )
    if mode == "gossip" and world > 1 and not mailbox_dir:
        raise ValueError("gossip mode needs a shared mailbox_dir")
    spec, E_a = validate_pools(pools)

    key = jax.random.key(seed)
    key, pkey = jax.random.split(key)
    params, opt_state = ppo.init_host_params(spec, cfg, pkey)
    np_params = jax.device_get(params)
    if not host_actor.supports_mirror(np_params):
        raise ValueError(
            "multi-host mode needs the numpy actor mirror (MLP torso)"
        )
    host_policy = host_actor.make_ppo_host_policy(spec, cfg)

    def make_act_fn(actor_params, rng):
        def act(o):
            action, logp, value = host_policy(actor_params, o, rng)
            return action, {"log_prob": logp, "value": value}

        return act

    queue = TrajQueue(
        depth=queue_depth, max_staleness=max_staleness,
        policy="drop_oldest", gauge_name=f"traj_queue_host{rank}",
    )
    publisher = PolicyPublisher(np_params, version=0)
    stop = threading.Event()
    actors = [
        ActorService(
            i, pool, queue, publisher, cfg.rollout_steps, make_act_fn,
            # Decorrelate across the fleet: rank strides by a large
            # prime over the per-actor prime stride.
            rng=np.random.default_rng(
                seed + 0x5EED + rank * 1_000_003 + i * 7919
            ),
            stop=stop,
        )
        for i, pool in enumerate(pools)
    ]

    mesh = update = check = None
    mailbox = writer = None
    local_update = None
    if mode == "sync":
        mesh = global_mesh()
        if mesh.devices.size < world:
            raise ValueError(
                f"sync mode: mesh has {mesh.devices.size} devices for "
                f"world={world} — was distributed_init called?"
            )
        update = make_multihost_update_step(
            spec, cfg, mesh, correction=correction,
            rho_bar=rho_bar, c_bar=c_bar,
        )
        check = make_consistency_check(mesh)
        params = replicate_global(mesh, jax.device_get(params))
        opt_state = replicate_global(mesh, jax.device_get(opt_state))
    else:
        local_update = ppo.make_async_update_step(
            spec, cfg, can_truncate=True, correction=correction,
            rho_bar=rho_bar, c_bar=c_bar,
        )
        if world > 1:
            mailbox = ParamMailbox()
            writer = FileMailboxWriter(
                mailbox_dir, rank, world, template=np_params,
                mailbox=mailbox, stop=stop, poll_s=gossip.poll_s,
            )
            # Publish the INITIAL params so peers' first reads succeed.
            write_params(mailbox_dir, rank, 0, np_params)
            writer.start()

    history: list = []
    trackers = MergedEpisodeTracker([a.tracker for a in actors])
    summary = {
        "rank": rank, "world": world, "mode": mode,
        "version_consistent": True, "fingerprint_consistent": True,
        "gossip_mixes": 0, "gossip_skips": 0, "gossip_lag_max": 0,
    }
    t_start = time.perf_counter()
    deadline = None if duration_s is None else t_start + float(duration_s)
    consumed_blocks = 0
    try:
        for a in actors:
            a.start()
        for it in range(num_iterations):
            telemetry.profiler_tick()
            for a in actors:
                if a.error is not None:
                    raise RuntimeError(
                        f"host {rank} actor {a.actor_id} died"
                    ) from a.error
            if writer is not None and writer.error is not None:
                raise RuntimeError(
                    f"host {rank} mailbox writer died"
                ) from writer.error
            with telemetry.span("iteration", it=it + 1):
                queue.set_consumer_version(it)
                with telemetry.span("queue_wait", it=it + 1):
                    block = consume_block(
                        queue, actors, context=f"host {rank} "
                    )
                staleness = max(it - block.version, 0)
                stop_after = False
                progress = np.float32(
                    min(it / cfg.anneal_iters, 1.0)
                    if cfg.anneal_iters > 0 else 0.0
                )
                extra = {}
                if mode == "sync":
                    with telemetry.span("host_to_device"):
                        # Snapshot the slot before release (the PR 6
                        # copy-on-transfer contract), then stage onto
                        # the global mesh.
                        # jaxlint: disable=host-sync (host-numpy copy of
                        # a queue slot — no device value is touched; the
                        # slot must be snapshotted before release
                        # rewrites it)
                        local = {
                            k: np.array(v) for k, v in block.arrays.items()
                        }
                        queue.release(block)
                        garrays = stage_global(mesh, local)
                    with telemetry.span("update", dispatch="async"):
                        for _ in range(updates_per_block):
                            key, ukey = jax.random.split(key)
                            # jaxlint: disable=donation-discipline
                            # (withheld: the replicated global-mesh
                            # trees feed the consistency check and the
                            # mailbox publish after the dispatch;
                            # donation is the ROADMAP kernel-level
                            # item's change, gated by perfsan)
                            params, opt_state, metrics = update(
                                params, opt_state,
                                # jaxlint: disable=host-sync (deliberate:
                                # the 2-word key data rides replicated as
                                # host numpy — typed PRNG keys don't
                                # cross make_array_from_process_local_data)
                                np.asarray(jax.random.key_data(ukey)),
                                garrays, progress,
                            )
                    np_params = fetch_local(params)
                    version = it + 1
                    # Broadcast-counter + replicated-params checks plus
                    # the stop vote, ONE collective (fp is the float32
                    # representative of the local digest; see
                    # make_consistency_check for why the counter uses
                    # an exact psum and the fingerprint a pmax/pmin
                    # equality).
                    fp = float(np.float32(params_fingerprint(np_params)))
                    vote = 1.0 if (
                        deadline is not None
                        and time.perf_counter() >= deadline
                    ) else 0.0
                    # jaxlint: disable=host-sync (deliberate: the
                    # consistency check IS a designed per-iteration
                    # barrier — sync mode's update is already a global
                    # collective, so this adds one tiny collective, not
                    # a new serialization)
                    vsum, fp_max, fp_min, votes = check(
                        float(version), fp, vote
                    )
                    stop_after = votes > 0
                    # jaxlint: disable=host-sync (python floats — the
                    # device sync happened inside `check` above)
                    v_ok = bool(vsum == mesh.devices.size * float(version))
                    fp_ok = bool(fp_max == fp_min == fp)
                    summary["version_consistent"] &= v_ok
                    summary["fingerprint_consistent"] &= fp_ok
                    extra.update(
                        version_sum=vsum, version_ok=v_ok,
                        fingerprint_ok=fp_ok,
                    )
                    # jaxlint: disable=host-sync (deliberate: scalar
                    # metric fetch after the update — the consistency
                    # check already fenced this iteration's dispatch)
                    metrics = {
                        k: np.asarray(v.addressable_data(0))
                        for k, v in metrics.items()
                    }
                else:
                    with telemetry.span("host_to_device"):
                        # jnp.array, NOT asarray: one copying transfer
                        # snapshots the slot (the PR 6 contract) —
                        # releasing only after it materializes.
                        # jaxlint: disable=transfer-discipline (the
                        # host plane's per-block upload by design —
                        # perfsan budgets the bytes)
                        arrays = {
                            k: jax.numpy.array(v)
                            for k, v in block.arrays.items()
                        }
                        queue.release(block)
                    kwargs = {}
                    if cfg.anneal_iters > 0:
                        # jaxlint: disable=transfer-discipline (scalar
                        # anneal progress — 4 bytes)
                        kwargs["progress"] = jax.numpy.asarray(progress)
                    with telemetry.span("update", dispatch="async"):
                        for _ in range(updates_per_block):
                            key, ukey = jax.random.split(key)
                            # jaxlint: disable=donation-discipline
                            # (withheld: gossip mixes and the mailbox
                            # publish read the input tree around the
                            # dispatch — the ROADMAP kernel-level item
                            # owns the donation change, perfsan-gated)
                            params, opt_state, metrics = local_update(
                                params, opt_state,
                                arrays["obs"], arrays["action"],
                                arrays["log_prob"], arrays["value"],
                                arrays["reward"], arrays["done"],
                                arrays["terminated"], arrays["final_obs"],
                                arrays["last_obs"], ukey, **kwargs,
                            )
                    # jaxlint: disable=transfer-discipline (deliberate:
                    # the gossip publish snapshot — one host fetch per
                    # block is the mailbox contract)
                    np_params = jax.device_get(params)
                    version = it + 1
                    stop_after = (
                        deadline is not None
                        and time.perf_counter() >= deadline
                    )
                    if mailbox is not None and version % gossip.every == 0:
                        round_ = version // gossip.every
                        writer.set_round(round_)
                        deposit = mailbox.take()
                        if deposit is not None:
                            peer_version, peer, peer_params = deposit
                            lag = max(version - peer_version, 0)
                            np_params = mix_params(
                                np_params, peer_params, gossip.weight
                            )
                            # jaxlint: disable=transfer-discipline
                            # (deliberate: re-placing the gossip-mixed
                            # params — once per gossip round, not per
                            # step)
                            params = jax.device_put(np_params)
                            summary["gossip_mixes"] += 1
                            summary["gossip_lag_max"] = max(
                                summary["gossip_lag_max"], lag
                            )
                            extra.update(
                                gossip_peer=peer, gossip_lag=lag
                            )
                        else:
                            summary["gossip_skips"] += 1
                        write_params(mailbox_dir, rank, version, np_params)

                publisher.publish(np_params, version=it)
                qs = queue.stats()
                extra.update(
                    env_steps=sum(a.steps_collected for a in actors),
                    consumed_env_steps=(it + 1) * cfg.rollout_steps * E_a,
                    block_actor=block.actor_id,
                    block_staleness=staleness,
                    queue_depth=qs["depth"],
                    queue_drops_full=qs["drops_full"],
                    queue_drops_stale=qs["drops_stale"],
                    learner_idle_s=qs["learner_idle_s"],
                )
                maybe_log(
                    it, log_every, metrics, trackers, history, log_fn,
                    extra=extra,
                    num_iterations=0 if deadline is not None else num_iterations,
                    force=it == 0,
                )
                consumed_blocks = it + 1
                if stop_after:
                    break
    finally:
        stop.set()
        for a in actors:
            a.join(timeout=30.0)
        if writer is not None:
            writer.join(timeout=5.0)
        queue.close()
    wall = time.perf_counter() - t_start
    consumed = consumed_blocks * cfg.rollout_steps * E_a
    summary.update(
        consumed_blocks=consumed_blocks,
        wall_s=round(wall, 3),
        consumed_env_steps=consumed,
        consumed_steps_per_s=round(consumed / wall, 1) if wall > 0 else 0.0,
        collected_env_steps=sum(a.steps_collected for a in actors),
        learner_idle_s=round(queue.stats()["learner_idle_s"], 3),
    )
    return np_params, history, summary
