"""Device-resident trajectory ring (ISSUE 13 tentpole).

The PR 6 `TrajQueue` is host numpy by design: every consumed block costs
one host→device transfer on the LEARNER's critical path — exactly like
lockstep, just off-thread for collection. This module keeps the
trajectory data in HBM end to end instead (Accelerated Methods, arxiv
1803.02811: large-batch device-side processing is where the parallelism
lives; IMPACT's per-block surrogate reuse, arxiv 1912.00167, only pays
when the block is already resident):

- **Storage** is a donated ring of fixed-shape encoded blocks living on
  the device: a pytree of `[depth, K, E, ...]` arrays at the codec
  storage dtype (`replay/quantize.py` kinds — raw / f16 / calibrated
  i8 / bool8, selected per block key by `codecs.traj_codecs`).
- **Actors enqueue encoded blocks**: the producer thread quantizes its
  numpy block on the host (`data_plane/codecs.py`, the numpy mirror of
  the quantize codecs — calibrate-then-freeze stats included), puts the
  encoded bytes to the device (int8 obs cross at 1/4 of the fp32
  bytes), and dispatches one donated `enqueue` program that scatters
  the block into its slot. The device-side cursor/version tree
  (`versions`/`seqs`/`count` riding `RingState`) tracks occupancy and
  the behavior-params version each slot was collected under; the host
  keeps a bit-equal mirror (the pending/free bookkeeping below) for
  scheduling decisions, so no device read-back is ever needed to pick
  a slot.
- **The learner gathers + decodes INSIDE its jitted update program**
  (`gather_block`, inlined by `ppo.make_device_update_step` and
  `device_replay.make_device_ingest_update`): steady-state consumption
  performs ZERO host→device transfers — the only traffic is the slot
  index scalar riding the dispatch.

Semantics carry over from `TrajQueue` unchanged: `policy="drop_oldest"`
reclaims the oldest pending slot when the ring is full (actors never
wait on the learner; the drop is counted), `policy="block"` is the
strict mode the lockstep-equivalence tests run under, and
`max_staleness` drops blocks whose behavior version aged past the bound
at `get` time. With the all-`raw` `fp32` codec the decoded block is
bit-identical to the host path's arrays, so `correction="none"` at
depth 1 is bitwise-equal to `train_host` (tests/test_async_host.py).

Donation discipline: `put` dispatches the donating `enqueue` and the
learner dispatches its (non-donating) gather+update under ONE lock, so
dispatch order — which is device execution order — always reads a slot
before the enqueue that overwrites it, and no thread can donate a state
handle another thread is about to dispatch with (the `run()` seam).
jaxlint's donation-aliasing pass covers the enqueue/gather call shapes
(tests/jaxlint_fixtures/donation_aliasing_*.py) and
`analysis/racesan.exercise_device_ring` drives the enqueue-vs-gather
interleavings with a leased-slot poisoner.

Calibration note: while the `i8` stats are still calibrating (first
`quantize.CALIBRATION_TRANSITIONS` transitions), a queued block may
decode under slightly newer stats than it was encoded with — the same
monotone-widening drift window the replay ring accepts, bounded by the
shallow ring depth; after the freeze, decode is exact-per-encode.

Telemetry: the ring registers a `device_ring` gauge (slots ×
bytes/block × codec mix, enqueue-transfer byte counters, and the
TrajQueue-compatible depth/staleness/drop row) with the resource
sampler; `scripts/run_report.py` renders it in Resources.
"""

# jaxlint: hot-module

from __future__ import annotations

import threading
import time
from collections import deque
from functools import partial
from typing import Any, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from actor_critic_tpu.data_plane import codecs as np_codecs
from actor_critic_tpu.replay import quantize
from actor_critic_tpu.utils import compile_cache as _compile_cache


class RingState(NamedTuple):
    """The device half of the ring: encoded block storage plus the
    cursor/version tree.

    `storage` holds one `[depth, ...block shape]` array per block key at
    the codec storage dtype; `quant` mirrors it with one
    `quantize.QuantStats` per key (live stats for `i8` keys, zero
    placeholders elsewhere — structure is codec-independent, so
    checkpoint templates and warmup eval_shapes never fork on the codec
    mode). `versions[slot]` is the behavior-params version the slot's
    block was collected under, `seqs[slot]` its global put sequence
    (occupancy: a slot is live iff its seq is among the newest), and
    `count` the total puts (saturating) — together the device-side
    source of truth the host bookkeeping mirrors."""

    storage: Any
    quant: Any
    versions: jax.Array  # int32 [depth]
    seqs: jax.Array      # int32 [depth]
    count: jax.Array     # int32 scalar


class RingLease(NamedTuple):
    """One consumed block's handle: the slot index to gather (leased
    until `release`) plus the version/actor bookkeeping the learner's
    log rows use — the `TrajBlock` of the device plane, minus the host
    arrays (the data never leaves HBM)."""

    slot: int
    version: int
    actor_id: int
    seq: int


def canonical_dtype(dtype) -> np.dtype:
    """The dtype a leaf actually stores at on this backend: x64-disabled
    jax truncates int64/float64, and the ring's byte accounting + host
    encode must agree with the device storage (the numpy mirror's
    argmax actions arrive int64 and store int32)."""
    return np.dtype(jax.dtypes.canonicalize_dtype(np.dtype(dtype)))


def init_ring(block_spec: dict, depth: int, codec_kinds: dict) -> RingState:
    """Zeroed ring for `depth` blocks shaped like `block_spec` (a dict
    of name → shape/dtype carriers, e.g. jax.ShapeDtypeStruct)."""
    storage = {
        name: jnp.zeros(
            (depth, *block_spec[name].shape),
            quantize.storage_dtype(
                codec_kinds[name],
                canonical_dtype(block_spec[name].dtype),
            ),
        )
        for name in block_spec
    }
    quant = {
        name: quantize.init_stats(
            codec_kinds[name], _item_struct(block_spec[name])
        )
        for name in block_spec
    }
    return RingState(
        storage=storage,
        quant=quant,
        versions=jnp.full((depth,), -1, jnp.int32),
        seqs=jnp.full((depth,), -1, jnp.int32),
        count=jnp.zeros((), jnp.int32),
    )


def _item_struct(leaf):
    """Stats-shape carrier: ring stats are SCALAR per block key (the
    host mirror folds the whole [K, E, ...] block as one batch —
    `np_init_stats(..., ())` — so the device placeholders must match;
    per-feature stats would demand the host re-derive the replay ring's
    item-axis convention for every block layout for no measured win)."""
    return jax.ShapeDtypeStruct((), jnp.dtype(leaf.dtype))


# One process-wide jit object (populated by the first make_enqueue
# call): the program closes over nothing, so every ring shares the
# dispatch cache — N rings with the same block spec compile ONCE, and
# the warmup planner's AOT lower targets the same object the live
# dispatch traces.
# jaxlint: thread-owned=main (first make_enqueue call happens on the
# constructing thread before any actor exists; later calls only read)
_ENQUEUE_JIT: list = []


def make_enqueue():
    """The donated scatter program: writes one encoded block into its
    slot and advances the cursor/version tree in place. One compiled
    program per (block spec × codec) — every actor of a run shares it.
    `quant` is the host's current stats tree, re-uploaded while
    calibrating and constant after the freeze, so the learner's in-jit
    decode always reads the stats the block was encoded against."""
    if _ENQUEUE_JIT:
        return _ENQUEUE_JIT[0]

    @partial(jax.jit, donate_argnums=0)
    def enqueue(state: RingState, encoded: dict, quant: Any,
                slot, version, seq) -> RingState:
        storage = jax.tree.map(
            lambda s, x: s.at[slot].set(x), state.storage, encoded
        )
        return RingState(
            storage=storage,
            quant=quant,
            versions=state.versions.at[slot].set(version),
            seqs=state.seqs.at[slot].set(seq),
            count=state.count + 1,
        )

    _ENQUEUE_JIT.append(enqueue)
    return enqueue


def gather_block(state: RingState, slot, codec_kinds: dict) -> dict:
    """Slot → decoded float block, INSIDE the caller's jitted program
    (dynamic-slice gather + codec decode; `slot` is a traced scalar).
    This is the zero-transfer consume: the learner's update closes over
    this call and the block never exists on the host."""
    return {
        name: quantize.decode(
            codec_kinds[name], state.quant[name], state.storage[name][slot]
        )
        for name in state.storage
    }


class DeviceTrajRing:
    """Host-side coordinator of the device ring: TrajQueue-compatible
    producer/consumer protocol (`put`/`get`/`release`/
    `set_consumer_version`/`stats`/`close`) over device-resident
    storage. `traj_queue.ActorService` pushes into it unchanged; the
    learner drives its jitted gather+update through `run()`.

    `codec` is a `codecs.traj_codecs` mode string ("fp32"/"f16"/"int8")
    or an explicit per-key kind dict.
    """

    def __init__(
        self,
        depth: int,
        block_spec: dict,
        codec: Any = "fp32",
        max_staleness: Optional[int] = None,
        policy: str = "drop_oldest",
        gauge_name: str = "device_ring",
        register_gauge: bool = True,
    ):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if policy not in ("drop_oldest", "block"):
            raise ValueError(f"unknown policy {policy!r}")
        if max_staleness is not None and max_staleness < 0:
            raise ValueError("max_staleness must be >= 0 or None")
        self.depth = int(depth)
        self.max_staleness = max_staleness
        self.policy = policy
        self._spec = dict(block_spec)
        self.codecs = (
            np_codecs.traj_codecs(codec, block_spec)
            if isinstance(codec, str) else dict(codec)
        )
        self._np_stats = {
            name: np_codecs.np_init_stats(self.codecs[name], ())
            for name in self._spec
        }
        self._stat_keys = [
            n for n, k in self.codecs.items() if k in quantize.STAT_KINDS
        ]
        # Per-key transitions-per-put for the calibration clock (the
        # freeze threshold is defined in TRANSITIONS, not elements):
        # ring blocks are time-major — every [K, E, ...] key carries
        # K·E transitions per put, and the [E, ...] keys (last_obs,
        # bootstrap_value) carry E. The modal leading pair across the
        # spec IS (K, E); keys not sharing it are the [E, ...] family.
        pairs = [
            tuple(leaf.shape[:2]) for leaf in self._spec.values()
            if len(leaf.shape) >= 2
        ]
        modal = max(set(pairs), key=pairs.count) if pairs else None
        self._transitions_per_put = {
            name: int(
                modal[0] * modal[1]
                if modal is not None and tuple(leaf.shape[:2]) == modal
                else (leaf.shape[0] if leaf.shape else 1)
            )
            for name, leaf in self._spec.items()
        }
        self._cv = threading.Condition()
        self._enqueue = make_enqueue()
        self._state = init_ring(block_spec, depth, self.codecs)
        self._quant_dev = self._state.quant
        self._free: list[int] = list(range(depth))
        self._pending: deque[RingLease] = deque()
        self._leased: set[int] = set()
        self._seq = 0
        self._consumer_version = 0
        self._puts = 0
        self._gets = 0
        self._drops_full = 0
        self._drops_stale = 0
        self._last_staleness = 0
        self._max_staleness_seen = 0
        self._idle_s = 0.0
        self._enqueue_bytes = 0
        self._closed = False
        self._gauge_key: Optional[str] = None
        if register_gauge:
            from actor_critic_tpu.telemetry import sampler as _sampler

            self._gauge_key = _sampler.register_gauge(gauge_name, self.stats)

    # -- byte accounting ---------------------------------------------------

    def bytes_per_block(self) -> int:
        """Encoded bytes one enqueue transfers (the codec-compressed
        figure the gauge row and bench records report)."""
        total = 0
        for name, leaf in self._spec.items():
            n = 1
            for d in leaf.shape:
                n *= d
            total += n * np_codecs.storage_np_dtype(
                self.codecs[name], canonical_dtype(leaf.dtype)
            ).itemsize
        return total

    def raw_bytes_per_block(self) -> int:
        """The same block's bytes at its device-canonical dtypes — what
        the host TrajQueue path transfers per consumed block."""
        total = 0
        for leaf in self._spec.values():
            n = 1
            for d in leaf.shape:
                n *= d
            total += n * canonical_dtype(leaf.dtype).itemsize
        return total

    # -- producer ----------------------------------------------------------

    def put(
        self,
        arrays: dict[str, np.ndarray],
        version: int,
        actor_id: int = 0,
        timeout: Optional[float] = None,
    ) -> bool:
        """Encode `arrays` on the host and scatter them into a ring
        slot on device. True once enqueued; False when no slot freed
        within `timeout` (under `policy="block"`, or drop-oldest with
        every slot leased). The caller's arrays are free to reuse
        immediately (encode copies)."""
        with self._cv:
            if self._closed:
                return False
            stats_changed = False
            for name in self._stat_keys:
                if name in arrays:
                    new = np_codecs.np_update_stats(
                        self.codecs[name], self._np_stats[name],
                        arrays[name],
                        num_transitions=self._transitions_per_put[name],
                    )
                    stats_changed |= new is not self._np_stats[name]
                    self._np_stats[name] = new
            stats = dict(self._np_stats)
            if stats_changed:
                # Small item-shaped tree; re-uploaded only while the
                # calibration window is open, constant after the freeze.
                self._quant_dev = {
                    name: quantize.QuantStats(
                        mean=jnp.asarray(st["mean"]),
                        scale=jnp.asarray(st["scale"]),
                        count=jnp.asarray(st["count"]),
                    )
                    for name, st in stats.items()
                }
        # Encode + transfer OUTSIDE the lock: numpy quantization and the
        # device put are the slow half and must not stall the learner's
        # dispatch seam. The stats snapshot above is immutable
        # (np_update_stats returns fresh arrays), so encoding against it
        # is race-free even while another actor keeps calibrating.
        encoded = {
            # astype to the device-canonical storage dtype BEFORE the
            # put: an int64 mirror action would otherwise ship 8 bytes
            # per element for jax to truncate to 4 on arrival.
            name: np_codecs.np_encode(
                self.codecs[name], stats[name], arrays[name]
            ).astype(
                np_codecs.storage_np_dtype(
                    self.codecs[name], canonical_dtype(self._spec[name].dtype)
                ),
                copy=False,
            )
            for name in self._spec
        }
        encoded_dev = jax.device_put(encoded)
        nbytes = sum(v.nbytes for v in encoded.values())
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while True:
                if self._closed:
                    return False
                slot = self._claim_slot_locked()
                if slot is not None:
                    break
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    return False
                self._cv.wait(0.1 if remaining is None else min(0.1, remaining))
            seq = self._seq
            self._seq += 1
            # Donating dispatch under the lock: the learner's gather for
            # any other slot is either already dispatched (device order
            # reads it first) or will dispatch against the NEW state.
            # quant is read HERE, not from a pre-encode snapshot: two
            # actors racing through the unlocked encode window could
            # otherwise upload an OLDER stats tree after a newer one,
            # regressing state.quant below what a pending block was
            # encoded with — the current _quant_dev is always the
            # newest (monotone by construction), so any pending block
            # decodes under equal-or-wider stats, the documented drift
            # bound.
            self._state = self._enqueue(
                self._state, encoded_dev, self._quant_dev,
                np.int32(slot), np.int32(version), np.int32(seq),
            )
            self._pending.append(
                RingLease(int(slot), int(version), int(actor_id), seq)
            )
            self._puts += 1
            self._enqueue_bytes += nbytes
            self._cv.notify_all()
            return True

    def _claim_slot_locked(self) -> Optional[int]:
        """A writable slot, or None when the caller must wait: free
        slots first; under drop-oldest a full ring reclaims its oldest
        PENDING block (leased slots are never overwritten — the learner
        may still be reading them); under `policy="block"` a full ring
        always waits."""
        if self.policy == "block":
            if self._in_flight() < self.depth and self._free:
                return self._free.pop()
            return None
        if self._free:
            return self._free.pop()
        if self._pending:
            old = self._pending.popleft()
            self._drops_full += 1
            return old.slot
        return None  # every slot leased: wait for a release

    def _in_flight(self) -> int:
        return len(self._pending) + len(self._leased)

    # -- consumer ----------------------------------------------------------

    def set_consumer_version(self, version: int) -> None:
        with self._cv:
            self._consumer_version = int(version)

    def get(self, timeout: Optional[float] = None) -> Optional[RingLease]:
        """Oldest fresh-enough block's lease (slot stays unwritable
        until `release`), or None after `timeout`. Same staleness-drop
        semantics as TrajQueue.get."""
        deadline = None if timeout is None else time.monotonic() + timeout
        t0 = time.monotonic()
        with self._cv:
            try:
                while True:
                    while self._pending:
                        lease = self._pending.popleft()
                        lag = self._consumer_version - lease.version
                        if (
                            self.max_staleness is not None
                            and lag > self.max_staleness
                        ):
                            self._free.append(lease.slot)
                            self._drops_stale += 1
                            self._cv.notify_all()
                            continue
                        self._leased.add(lease.slot)
                        self._gets += 1
                        self._last_staleness = max(lag, 0)
                        self._max_staleness_seen = max(
                            self._max_staleness_seen, self._last_staleness
                        )
                        return lease
                    remaining = (
                        None if deadline is None
                        else deadline - time.monotonic()
                    )
                    if remaining is not None and remaining <= 0:
                        return None
                    self._cv.wait(
                        0.1 if remaining is None else min(0.1, remaining)
                    )
            finally:
                self._idle_s += time.monotonic() - t0

    def release(self, lease: RingLease) -> None:
        """Return a leased slot to the writable pool (call after the
        LAST update dispatch against it — dispatch order then guarantees
        any later overwrite executes after the reads)."""
        with self._cv:
            self._leased.discard(lease.slot)
            self._free.append(lease.slot)
            self._cv.notify_all()

    def run(self, fn, *args, **kwargs):
        """Dispatch a learner program against the CURRENT ring state:
        `fn(state, *args, **kwargs)` under the ring lock, so no enqueue
        can donate the state handle between fetch and dispatch. The jit
        call inside `fn` returns at enqueue time (async dispatch), so
        the lock is held for dispatch only, never device execution."""
        with self._cv:
            return fn(self._state, *args, **kwargs)

    # -- checkpoint (strip/resume: stats survive, storage never saved) -----

    def quant_host(self) -> dict:
        """The host-side quantizer stats as a plain numpy tree — the
        ONLY part of the ring a checkpoint carries (the PR 8
        `strip_replay` contract, taken to its limit: trajectory blocks
        are transient collection data, so the 'stub' is no storage at
        all, just the calibrate-then-freeze stats a resumed run must
        re-encode against)."""
        with self._cv:
            return {
                name: {k: np.asarray(v) for k, v in st.items()}
                for name, st in self._np_stats.items()
            }

    def install_quant(self, tree: dict) -> None:
        """Adopt restored stats (resume-reattach: fresh storage, the
        run's original standardization)."""
        with self._cv:
            self._np_stats = {
                name: {
                    "mean": np.asarray(st["mean"], np.float32),
                    "scale": np.asarray(st["scale"], np.float32),
                    "count": np.asarray(st["count"], np.int32),
                }
                for name, st in tree.items()
            }
            self._quant_dev = {
                name: quantize.QuantStats(
                    mean=jnp.asarray(st["mean"]),
                    scale=jnp.asarray(st["scale"]),
                    count=jnp.asarray(st["count"]),
                )
                for name, st in self._np_stats.items()
            }
            self._state = self._state._replace(quant=self._quant_dev)

    # -- introspection -----------------------------------------------------

    def __len__(self) -> int:
        with self._cv:
            return len(self._pending)

    def codec_mix(self) -> str:
        return ",".join(f"{n}:{self.codecs[n]}" for n in sorted(self.codecs))

    def stats(self) -> dict:
        """Gauge row: the TrajQueue-compatible depth/staleness/drop
        fields plus the device-ring byte accounting (slots ×
        bytes/block × codec mix; enqueue transfer total; the learner's
        per-consume transfer is structurally zero — only the slot index
        rides the dispatch)."""
        with self._cv:
            return {
                "capacity": self.depth,
                "depth": len(self._pending),
                "leased": len(self._leased),
                "puts": self._puts,
                "gets": self._gets,
                "drops_full": self._drops_full,
                "drops_stale": self._drops_stale,
                "observe_staleness": self._last_staleness,
                "staleness_max": self._max_staleness_seen,
                "learner_idle_s": round(self._idle_s, 3),
                "slots": self.depth,
                "bytes_per_block": self.bytes_per_block(),
                "raw_bytes_per_block": self.raw_bytes_per_block(),
                "enqueue_bytes": self._enqueue_bytes,
                "consume_transfer_bytes": 0,
                "codec_mix": self.codec_mix(),
            }

    def close(self) -> None:
        with self._cv:
            if self._closed:
                return
            self._closed = True
            gauge_key, self._gauge_key = self._gauge_key, None
            self._cv.notify_all()
        if gauge_key is not None:
            from actor_critic_tpu.telemetry import sampler as _sampler

            _sampler.unregister_gauge(gauge_key)


# -- AOT warmup (utils/compile_cache.py registry; ISSUE 13) -----------------

def ctx_block_spec(ctx) -> dict:
    """The block spec a WarmupContext's run will push through the ring
    (shared by this module's enqueue planner and the per-algo update
    planners, so their signatures can never drift apart)."""
    if ctx.algo == "ppo":
        from actor_critic_tpu.algos import ppo

        return ppo.async_block_spec(
            ctx.spec, ctx.cfg, ctx.async_actors, ctx.async_correction
        )
    from actor_critic_tpu.data_plane import device_replay

    return device_replay.offpolicy_block_spec(
        ctx.spec, ctx.cfg, ctx.async_actors
    )


def abstract_ring_state(block_spec: dict, depth: int, kinds: dict):
    """Shape/dtype tree of the ring state via eval_shape (no device
    allocation — a deep pixel ring would otherwise materialize)."""
    return jax.eval_shape(partial(init_ring, block_spec, depth, kinds))


@_compile_cache.register_warmup("ring.make_enqueue")
def _warmup_enqueue(ctx):
    if (
        ctx.data_plane != "device"
        or not ctx.async_actors
        or ctx.fused
        or ctx.algo not in ("ppo", "ddpg", "td3", "sac")
    ):
        return None
    block_spec = ctx_block_spec(ctx)
    kinds = np_codecs.traj_codecs(ctx.plane_codec, block_spec)
    state_abs = abstract_ring_state(block_spec, ctx.queue_depth, kinds)
    encoded = {
        name: _compile_cache.array_struct(
            leaf.shape,
            np_codecs.storage_np_dtype(kinds[name], leaf.dtype),
        )
        for name, leaf in block_spec.items()
    }
    quant_abs = state_abs.quant
    s = _compile_cache.scalar_struct
    jitted = make_enqueue()
    return lambda: _compile_cache.aot_compile(
        jitted, state_abs, encoded, quant_abs,
        s(np.int32), s(np.int32), s(np.int32),
    )
