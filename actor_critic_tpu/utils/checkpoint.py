"""Checkpoint / resume via orbax (SURVEY.md §5.3-5.4).

The reference genre saves with `tf.train.Saver` periodically and dies on
failure (reference mount empty at survey, SURVEY.md §0); the TPU build's
recovery story is checkpoint-restart: every K iterations the FULL
trainer state pytree — params, optimizer state, env/rollout state, PRNG
keys, step counters, normalizer stats — is saved asynchronously, and
`resume_or_init` restores the exact state so a restarted run is
bitwise-identical to an uninterrupted one (the trainers are pure
functions of their state; tested in tests/test_checkpoint.py).

JAX typed PRNG keys are packed to their raw uint32 `key_data` on save
and re-wrapped on restore (orbax stores plain arrays), keyed off the
template state's leaf types, so any trainer state NamedTuple works
unmodified.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import orbax.checkpoint as ocp

from actor_critic_tpu.utils import numguard


def _is_typed_key(x) -> bool:
    return hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jax.dtypes.prng_key)


def pack_keys(state: Any) -> Any:
    """Replace typed PRNG key leaves with their raw uint32 key data."""
    return jax.tree.map(
        lambda x: jax.random.key_data(x) if _is_typed_key(x) else x, state
    )


@jax.jit
def _owned_copy(tree: Any) -> Any:
    """On-device clone: outputs are fresh jax-owned buffers (and, with
    uncommitted inputs, uncommitted)."""
    return jax.tree.map(jnp.copy, tree)


def uncommit(state: Any) -> Any:
    """Normalize a just-restored state for the compile-once contract
    (ISSUE 4): every leaf becomes an UNCOMMITTED, JAX-OWNED
    default-device array. Two distinct failure modes force this:

    - COMMITMENT: orbax restores committed arrays (explicit sharding),
      and jit bakes committed-arg shardings into the lowered module —
      a resumed process would lower byte-different HLO from a fresh one
      and MISS every persistent-cache entry the fresh leg or the AOT
      warmup wrote (verified: the restored-state module gains per-arg
      `mhlo.sharding` attributes). The host round-trip below restores
      the fresh leg's cache keys.
    - OWNERSHIP: device_put of host memory can alias it zero-copy, and
      DONATING such a buffer into a DESERIALIZED cached executable
      corrupts the glibc heap in this container ("corrupted
      double-linked list" → SIGSEGV one dispatch later; reproduced 6/6
      with restored states under a warm cache, clean 6/6 with fresh
      states or cold compiles). The `_owned_copy` clone reads the
      maybe-aliased buffers WITHOUT donation and emits buffers XLA
      allocated itself, which every downstream donating dispatch can
      safely consume.

    One host round-trip plus one on-device copy per restore buys the
    resumed leg a near-compile-free, crash-free start.

    Mesh-SHARDED states pass through untouched: the host round-trip
    would collapse their shards onto one device, and the dp/seqpar
    drivers that restore them manage placement explicitly (they sit
    outside train.py's compile-cache scope)."""
    for leaf in jax.tree.leaves(state):
        try:
            multi = len(leaf.sharding.device_set) > 1
        except AttributeError:
            multi = False
        if multi:
            return state
    placed = jax.tree.map(
        lambda x: (
            jax.device_put(jax.device_get(x))
            if isinstance(x, jax.Array)
            else x
        ),
        state,
    )
    return _owned_copy(placed)


def unpack_keys(restored: Any, template: Any) -> Any:
    """Re-wrap raw key data wherever `template` holds a typed key."""
    return jax.tree.map(
        lambda t, r: (
            jax.random.wrap_key_data(r, impl=jax.random.key_impl(t))
            if _is_typed_key(t)
            else r
        ),
        template,
        restored,
    )


class Checkpointer:
    """Thin wrapper over `ocp.CheckpointManager` for trainer states.

    Saves are async (the train loop keeps running while the write
    completes); `wait()` blocks, and `close()` waits + releases.
    Restored states are ownership/commitment-normalized (`uncommit`) so
    resumed processes share the fresh process's compilation-cache keys
    and never donate externally-aliased buffers into deserialized
    executables.
    """

    def __init__(
        self,
        directory: str | os.PathLike,
        max_to_keep: int = 3,
        save_interval_steps: int = 1,
    ):
        self._mgr = ocp.CheckpointManager(
            os.path.abspath(os.fspath(directory)),
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps,
            ),
        )

    def save(
        self,
        step: int,
        state: Any,
        metrics: Optional[dict] = None,
        force: bool = False,
    ) -> bool:
        """Persist `state` (and optionally the latest scalar `metrics`)
        under `step`. Returns True if a save happened (the manager skips
        steps closer than `save_interval_steps`).

        Metrics ride along as a JSON item so a resume that finds nothing
        left to run can still report the run's final metrics instead of
        an empty dict (see `checkpointed_train`).

        Non-finite STATE refuses to commit (`NonFiniteError`, ISSUE 14):
        a NaN-poisoned params tree written to disk is inherited by every
        future resume — the previous good checkpoint must stay the
        latest instead. The gate sweeps packed (plain-array) leaves, so
        typed PRNG keys cost nothing; metrics may legitimately carry a
        non-finite loss (that IS the forensic record of a divergence)
        and are never refused.
        """
        packed = pack_keys(state)
        numguard.check_finite(packed, "checkpoint commit", name="state")
        m = {k: float(v) for k, v in (metrics or {}).items()}
        # The item is named `run_metrics` because newer orbax reserves
        # the bare name `metrics` for its own best-checkpoint tracking
        # and refuses Composite items using it.
        return self._mgr.save(
            step,
            args=ocp.args.Composite(
                state=ocp.args.StandardSave(packed),
                run_metrics=ocp.args.JsonSave(m),
            ),
            force=force,
        )

    def restore(self, template: Any, step: Optional[int] = None) -> Any:
        """Restore the checkpoint at `step` (default: latest) into the
        structure/shardings of `template` (a concrete or abstract state).

        The returned leaves are normalized by `uncommit` — uncommitted,
        XLA-owned default-device arrays — so a resumed process lowers
        the same HLO (and hits the same persistent-compilation-cache
        entries) as a fresh one, and downstream donating dispatches
        never free buffers orbax/numpy still own."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError("no checkpoint to restore")
        packed = pack_keys(template)
        abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, packed)
        try:
            restored = self._mgr.restore(
                step, args=ocp.args.Composite(state=ocp.args.StandardRestore(abstract))
            )["state"]
        except ValueError as e:
            # Legacy layout ONLY: a bare StandardSave with no named items
            # (written before metrics rode along) makes orbax refuse
            # Composite args with its "unnamed checkpointable" signature.
            # Any other ValueError (e.g. template shape/dtype mismatch) is
            # a genuine failure and must surface as itself, not as a
            # confusing secondary error from the bare-form retry.
            msg = str(e)
            if not ("unnamed" in msg or "Composite" in msg):
                raise
            restored = self._mgr.restore(
                step, args=ocp.args.StandardRestore(abstract)
            )
        # Normalized BEFORE key re-wrap (plain uint32 leaves throughout),
        # so typed keys come out of wrap_key_data uncommitted like a
        # fresh process's. Only while the persistent compile cache is
        # live: both failure modes uncommit guards against need a warm
        # cache (key mismatch / deserialized-executable donation), and
        # the normalization's transient 2x device materialization must
        # not be charged to cache-less restores of replay-ring-sized
        # states. (train.py enables the cache before any Checkpointer
        # exists, so the ordering holds.)
        from actor_critic_tpu.utils import compile_cache

        if compile_cache.enabled_dir() is not None:
            restored = uncommit(restored)
        return unpack_keys(restored, template)

    def restore_metrics(self, step: Optional[int] = None) -> dict:
        """The scalar metrics saved alongside the checkpoint at `step`
        (default: latest); {} if none were recorded."""
        if step is None:
            step = self.latest_step()
            if step is None:
                return {}
        for item in ("run_metrics", "metrics"):  # current name, then legacy
            try:
                out = self._mgr.restore(
                    step,
                    args=ocp.args.Composite(**{item: ocp.args.JsonRestore()}),
                )[item]
                return dict(out or {})
            except (FileNotFoundError, KeyError, ValueError) as e:
                import json

                if isinstance(e, json.JSONDecodeError):
                    # A truncated/corrupt metrics item is NOT "no
                    # metrics" — surface it.
                    raise
                # Legitimately absent under this name: fall through to
                # the legacy spelling (checkpoints written before the
                # orbax reserved-name rename), then to {} (legacy bare-
                # StandardSave layouts raise ValueError on Composite
                # args).
        return {}

    @property
    def directory(self) -> str:
        """The checkpoint root — sidecar files (e.g. the learned chunk
        wall) live next to the step directories."""
        return str(self._mgr.directory)

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self) -> list[int]:
        return list(self._mgr.all_steps())

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _read_chunk_wall(path: str) -> Optional[float]:
    """The persisted steady-state chunk wall seconds, or None (absent /
    unreadable / non-positive — all mean "nothing learned yet")."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    # Valid-but-foreign JSON (a bare number, a list) must read as
    # "nothing learned", not crash — this sidecar is advisory.
    wall = data.get("chunk_wall_s") if isinstance(data, dict) else None
    if isinstance(wall, (int, float)) and not isinstance(wall, bool):
        return float(wall) if wall > 0 else None
    return None


def _persist_chunk_wall(path: str, wall_s: float) -> None:
    """Record the largest steady-state (post-compile) chunk wall observed
    so a RESUMED process can widen its armed watchdog before its own
    chunk 1 — whose wall is compile-inflated and deliberately never
    ratcheted from."""
    prev = _read_chunk_wall(path)
    if prev is not None and prev >= wall_s:
        return
    try:
        with open(path, "w") as f:
            json.dump({"chunk_wall_s": round(float(wall_s), 3)}, f)
    except OSError:
        pass  # advisory sidecar; never take the run down


def _compile_probe() -> Optional[Callable[[], int]]:
    """A monotonically-increasing compile-event counter from the
    telemetry compile listener, or None when the listener isn't
    installed. The chunk-wall ratchet samples it around each dispatch to
    MEASURE whether the dispatch paid XLA compile, instead of guessing
    from 'first dispatch at this k' (tests monkeypatch this seam to pin
    either path)."""
    try:
        from actor_critic_tpu.telemetry import profiler
    except Exception:  # pragma: no cover - telemetry always importable
        return None
    if not profiler.introspection_active():
        return None
    return profiler.compile_event_count


def resume_or_init(ckpt: Checkpointer, init_state: Any) -> tuple[Any, int]:
    """(state, completed_iterations): the latest checkpoint if one exists,
    else the freshly-initialized state at iteration 0."""
    step = ckpt.latest_step()
    if step is None:
        return init_state, 0
    return ckpt.restore(init_state, step), step


# How long `checkpointed_train` lets other threads run between the last row
# and the tear-down: a few polls of a 2 ms watcher, once a run.
LAST_ROW_YIELD_S = 0.01


def checkpointed_train(
    step_fn: Callable[..., tuple[Any, dict]],
    init_state: Any,
    num_iterations: int,
    ckpt: Optional[Checkpointer] = None,
    save_every: int = 0,
    log_fn: Optional[Callable[[int, dict], None]] = None,
    resume: bool = True,
    stride: int = 1,
    log_due: Optional[Callable[[int], bool]] = None,
) -> tuple[Any, dict]:
    """Restart-idempotent train loop (SURVEY.md §5.3).

    Resumes from the latest checkpoint (if any, and `resume`), runs the
    remaining iterations with `step_fn` — a jitted `(state) → (state,
    metrics)` when `stride == 1`, or `(state, k)` advancing k iterations
    per dispatch when `stride > 1` — saving on the `save_every` cadence
    (plus once at the end; `save_every<=0` means end-only) and calling
    `log_fn(it, metrics)` after each DISPATCH: that is every iteration
    at `stride == 1` but only once per chunk at `stride > 1`, with `it`
    jumping by the chunk size. Re-running after a mid-loop kill produces
    the same final state as an uninterrupted run, because the state
    pytree carries everything. With `ckpt=None` it is a plain train
    loop — the single implementation every caller shares.

    `stride > 1` is the chunked-dispatch mode: `step_fn` must then take
    `(state, k)` and advance k iterations in ONE device dispatch
    (a `lax.scan` over the per-iteration step). The counter advances by
    `min(stride, remaining)` per call, so arbitrary `num_iterations`
    and resume points work (the short tail chunk costs one extra
    compile). Save/log callbacks fire only at chunk boundaries — the
    caller is responsible for choosing cadences that are multiples of
    `stride` (train.py snaps them up and says so).

    `log_due(it)` says whether `log_fn` will materialize this dispatch's
    metrics (the loop's one device sync). Where it does AND a telemetry
    session is installed, the loop takes that sync itself under
    `device_wait` spans (the dispatch before, then this one), so that
    `log` starts when the device has finished and holds the host's own
    work only: a span that began before a profiler capture, or ends
    after it, is not in the capture, and the idle gap after a log row
    would have no `ac:` annotation to carry it. Without a session (or
    without `log_due`) the wait stays inside `log_fn`'s first `float()`,
    which enqueues its transfer behind the running step: waiting first
    costs a row 0.3-0.5 ms of device idle (PERF.md, PR 26), so an
    untraced run does not pay it.
    """
    if ckpt is not None and resume:
        state, done = resume_or_init(ckpt, init_state)
    else:
        state, done = init_state, 0
    # A resume that finds the run already complete would otherwise return
    # {} and the caller's summary would silently lose all metrics. (Only
    # hit that case — a mid-run resume overwrites metrics on step one.)
    metrics: dict = (
        ckpt.restore_metrics(done)
        if (ckpt is not None and done and done >= num_iterations)
        else {}
    )
    from actor_critic_tpu import telemetry
    from actor_critic_tpu.utils import watchdog
    from actor_critic_tpu.utils.cadence import should_save

    chunk_wall_path = None
    if stride > 1 and ckpt is not None:
        chunk_wall_path = os.path.join(ckpt.directory, "chunk_wall.json")
        learned = _read_chunk_wall(chunk_wall_path)
        if learned is not None:
            # A resumed process recompiles from scratch and its first
            # dispatch is skipped by the ratchet below, so without this
            # the run would enter chunk 2 still on the CLI timeout even
            # when a previous leg proved chunks legitimately run longer.
            watchdog.ensure_timeout_at_least(3.0 * learned)

    it = done
    previous = None  # the dispatch before this one's metrics (device_wait)
    timed_k = None  # heuristic fallback: stride of the last compile-paid dispatch
    while it < num_iterations:
        # First chunk after a misaligned resume realigns to stride
        # boundaries (resume at it=1000, stride=64 → k=24 then 64s), so
        # the snapped log/eval/save cadences — which fire only when
        # `it % cadence == 0` — keep firing for the rest of the run.
        k = stride - it % stride if it % stride else stride
        k = min(k, num_iterations - it)
        watchdog.beat()  # progress heartbeat (utils/watchdog.py)
        # Dispatch boundary for any armed on-demand profile window
        # (telemetry/profiler.py; one "iter" here = one chunk at
        # stride > 1 — the capturable unit of fused work).
        telemetry.profiler_tick()
        compile_count = _compile_probe() if stride > 1 else None
        compiles_before = compile_count() if compile_count else 0
        t_dispatch = time.monotonic()
        # The span measures enqueue-to-return, not device wall: a jitted
        # call returns at dispatch, and fencing here would break the
        # async pipelining (the first sync lands in the log span).
        with telemetry.span("update", it=it + k, dispatch="async"):
            state, metrics = (
                step_fn(state, k) if stride > 1 else step_fn(state)
            )
        if stride > 1 and watchdog.armed():
            # A chunk that legitimately outlasts --stall-timeout must not
            # be misread as a stall on the NEXT chunk (one beat per chunk;
            # the kill/resume loop that never clears a chunk is ADVICE.md
            # round-4 #2). A jitted call returns at ENQUEUE time, so the
            # true chunk wall is only observable behind a block — block on
            # the (scalar) metrics, which complete with the chunk program;
            # only done while a watchdog is armed, so the unwatched path
            # keeps its async pipelining. A completed chunk is proof of
            # the real wall time — raise any armed watchdog to 3x that,
            # with headroom for cache misses on tail chunks.
            jax.block_until_ready(metrics)
            chunk_wall = time.monotonic() - t_dispatch
            if compile_count is not None:
                # MEASURED compile attribution (ISSUE 4): the telemetry
                # compile listener saw XLA compile during this dispatch.
                # (A persistent-cache hit also funnels through — its
                # near-zero wall makes the conservative grace extension
                # harmless.)
                paid_compile = compile_count() > compiles_before
            else:
                # Fallback heuristic (telemetry off): a dispatch with a
                # k this process hasn't timed yet paid compile — the
                # process's first chunk, the realignment chunk, the
                # short tail (~60s observed here).
                paid_compile = k != timed_k
                timed_k = k
            if paid_compile:
                # Ratcheting or persisting a compile-carrying wall would
                # bake compile time into 3x the stall timeout
                # permanently, weakening wedge detection for the rest of
                # the run and (via the sidecar) every future leg. Shield
                # the NEXT chunk with a temporary grace extension
                # instead; the first clean dispatch supplies the wall.
                watchdog.extend_grace(3.0 * chunk_wall)
            else:
                watchdog.ensure_timeout_at_least(3.0 * chunk_wall)
                if chunk_wall_path is not None:
                    _persist_chunk_wall(chunk_wall_path, chunk_wall)
        it += k
        if should_save(it, save_every, num_iterations):
            # The span is emitted even with ckpt=None (args record
            # whether a save actually ran): the checkpoint phase
            # boundary exists in every trace, so run reports can compare
            # checkpointed and checkpoint-free runs phase-for-phase.
            with telemetry.span("checkpoint", step=it, saved=ckpt is not None):
                if ckpt is not None:
                    # Sync before handing buffers to the async saver:
                    # donation would otherwise let the next step
                    # overwrite in-flight reads.
                    jax.block_until_ready(state)
                    ckpt.save(it, state, metrics=metrics, force=True)
        if log_fn is not None:
            if (
                log_due is not None
                and telemetry.current() is not None
                and log_due(it)
            ):
                # In two steps, so that the wait that ends with the device
                # is at most one dispatch long: it then lies inside a
                # capture that the whole wait since the last row would
                # cross the edge of.
                if previous is not None:
                    with telemetry.span("device_wait", it=it - k):
                        jax.block_until_ready(previous)
                with telemetry.span("device_wait", it=it):
                    jax.block_until_ready(metrics)
            with telemetry.span("log", it=it):
                log_fn(it, metrics)
        previous = metrics
    # What follows the last row is tear-down: as the callers' frames go, the
    # step's state and its compiled programs are destroyed, and that holds
    # the interpreter (0.15-0.3 s at a 340 MB step program on a v5e: PERF.md,
    # Findings, PR 34). Whoever follows the rows from a thread of this
    # process (the telemetry sampler, a serving sidecar, the benchmark's row
    # watcher, which polls every 2 ms) is let in first, so that it sees the
    # last row when it appears and not when the tear-down ends.
    time.sleep(LAST_ROW_YIELD_S)
    if ckpt is not None:
        ckpt.wait()
    return state, metrics
