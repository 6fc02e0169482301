"""Sharded multi-process host env pool (ISSUE 2 tentpole).

`HostEnvPool`'s gym backend steps E envs serially inside one
SyncVectorEnv, so a single slow simulator step stalls the whole batch
and a pool step costs E × per-env wall time (the host bound of SURVEY
§7.0/§7.2). `ShardedVecEnv` shards the E envs across W worker
processes — the GA3C / Accelerated-Methods batched-simulation design
(PAPERS.md 1611.06256, 1803.02811) — each worker holding its own
`gym.make` stack inside a per-shard SyncVectorEnv with SAME_STEP
autoreset, so step/reset/final_obs semantics are exactly the
single-process pool's. Per-step data moves through preallocated
shared-memory blocks:

    parent:   actions → shm, broadcast "step"        (one send per worker)
    worker w: SyncVectorEnv.step(act[lo:hi]) → obs / reward / terminated /
              truncated / final_obs slices written into shm[lo:hi]
    parent:   barrier (one ack per worker) → batched step output

One broadcast + one barrier per batch step; observations never pass
through pickle. Seeding is per-shard deterministic over GLOBAL env
indices: worker w seeds its SyncVectorEnv with [seed+lo .. seed+hi-1],
exactly the list one big SyncVectorEnv.reset(seed) derives, so a
sharded pool reproduces the single-process pool's trajectories
bit-for-bit at fixed seeds (tests/test_shard_pool.py).

Workers are SPAWNED, not forked: the parent has jax initialized (and on
a TPU host holds the chip), and forking a process with live XLA threads
can wedge the child. A chip belongs to one process, so the parent exports
JAX_PLATFORMS=cpu around the spawns: a worker that imports jax (gymnasium
wrappers can) stays on the CPU and never contends for the parent's
device.

Spawn's standard caveat applies: the constructing script must be
import-safe (pool construction behind `if __name__ == "__main__"` or
inside a function) — train.py and pytest both are.

Failure contract: a worker crash (env exception or process death)
surfaces as a RuntimeError from the pending barrier — never a hang.
Telemetry: workers buffer one span record per batch step (a bounded
deque), relayed to the parent once per collection block
(`drain_telemetry`, called by host_collect) and merged into
spans.jsonl under each worker's REAL pid — one Perfetto lane per
worker process; per-worker busy seconds also accumulate in a shared
stats block feeding `worker_stats()` and the pool-utilization gauge
registered with the resource sampler (telemetry/sampler.py
`register_gauge`).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import time
from typing import Any, Optional

import numpy as np


def make_host_env(env_id: str, env_kwargs: dict, pixel_preprocess: bool):
    """One gym env exactly as HostEnvPool's gym backend builds it (shared
    by the in-process SyncVectorEnv, the sharded workers, and the parent's
    space probe, so all three see identical spaces/wrappers)."""
    import gymnasium as gym

    e = gym.make(env_id, **env_kwargs)
    if pixel_preprocess:
        from actor_critic_tpu.envs.pixel_wrappers import PixelPreprocess

        e = PixelPreprocess(e)
    return e


def shard_bounds(num_envs: int, workers: int) -> list[tuple[int, int]]:
    """[lo, hi) global env-index range per worker; remainders go to the
    first shards so sizes differ by at most one."""
    base, extra = divmod(num_envs, workers)
    bounds, lo = [], 0
    for w in range(workers):
        hi = lo + base + (1 if w < extra else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def _shared_raw(ctx, dtype: np.dtype, shape: tuple[int, ...]):
    """Anonymous shared-memory block sized for (dtype, shape). RawArray
    (not named shared_memory): inheritable through Process args under
    spawn with no name-registry cleanup to leak."""
    n = max(int(np.prod(shape)), 1) * np.dtype(dtype).itemsize
    return ctx.RawArray("b", n)


def _np_view(raw, dtype: np.dtype, shape: tuple[int, ...]) -> np.ndarray:
    return np.frombuffer(raw, dtype=dtype).reshape(shape)


# Per-worker telemetry ring: one (epoch_start, dur_s) record per batch
# step, buffered locally and shipped to the parent on "drain" (once per
# collection block while a session is installed). Bounded so a run
# WITHOUT telemetry — which never drains — holds at most this many
# records per worker, the oldest rolling off.
_TELEMETRY_RING = 4096

# Phase name of the relayed records (the *_PHASE suffix keeps it visible
# to tests/test_span_names.py's canonical-vocabulary scan).
_WORKER_PHASE = "env_step_worker"


def _worker_main(
    conn, wid, env_id, env_kwargs, pixel_preprocess, lo, hi, raw, specs
):
    """Worker loop: own gym stack, commands in, shm slices out. Any
    exception is sent back as ("error", traceback) — the parent raises it
    at the barrier, so a crash is an error, not a hang."""
    import traceback
    from collections import deque

    try:
        from gymnasium.vector import AutoresetMode, SyncVectorEnv

        views = {k: _np_view(raw[k], *specs[k]) for k in raw}
        n = hi - lo
        envs = SyncVectorEnv(
            [
                (lambda: make_host_env(env_id, env_kwargs, pixel_preprocess))
                for _ in range(n)
            ],
            autoreset_mode=AutoresetMode.SAME_STEP,
        )
        stats = views["stats"]
        tel: deque = deque(maxlen=_TELEMETRY_RING)
        tel_dropped = 0
        while True:
            cmd, payload = conn.recv()
            if cmd == "reset":
                obs, _ = envs.reset(seed=payload)
                views["obs"][lo:hi] = obs
                conn.send(("ok", None))
            elif cmd == "drain":
                # Ship the buffered span records (wall-clock epoch start
                # + duration; time.time() is shared across processes on
                # one host, so the parent can place them on its tracer's
                # ts axis) and start a fresh buffer.
                conn.send(
                    ("ok", {"records": list(tel), "dropped": tel_dropped})
                )
                tel.clear()
                tel_dropped = 0
            elif cmd == "step":
                t_epoch = time.time()
                t0 = time.perf_counter()
                obs, rew, term, trunc, info = envs.step(
                    np.array(views["act"][lo:hi])
                )
                views["obs"][lo:hi] = obs
                views["reward"][lo:hi] = rew
                views["terminated"][lo:hi] = term
                views["truncated"][lo:hi] = trunc
                # Full numeric final_obs slice (pre-reset rows where done,
                # == obs elsewhere) — same contract as the native engine,
                # so the parent never unpacks gymnasium's object array.
                final = views["final_obs"]
                final[lo:hi] = obs
                fos = info.get("final_obs")
                if fos is not None:
                    for j, fo in enumerate(fos):
                        if fo is not None:
                            final[lo + j] = fo
                dt = time.perf_counter() - t0
                stats[wid, 0] += dt       # cumulative busy seconds
                stats[wid, 1] += n        # cumulative env steps
                stats[wid, 2] = dt        # last batch-step wall
                if len(tel) == tel.maxlen:
                    tel_dropped += 1
                tel.append((t_epoch, dt))
                conn.send(("ok", None))
            elif cmd == "close":
                envs.close()
                conn.send(("ok", None))
                return
    except (EOFError, KeyboardInterrupt):
        return  # parent went away; daemon worker just exits
    except Exception:
        try:
            conn.send(("error", traceback.format_exc()))
        except Exception:
            pass


class ShardedVecEnv:
    """E gym envs sharded over W spawned workers behind the SyncVectorEnv
    surface HostEnvPool consumes (`single_*_space`, `reset(seed=...)`,
    `step(actions) -> (obs, reward, term, trunc, info)`, `close()`).

    `info["final_obs"]` is a full [E, ...] numeric array in the env's
    native obs dtype (the native-engine convention), already correct for
    non-done rows.
    """

    def __init__(
        self,
        env_id: str,
        num_envs: int,
        workers: int,
        env_kwargs: Optional[dict] = None,
        pixel_preprocess: bool = False,
        step_timeout_s: float = 300.0,
    ):
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        if workers > num_envs:
            raise ValueError(
                f"workers={workers} exceeds num_envs={num_envs}; an empty "
                "shard would idle a whole process"
            )
        self.num_envs = E = int(num_envs)
        self.num_workers = W = int(workers)
        env_kwargs = dict(env_kwargs or {})
        self._step_timeout_s = float(step_timeout_s)

        # Probe one env in-process for the spaces (wrappers included).
        probe = make_host_env(env_id, env_kwargs, pixel_preprocess)
        self.single_observation_space = probe.observation_space
        self.single_action_space = probe.action_space
        probe.close()
        obs_space = self.single_observation_space
        obs_dtype = np.dtype(obs_space.dtype)
        if hasattr(self.single_action_space, "n"):
            act_spec = (np.dtype(np.int64), (E,))
        else:
            # HostEnvPool delivers clipped/scaled float32 Box actions.
            act_spec = (np.dtype(np.float32), (E, *self.single_action_space.shape))
        specs: dict[str, tuple[np.dtype, tuple[int, ...]]] = {
            "act": act_spec,
            "obs": (obs_dtype, (E, *obs_space.shape)),
            "final_obs": (obs_dtype, (E, *obs_space.shape)),
            "reward": (np.dtype(np.float64), (E,)),
            "terminated": (np.dtype(np.bool_), (E,)),
            "truncated": (np.dtype(np.bool_), (E,)),
            "stats": (np.dtype(np.float64), (W, 3)),
        }
        ctx = mp.get_context("spawn")
        raw = {k: _shared_raw(ctx, dt, shp) for k, (dt, shp) in specs.items()}
        self._views = {k: _np_view(raw[k], *specs[k]) for k in specs}
        self._bounds = shard_bounds(E, W)
        self._conns: list[Any] = []
        self._procs: list[Any] = []
        # Spawned children inherit os.environ: pin them to the CPU for
        # the spawn window so a worker can never open (or wait on) the
        # chip this process holds.
        saved_platforms = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for w, (lo, hi) in enumerate(self._bounds):
                parent_conn, child_conn = ctx.Pipe()
                proc = ctx.Process(
                    target=_worker_main,
                    args=(
                        child_conn, w, env_id, env_kwargs,
                        pixel_preprocess, lo, hi, raw, specs,
                    ),
                    daemon=True,
                    name=f"env-shard-{w}",
                )
                proc.start()
                child_conn.close()
                self._conns.append(parent_conn)
                self._procs.append(proc)
        finally:
            if saved_platforms is None:
                os.environ.pop("JAX_PLATFORMS", None)
            else:
                os.environ["JAX_PLATFORMS"] = saved_platforms
        self._closed = False
        self._gauge_prev = (time.monotonic(), 0.0)
        self._gauge_last_util = 0.0
        # The gauge is a stateful rate integrator with TWO independent
        # consumers since ISSUE 3 — the 5s sampler thread AND every
        # /metrics HTTP scrape (exporter → sample_row) — so its
        # read-modify-write needs a lock, and a scrape must not shrink
        # the sampler's utilization window to a meaningless sliver.
        import threading

        self._gauge_lock = threading.Lock()
        from actor_critic_tpu.telemetry import sampler as _sampler

        self._gauge_name = _sampler.register_gauge("host_pool", self._gauge)

    # -- parent⇄worker plumbing -------------------------------------------
    def _death_msg(self, w: int) -> str:
        rc = self._procs[w].exitcode
        return (
            f"env worker {w} died (exitcode={rc}) — the sharded pool is "
            "unusable; checkpoint-restart the run"
        )

    def _send(self, w: int, msg) -> None:
        try:
            self._conns[w].send(msg)
        except (BrokenPipeError, OSError):
            raise RuntimeError(self._death_msg(w)) from None

    def _await(self, w: int):
        conn, proc = self._conns[w], self._procs[w]
        deadline = time.monotonic() + self._step_timeout_s
        while True:
            try:
                if conn.poll(0.2):
                    kind, payload = conn.recv()
                    if kind == "error":
                        raise RuntimeError(
                            f"env worker {w} crashed:\n{payload}"
                        )
                    return payload
            except (EOFError, ConnectionResetError, OSError):
                raise RuntimeError(self._death_msg(w)) from None
            if not proc.is_alive() and not conn.poll(0.2):
                raise RuntimeError(self._death_msg(w))
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"env worker {w} gave no answer within "
                    f"{self._step_timeout_s:.0f}s (simulator wedged?)"
                )

    def _barrier(self) -> None:
        for w in range(self.num_workers):
            self._await(w)

    # -- SyncVectorEnv surface --------------------------------------------
    def reset(self, seed=None, options=None):
        if isinstance(seed, int):
            # SyncVectorEnv's int→list rule over GLOBAL indices, so shard
            # layout never changes which env gets which seed.
            seeds = [seed + i for i in range(self.num_envs)]
        elif seed is None:
            seeds = [None] * self.num_envs
        else:
            seeds = list(seed)
        for w, (lo, hi) in enumerate(self._bounds):
            self._send(w, ("reset", seeds[lo:hi]))
        self._barrier()
        return self._views["obs"].copy(), {}

    def step(self, actions: np.ndarray):
        self._views["act"][:] = actions
        for w in range(self.num_workers):
            self._send(w, ("step", None))
        self._barrier()
        v = self._views
        # Copies, not views: callers hold step outputs across the next
        # step, and the shm blocks are rewritten in place.
        return (
            v["obs"].copy(),
            v["reward"].copy(),
            v["terminated"].copy(),
            v["truncated"].copy(),
            {"final_obs": v["final_obs"].copy()},
        )

    def close(self) -> None:
        # Test-and-set under the gauge lock: close() can race itself
        # (training-loop teardown vs. an exception path unwinding), and
        # two callers passing the flag check would double-close the
        # worker pipes.
        with self._gauge_lock:
            if self._closed:
                return
            self._closed = True
        from actor_critic_tpu.telemetry import sampler as _sampler

        _sampler.unregister_gauge(self._gauge_name)
        for conn in self._conns:
            try:
                conn.send(("close", None))
            except (BrokenPipeError, OSError):
                pass
        for proc in self._procs:
            proc.join(timeout=5.0)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=5.0)
        for conn in self._conns:
            try:
                conn.close()
            except OSError:
                pass

    # -- telemetry ---------------------------------------------------------
    def drain_telemetry(self) -> int:
        """Ship each worker's buffered per-step span records into the
        installed session's spans.jsonl with the worker's REAL pid, so
        Perfetto renders one lane per worker process (idle gaps between
        batch steps included) instead of the parent's synthetic busy-sum
        reconstruction. Called by host_collect once per collection block;
        returns the number of records merged (0 without a session)."""
        from actor_critic_tpu import telemetry

        s = telemetry.current()
        if s is None or self._closed:
            return 0
        # Consume EVERY worker's reply before emitting anything: an
        # emission failure mid-loop (e.g. spans.jsonl hitting ENOSPC)
        # must not leave unread "drain" acks in the pipes — the next
        # "step" barrier would consume a stale ack and every subsequent
        # exchange would read one-step-old shared memory.
        for w in range(self.num_workers):
            self._send(w, ("drain", None))
        payloads = [self._await(w) for w in range(self.num_workers)]
        batch = []
        for w, (lo, hi) in enumerate(self._bounds):
            payload = payloads[w]
            pid = self._procs[w].pid
            s.tracer.name_process(pid, f"env-shard-{w}")
            args = {"worker": w, "envs": hi - lo}
            batch.extend(
                (_WORKER_PHASE, t_epoch, dur, pid, 0, args)
                for t_epoch, dur in payload["records"]
            )
            if payload["dropped"]:
                telemetry.event(
                    "worker_telemetry_dropped",
                    worker=w, dropped=payload["dropped"],
                )
        # One locked write for the whole block's records (hot path:
        # runs on the training thread once per collection block).
        s.tracer.complete_foreign_many(batch)
        return len(batch)

    def worker_stats(self) -> list[dict]:
        stats = self._views["stats"]
        return [
            {
                "worker": w,
                "envs": hi - lo,
                "busy_s": round(float(stats[w, 0]), 4),
                "env_steps": int(stats[w, 1]),
                "last_step_s": round(float(stats[w, 2]), 6),
            }
            for w, (lo, hi) in enumerate(self._bounds)
        ]

    # Calls closer together than this reuse the previous utilization
    # instead of resetting the window: back-to-back /metrics scrapes (or
    # a scrape racing the sampler tick) would otherwise measure a
    # sliver-of-a-second window and report noise.
    _GAUGE_MIN_WINDOW_S = 1.0

    def _gauge(self) -> dict:
        """Pool-utilization row for the resource sampler AND /metrics
        scrapes: the busy fraction of the worker fleet over the window
        since the previous (window-resetting) call — the number that
        says whether the pool or the device is the bottleneck."""
        stats = self._views["stats"]
        busy = float(stats[:, 0].sum())
        with self._gauge_lock:
            now = time.monotonic()
            prev_t, prev_busy = self._gauge_prev
            dt = now - prev_t
            if dt >= self._GAUGE_MIN_WINDOW_S:
                util = (busy - prev_busy) / (dt * self.num_workers)
                self._gauge_last_util = round(min(max(util, 0.0), 1.0), 4)
                self._gauge_prev = (now, busy)
            util = self._gauge_last_util
        return {
            "workers": self.num_workers,
            "num_envs": self.num_envs,
            "env_steps": int(stats[:, 1].sum()),
            "busy_s": round(busy, 3),
            "utilization": util,
        }
