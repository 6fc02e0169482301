"""Compile-once subsystem (ISSUE 4): persistent XLA compilation cache,
background AOT warmup, and shape-stabilized entry points.

Every cold start — and every `run_resumable.sh` retry leg — used to pay
full XLA compile before the first env step, and the PR 3 compile
listener could *name* a recompile storm but nothing prevented one. This
module is the prevention layer, three parts:

1. **Persistent compilation cache** (`enable_persistent_cache`): JAX's
   on-disk executable cache (`jax_compilation_cache_dir`) with the
   min-compile-time/min-entry-size floors dropped to zero so every
   program is cached. Where it lives is decided in ONE place,
   `resolve_cache_dir`: `JAX_COMPILATION_CACHE_DIR` when the environment
   sets it, else `<checkout>/.jax_cache` — a fixed path, because the
   path is part of what makes a later process find the entries again.
   `train.py`, `scripts/serve.py` and `chip_smoke.py` all go through it.
   Hit/miss counts ride the `jax.monitoring` cache events into
   `cache_stats()` (exported at `/metrics`, attributed per-function in
   `run_report.py`).

2. **AOT warmup registry** (`register_warmup` / `start_warmup`): each
   jitted entry point in `algos/` registers a *planner* that derives the
   entry's abstract argument shapes from the env spec + config (via
   `jax.eval_shape`, no device allocation) and returns a thunk that
   `.lower(...).compile()`s it. `start_warmup` runs every applicable
   thunk on a background daemon thread while the env pool spawns/resets
   and the checkpoint restores, so time-to-first-step hides compile
   instead of serializing on it. Compiled executables land in the
   persistent cache; the training loop's own first dispatch then
   re-traces and *hits* the cache instead of compiling. Each entry is a
   `warmup` span on the `aot-warmup` thread.
   `scripts/check_warmup_registry.py` (tier-1, via
   tests/test_warmup_registry.py) fails when a `jax.jit` entry point in
   `algos/` or `models/` is neither registered here nor listed in
   `EXEMPT` with a reason.

3. **Shape stabilization** (`make_chunked_step`, `pad_to_bucket`): the
   recompile sources the PR 3 attribution table exposed were variable
   *static* shapes — chiefly the chunked fused loop's tail/realignment
   dispatches, where every distinct k was its own XLA program. Partial
   chunks are now padded to the full-stride bucket and cut with an
   `n_valid` validity mask (a traced scalar), so a chunked run compiles
   exactly TWO programs (full + masked bucket) no matter how it is
   resumed or where it ends. `pad_to_bucket` is the generic batch-axis
   version for host-side callers that would otherwise feed a jitted
   entry point a ragged tail batch. Audit note: the fused eval program
   already masks episode tails in-shape (`common.evaluate`'s `alive`
   mask) and host pools always deliver full `[K, E]` blocks, so those
   paths carry no variable shapes to stabilize.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from functools import partial
from typing import Any, Callable, Optional

# ---------------------------------------------------------------------------
# Persistent compilation cache
# ---------------------------------------------------------------------------

# Process-global hit/miss counters fed by jax.monitoring's cache events.
# Like the telemetry compile counter, listeners cannot be unregistered,
# so registration is once-per-process and the counts only grow.
_CACHE_STATS = {"hits": 0, "misses": 0}
_stats_lock = threading.Lock()
# The same events counted per compiling thread: a cache event fires on
# the thread inside the compile call, so a per-thread count attributes a
# hit to THAT compile even while the AOT warmup thread compiles beside
# the training thread (telemetry/profiler.py reads it).
_thread_stats = threading.local()
_stats_installed = False
_enabled_dir: Optional[str] = None


def _on_cache_event(name: str, **kwargs) -> None:
    # Cache events fire from whichever thread compiles — the AOT warmup
    # runner overlaps the training thread — and an unlocked += on the
    # shared counters loses increments. Events are rare; the lock is
    # noise-level.
    if name.endswith("/cache_hits"):
        _thread_stats.hits = getattr(_thread_stats, "hits", 0) + 1
        with _stats_lock:
            _CACHE_STATS["hits"] += 1
    elif name.endswith("/cache_misses"):
        with _stats_lock:
            _CACHE_STATS["misses"] += 1


def ensure_cache_stats_listener() -> bool:
    """Idempotently hook the persistent-cache hit/miss event stream."""
    global _stats_installed
    with _stats_lock:
        if _stats_installed:
            return True
        try:
            import jax.monitoring

            jax.monitoring.register_event_listener(_on_cache_event)
            _stats_installed = True
        except Exception:
            return False  # telemetry must never take a run down
        return True


def cache_stats() -> dict:
    """{'hits', 'misses'} of the persistent compilation cache since the
    listener was installed (zeros when the cache was never enabled)."""
    return dict(_CACHE_STATS)


def thread_cache_hits() -> int:
    """Persistent-cache hits observed on the CALLING thread."""
    return getattr(_thread_stats, "hits", 0)


def enabled_dir() -> Optional[str]:
    """The cache directory this process enabled, or None."""
    return _enabled_dir


def enable_persistent_cache(cache_dir: str | os.PathLike) -> str:
    """Point JAX's persistent compilation cache at `cache_dir` (created
    if absent) with the caching floors at zero, so EVERY compiled
    program is written and a later process (or a post-`clear_caches`
    re-trace in this one) deserializes instead of recompiling. Returns
    the absolute directory. Safe to call more than once; the last
    directory wins."""
    global _enabled_dir
    import jax

    cache_dir = os.path.abspath(os.fspath(cache_dir))
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Floors down: the default 1s/min-size floors exist to avoid caching
    # trivial programs, but here the whole point is that leg N+1 skips
    # even the small compiles (dozens of sub-second utility jits add up
    # on a 1-core host).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _reset_jax_cache_state()
    ensure_cache_stats_listener()
    _enabled_dir = cache_dir
    return cache_dir


def _reset_jax_cache_state() -> None:
    """Drop jax's internal cache latches. `is_cache_used` and the cache
    handle are evaluated ONCE per process at the first compile — a
    process that compiled anything before `enable_persistent_cache`
    (test suites, import-time jits) would silently keep the cache
    disabled forever without this. Internal API of the installed jax."""
    from jax._src import compilation_cache as _cc

    _cc.reset_cache()


class temporary_cache:
    """Context manager: enable the persistent cache at `cache_dir`, then
    restore the previous configuration on exit (for tests and benches —
    `train.py` uses the one-shot `enable_persistent_cache`)."""

    def __init__(self, cache_dir: str | os.PathLike):
        self._dir = cache_dir

    def __enter__(self) -> str:
        import jax

        self._prev = jax.config.jax_compilation_cache_dir
        self._prev_floors = {
            flag: getattr(jax.config, flag)
            for flag in ("jax_persistent_cache_min_compile_time_secs",
                         "jax_persistent_cache_min_entry_size_bytes")
        }
        self._prev_enabled = _enabled_dir
        return enable_persistent_cache(self._dir)

    def __exit__(self, *exc) -> None:
        global _enabled_dir
        import jax

        jax.config.update("jax_compilation_cache_dir", self._prev)
        # The caching floors are process-global too — a caller with its
        # own cache configured must get its floors back, not keep the
        # cache-everything zeros.
        for flag, value in self._prev_floors.items():
            jax.config.update(flag, value)
        # Re-latch from the restored config so later compiles in this
        # process don't keep using (or skipping) the temporary dir.
        _reset_jax_cache_state()
        _enabled_dir = self._prev_enabled


# <checkout>/.jax_cache, from this file's own location (utils/ → package
# → checkout): the same directory from any working directory.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def resolve_cache_dir(cli_value: Optional[str] = None) -> Optional[str]:
    """THE rule for where the persistent compile cache lives (train.py,
    scripts/serve.py, chip_smoke.py; scripts/run_resumable.sh mirrors it):

    - `JAX_COMPILATION_CACHE_DIR` set → that directory, whatever the
      command line says: whoever placed the cache from outside (the chip
      machine, CI) is the one who can find it again.
    - not set → an explicit `--compile-cache-dir DIR`, else
      `DEFAULT_CACHE_DIR` (`<checkout>/.jax_cache`). A cache keyed to a
      run directory, a temporary name, a pid or a time never hits twice.
    - 'none'/'off'/'' → None: the program enables no cache.
    """
    if cli_value is not None and cli_value.lower() in ("", "none", "off"):
        return None
    return (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or cli_value
        or DEFAULT_CACHE_DIR
    )


# ---------------------------------------------------------------------------
# Shape stabilization
# ---------------------------------------------------------------------------

def bucket_size(n: int, buckets: tuple[int, ...]) -> int:
    """The smallest bucket >= n (buckets need not be sorted). Raises when
    n exceeds every bucket — a silent overflow would recompile, the exact
    failure this module exists to prevent."""
    if n < 0:
        raise ValueError(f"n must be >= 0, got {n}")
    fitting = [b for b in buckets if b >= n]
    if not fitting:
        raise ValueError(f"n={n} exceeds every bucket in {sorted(buckets)}")
    return min(fitting)


def pad_to_bucket(x, buckets: tuple[int, ...], axis: int = 0):
    """Zero-pad `x` along `axis` to the smallest fitting bucket size;
    returns (padded, valid_mask) where `valid_mask` is float32 [bucket]
    with 1.0 on real rows. Feeding jitted entry points bucketed batches
    instead of ragged tails bounds the distinct compiled programs to
    len(buckets) — pair with a masked reduction on the consumer side."""
    import numpy as np

    x = np.asarray(x)
    n = x.shape[axis]
    b = bucket_size(n, buckets)
    mask = np.zeros(b, np.float32)
    mask[:n] = 1.0
    if b == n:
        return x, mask
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, b - n)
    return np.pad(x, widths), mask


def make_chunked_step(raw_step: Callable, stride: int) -> Callable:
    """Shape-stabilized chunked dispatch: `(state, k) -> (state, metrics)`
    advancing k <= stride iterations of `raw_step` in ONE device
    program.

    Exactly two XLA programs ever compile, regardless of resume point or
    iteration count: the full-stride scan (the steady-state hot path,
    zero masking overhead) and ONE masked bucket for partial chunks —
    the tail/realignment dispatch is padded to the full stride and cut
    with a traced `n_valid` scalar, so every distinct partial k reuses
    the same executable (the old static-k design compiled a fresh
    program per distinct tail, the top recompile source in PR 3's
    attribution table). The masked program applies `raw_step` only to
    the first `n_valid` scan slots (the carry is held constant after),
    so results are bit-for-bit those of k sequential steps; metrics are
    the LAST VALID iteration's slice, matching the per-iteration loop's
    point-in-time logging semantics.
    """
    import jax
    import jax.numpy as jnp

    if stride < 1:
        raise ValueError(f"stride must be >= 1, got {stride}")

    @partial(jax.jit, donate_argnums=0)
    def full(s):
        s, ms = jax.lax.scan(lambda c, _: raw_step(c), s, None, length=stride)
        return s, jax.tree.map(lambda x: x[-1], ms)

    @partial(jax.jit, donate_argnums=0)
    def masked(s, n_valid):
        def body(c, i):
            new_c, m = raw_step(c)
            # cond lowers to select inside scan and round-trips typed
            # PRNG-key leaves (jnp.where on extended dtypes does not).
            c = jax.lax.cond(
                i < n_valid, lambda a, b: a, lambda a, b: b, new_c, c
            )
            return c, m
        s, ms = jax.lax.scan(body, s, jnp.arange(stride))
        last = jnp.maximum(n_valid, 1) - 1
        return s, jax.tree.map(lambda x: x[last], ms)

    def step(s, k: int):
        if k >= stride:
            return full(s)
        return masked(s, jnp.asarray(k, jnp.int32))

    # Exposed for AOT warmup (the registry compiles both programs with
    # abstract state so the run's first dispatch hits the cache).
    step.full = full
    step.masked = masked
    return step


# ---------------------------------------------------------------------------
# AOT warmup registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WarmupContext:
    """Everything a planner needs to derive an entry point's abstract
    argument shapes for THIS run: the resolved algo/env/config plus the
    CLI knobs that change which programs will execute (chunking, eval
    cadence, overlap mirroring, resume)."""

    algo: str            # resolved preset algo (td3/a3c keep their alias)
    fused: bool          # jax:* fused trainer vs host pool
    spec: Any            # EnvSpec (env.spec / pool.spec)
    cfg: Any             # the algo's frozen config dataclass
    env: Any = None      # the JaxEnv (fused runs only)
    chunk: int = 1       # --chunk (fused runs)
    iterations: int = 0  # --iterations (tail-chunk prediction)
    eval_every: int = 0  # --eval-every (eval programs compile only if on)
    eval_envs: int = 4   # --eval-envs (host eval pool batch)
    overlap: bool = True  # host loops: numpy actor mirror enabled
    resume: bool = False  # --resume (realignment chunks possible)
    # Async actor–learner decoupling (ISSUE 6): actor count (0 =
    # lockstep) and the learner's staleness correction — together they
    # decide WHICH update program runs and at what [K, E_a] block shape
    # (E_a = num_envs // async_actors).
    async_actors: int = 0
    async_correction: str = "vtrace"
    # Device-resident data plane (ISSUE 13): "device" stages trajectory
    # blocks in a donated HBM ring (data_plane/ring.py) and the learner
    # gathers+decodes in-jit — a different update program (and an
    # enqueue program) than the host plane's, at the same block shapes.
    # plane_codec picks the ring's per-key quantize codecs; queue_depth
    # sizes the ring the warmup's abstract state must match.
    data_plane: str = "host"
    plane_codec: str = "fp32"
    queue_depth: int = 4
    # Policy-serving gateway (ISSUE 10): non-empty bucket sizes put the
    # context in SERVING mode — plan_warmup then runs only the planners
    # registered with `register_warmup(..., serving=True)` (the serving
    # act programs), and none of the training planners: a gateway
    # process must not spend startup compiling update programs it will
    # never dispatch. serving_sample picks the stochastic act program
    # over the greedy one.
    serving_buckets: tuple[int, ...] = ()
    serving_sample: bool = False


# name -> planner(ctx) -> Optional[() -> None].  A planner returns None
# when its entry point will not run under this context (wrong algo, host
# entry on a fused run, mirror-covered acting path, eval disabled ...).
# jaxlint: thread-owned=import (populated only by @register_warmup
# decorators running at module-import time under the import lock; the
# warmup thread and the registry lint only read it afterwards)
_REGISTRY: dict[str, Callable[[WarmupContext], Optional[Callable]]] = {}

# Planners that belong to the SERVING side of the registry (registered
# with `register_warmup(..., serving=True)`): plan_warmup runs exactly
# one side per context — serving planners for a gateway context
# (ctx.serving_buckets non-empty), training planners otherwise.
# jaxlint: thread-owned=import (same import-time population as _REGISTRY)
_SERVING_PLANNERS: set[str] = set()

# jax.jit sites in algos//models/ that the lint must NOT require a
# registration for, with the reason a reviewer needs. Keys are
# "<module>.<enclosing function>" as scripts/check_warmup_registry.py
# derives them.
EXEMPT: dict[str, str] = {
    "host_loop.fused_train_loop":
        "loop driver jitting the step passed in; warmed via the "
        "per-algo <algo>.make_train_step registration",
    "host_loop.off_policy_train_host":
        "jits the per-algo make_greedy_act factory, registered as "
        "<algo>.make_greedy_act",
    "ppo.train_host":
        "jits ppo.make_greedy_act, registered under that name",
    "impala.make_sp_update":
        "mesh-sharded multi-device program; built only by the explicit "
        "parallel drivers, outside train.py's warmup scope",
    "impala.make_sp_train_step":
        "mesh-sharded multi-device program; built only by the explicit "
        "parallel drivers, outside train.py's warmup scope",
}


def register_warmup(name: str, serving: bool = False):
    """Decorator: register `planner(ctx) -> thunk | None` under `name`
    ("<module>.<factory>", the key the registry lint checks).
    `serving=True` marks the planner as belonging to the serving side
    of the registry (see _SERVING_PLANNERS)."""

    def deco(planner):
        _REGISTRY[name] = planner
        if serving:
            _SERVING_PLANNERS.add(name)
        return planner

    return deco


def registered_warmups() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def plan_warmup(ctx: WarmupContext) -> list[tuple[str, Callable]]:
    """(name, compile-thunk) for every registered entry point applicable
    to this run. Planner errors are contained per entry — warmup is an
    optimization and must never take the run down — but NOT silent: a
    planner that raises (e.g. a factory signature drifted under it)
    leaves a stderr line and a `warmup_plan_error` telemetry event, so
    the entry losing its warmup is a visible regression, not a quiet
    return to first-dispatch compile."""
    import sys

    from actor_critic_tpu.telemetry import session as _session

    serving_ctx = bool(ctx.serving_buckets)
    out: list[tuple[str, Callable]] = []
    for name in sorted(_REGISTRY):
        # One registry side per context: a serving context runs only the
        # serving planners (training planners would compile update/eval
        # programs the gateway never dispatches), and vice versa.
        if (name in _SERVING_PLANNERS) != serving_ctx:
            continue
        try:
            thunk = _REGISTRY[name](ctx)
        except Exception as e:
            print(
                f"[compile_cache] warmup planner {name!r} failed: "
                f"{type(e).__name__}: {e}",
                file=sys.stderr, flush=True,
            )
            try:
                _session.event(
                    "warmup_plan_error", entry=name, error=str(e)[:500]
                )
            except Exception:
                pass
            thunk = None
        if thunk is not None:
            out.append((name, thunk))
    return out


# Every runner this process started, oldest first. Compile failures on
# the warmup thread are contained (a `results` row with "error"), so a
# caller that must not miss one — chip_smoke.py — waits on these and
# reads the rows after `train.main` returns.
# jaxlint: thread-owned=main (appended by start() on the launching
# thread only; readers take a snapshot)
_STARTED_RUNNERS: list["WarmupRunner"] = []


def started_warmups() -> tuple["WarmupRunner", ...]:
    return tuple(_STARTED_RUNNERS)


class WarmupRunner:
    """Background executor for one run's warmup plan.

    Runs each thunk on a daemon thread (XLA compilation releases the
    GIL, so it genuinely overlaps host-side env spawn/reset/restore),
    records per-entry compile wall + outcome in `results`, and times each
    entry as a `warmup` span (args `entry`, and `error` where it failed),
    which holds the entry's `jit_*` and `compile` spans. `wait()` is for
    tests/benches; the training loop never joins it."""

    def __init__(self, plan: list[tuple[str, Callable]]):
        self._plan = plan
        # jaxlint: thread-owned=warmup (single writer: only the warmup
        # thread appends; benches/tests read AFTER wait() — the _done
        # Event's set/wait pair orders those appends before the read)
        self.results: list[dict] = []
        self._done = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="aot-warmup", daemon=True
        )

    def start(self) -> "WarmupRunner":
        _STARTED_RUNNERS.append(self)
        self._thread.start()
        return self

    def _run(self) -> None:
        from actor_critic_tpu.telemetry import session as _session

        for name, thunk in self._plan:
            t0 = time.perf_counter()
            row = {"entry": name}
            with _session.span("warmup", entry=name) as sp:
                try:
                    thunk()
                    row["compile_s"] = round(time.perf_counter() - t0, 4)
                except Exception as e:  # warmup must never take the run down
                    row["error"] = str(e)[:500]
                    sp.annotate(error=row["error"])
            self.results.append(row)
        # The thunks close over envs and jitted programs; a finished
        # runner stays listed in _STARTED_RUNNERS and keeps only its rows.
        self._plan = []
        self._done.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    @property
    def done(self) -> bool:
        return self._done.is_set()


def start_warmup(ctx: WarmupContext) -> WarmupRunner:
    """Plan + launch the background AOT warmup for this run (callers
    that want to print/inspect the plan first use `plan_warmup` +
    `WarmupRunner` directly, as train.py does)."""
    return WarmupRunner(plan_warmup(ctx)).start()


# -- planner helpers (shared by the per-algo registrations) -----------------

def key_struct():
    """Abstract typed-PRNG-key scalar (ShapeDtypeStruct with key dtype)."""
    import jax

    return jax.eval_shape(lambda: jax.random.key(0))


def scalar_struct(dtype):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct((), jnp.dtype(dtype))


def array_struct(shape, dtype):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def aot_compile(jitted, *args, **kwargs):
    """`.lower(...).compile()` — the compiled executable is not installed
    into the jit dispatch cache (JAX AOT contract), but with the
    persistent cache enabled the byproduct IS the cache entry the live
    dispatch will hit after its cheap re-trace."""
    return jitted.lower(*args, **kwargs).compile()


def jitted_thunk(fn: Callable, *args, **kwargs) -> Callable:
    """Warmup thunk for a function the training loop jits INLINE (e.g.
    the greedy factories): jit here, AOT-compile on call. Living in this
    module keeps the jit site out of algos/ — the registry lint scans
    there and planners must not register their own plumbing."""
    import jax

    jitted = jax.jit(fn)
    return lambda: aot_compile(jitted, *args, **kwargs)


def fused_state_struct(ctx: WarmupContext, init_state: Callable):
    """Abstract train state via eval_shape — no device allocation (a
    4096-env replay-carrying state would otherwise materialize twice)."""
    import jax

    return jax.eval_shape(
        partial(init_state, ctx.env, ctx.cfg), jax.random.key(0)
    )


def fused_step_thunk(ctx: WarmupContext, init_state: Callable,
                     make_train_step: Callable) -> Callable:
    """Warmup thunk for a fused train step under this run's dispatch
    shape: plain jit at chunk=1, else the full-stride program plus —
    only when a partial chunk can occur (tail or resume realignment) —
    the masked bucket."""
    import jax
    import jax.numpy as jnp

    state_abs = fused_state_struct(ctx, init_state)
    raw_step = make_train_step(ctx.env, ctx.cfg)
    if ctx.chunk <= 1:
        jitted = jax.jit(raw_step, donate_argnums=0)
        return lambda: aot_compile(jitted, state_abs)

    step = make_chunked_step(raw_step, ctx.chunk)
    need_masked = ctx.resume or (
        ctx.iterations > 0 and ctx.iterations % ctx.chunk != 0
    )

    def thunk():
        if ctx.iterations == 0 or ctx.iterations >= ctx.chunk:
            aot_compile(step.full, state_abs)
        if need_masked or ctx.iterations < ctx.chunk:
            aot_compile(step.masked, state_abs, scalar_struct(jnp.int32))

    return thunk


def fused_eval_thunk(ctx: WarmupContext, init_state: Callable,
                     make_eval_fn: Callable) -> Optional[Callable]:
    """Warmup thunk for the fused greedy-eval program (train.py jits it
    with static default num_envs/num_steps); None when eval is off."""
    import jax

    if ctx.eval_every <= 0:
        return None
    state_abs = fused_state_struct(ctx, init_state)
    ev = jax.jit(make_eval_fn(ctx.env, ctx.cfg), static_argnums=(2, 3))
    k = key_struct()
    return lambda: aot_compile(ev, state_abs, k)


def host_obs_struct(ctx: WarmupContext, leading: tuple[int, ...]):
    """[*leading, *obs_shape] in the dtype the pool actually delivers
    (float32, or uint8 for preserved pixel obs — host_pool casts float64
    MuJoCo obs before they reach any buffer)."""
    return array_struct((*leading, *ctx.spec.obs_shape), ctx.spec.obs_dtype)


def mirror_active(ctx: WarmupContext, params_abs) -> bool:
    """Whether the host loop will EXPLORE through the numpy mirror
    (models/host_actor) — in which case the jitted act entry point is
    constructed but never dispatched, and warming it would compile a
    program the run never runs. `supports_mirror` only inspects the
    param tree's structure, so the abstract tree suffices."""
    from actor_critic_tpu.models import host_actor

    return ctx.overlap and host_actor.supports_mirror(params_abs)


def greedy_mirror_active(params_abs) -> bool:
    """Whether host EVAL runs through the numpy mirror. Unlike exploring,
    the loops mirror eval whenever the params support it (overlap only
    gates acting), so the jitted greedy program never dispatches."""
    from actor_critic_tpu.models import host_actor

    return host_actor.supports_mirror(params_abs)


def register_fused_warmups(module: str, aliases, init_state: Callable,
                           make_train_step: Callable,
                           make_eval_fn: Callable) -> None:
    """Register the two fused-trainer entry points every algo shares:
    `<module>.make_train_step` (the per-dispatch program train.py jits —
    plain, or the chunked full+masked pair) and `<module>.make_eval_fn`
    (the greedy-eval program, when --eval-every is on)."""
    aliases = frozenset(aliases)

    @register_warmup(f"{module}.make_train_step")
    def _step(ctx):
        if not ctx.fused or ctx.algo not in aliases:
            return None
        return fused_step_thunk(ctx, init_state, make_train_step)

    @register_warmup(f"{module}.make_eval_fn")
    def _eval(ctx):
        if not ctx.fused or ctx.algo not in aliases:
            return None
        return fused_eval_thunk(ctx, init_state, make_eval_fn)


def register_offpolicy_warmups(module: str, aliases, *,
                               init_learner: Callable,
                               make_host_act_fn: Callable,
                               make_host_ingest_update: Callable,
                               make_greedy_act: Callable,
                               init_state: Callable,
                               make_train_step: Callable,
                               make_eval_fn: Callable) -> None:
    """Register the DDPG/TD3/SAC entry-point family: the host-path
    explore act / ingest+update / greedy-eval programs (skipping the
    ones the numpy mirror replaces) plus the shared fused pair."""
    aliases = frozenset(aliases)

    def _learner_abs(ctx):
        import jax

        return jax.eval_shape(
            partial(
                init_learner, tuple(ctx.spec.obs_shape),
                ctx.spec.action_dim, ctx.cfg,
            ),
            jax.random.key(0),
        )

    @register_warmup(f"{module}.make_host_act_fn")
    def _act(ctx):
        import numpy as np

        if ctx.fused or ctx.algo not in aliases or ctx.async_actors:
            return None  # async actors always act through the mirror
        actor_abs = _learner_abs(ctx).actor_params
        if mirror_active(ctx, actor_abs):
            return None  # the numpy mirror explores; never dispatched
        jitted = make_host_act_fn(ctx.spec.action_dim, ctx.cfg)
        obs = host_obs_struct(ctx, (ctx.cfg.num_envs,))
        return lambda: aot_compile(
            jitted, actor_abs, obs, key_struct(), scalar_struct(np.int32)
        )

    @register_warmup(f"{module}.make_host_ingest_update")
    def _ingest(ctx):
        import numpy as np

        if ctx.fused or ctx.algo not in aliases:
            return None
        if ctx.data_plane == "device" and ctx.async_actors:
            # ISSUE 13: the device plane dispatches
            # device_replay.make_device_ingest_update instead — the
            # argument-fed program would be a wasted warmup compile.
            return None
        from actor_critic_tpu.algos.common import OffPolicyTransition

        cfg = ctx.cfg
        # Async actor fleets feed per-actor [K, E/A] blocks (ISSUE 9
        # satellite: off-policy through ActorService); the lockstep
        # loop ingests the full [K, E] block.
        K = cfg.steps_per_iter
        E = cfg.num_envs // ctx.async_actors if ctx.async_actors else cfg.num_envs
        learner_abs = _learner_abs(ctx)
        traj = OffPolicyTransition(
            obs=host_obs_struct(ctx, (K, E)),
            action=array_struct((K, E, ctx.spec.action_dim), np.float32),
            reward=array_struct((K, E), np.float32),
            next_obs=host_obs_struct(ctx, (K, E)),
            terminated=array_struct((K, E), np.float32),
            done=array_struct((K, E), np.float32),
        )
        jitted = make_host_ingest_update(ctx.spec.action_dim, cfg)
        return lambda: aot_compile(
            jitted, learner_abs, traj, scalar_struct(np.int32)
        )

    @register_warmup(f"{module}.make_greedy_act")
    def _greedy(ctx):
        if ctx.fused or ctx.algo not in aliases or ctx.eval_every <= 0:
            return None
        actor_abs = _learner_abs(ctx).actor_params
        if greedy_mirror_active(actor_abs):
            return None  # eval mirrors on the host; never dispatched
        obs = host_obs_struct(ctx, (ctx.eval_envs,))
        return jitted_thunk(
            make_greedy_act(ctx.spec.action_dim, ctx.cfg), actor_abs, obs
        )

    register_fused_warmups(
        module, aliases, init_state, make_train_step, make_eval_fn
    )
