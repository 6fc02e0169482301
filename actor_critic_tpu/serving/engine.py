"""Bucketed, AOT-warm act programs for the policy-serving gateway
(ISSUE 10 tentpole).

A serving process dispatches ONE jitted act program per bucket size:
incoming micro-batches are padded to the smallest fitting bucket
(`compile_cache.pad_to_bucket`), so the distinct compiled programs are
bounded by `len(buckets)` no matter how request sizes mix — the same
shape-stabilization discipline the chunked trainer uses (ISSUE 4), now
pointed at traffic. Every bucket is compiled at startup, two ways:

- `register_warmup("engine.make_act_program", serving=True)`: the
  registry planner AOT-compiles each bucket from ABSTRACT params on the
  background warmup thread (persistent-cache prewarm, overlapping
  checkpoint restore), keyed off `WarmupContext.serving_buckets`;
- `PolicyEngine.warm(params)`: one concrete dispatch per bucket through
  the live jit, so the dispatch cache itself is hot before the gateway
  accepts traffic — steady-state serving is 0-recompile even with no
  persistent cache configured.

Param trees installed into the store are normalized by
`prepare_params`: `checkpoint.uncommit` re-places restored leaves as
uncommitted XLA-owned buffers, because committed (orbax-restored)
arrays lower byte-different HLO that would miss both the warmup's cache
entries and the live dispatch cache — a hot-swap would otherwise pay a
recompile on its first flush (the exact PR 4 failure mode, resurfacing
as a p99 spike).
"""

from __future__ import annotations

import itertools
from typing import Any, Optional

import numpy as np

from actor_critic_tpu.utils import compile_cache

# Serving act programs are tiny (one policy forward); a fine-grained
# ladder keeps padding waste low at small occupancy while the top end
# bounds rows-per-flush.
DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32, 64)

SUPPORTED_ALGOS = ("ppo", "ddpg", "td3", "sac")


def make_act_program(spec, cfg, algo: str = "ppo", sample: bool = False):
    """The jitted serving act program for one policy architecture:
    `(params, obs) -> actions` (greedy), or `(params, obs, key) ->
    actions` with `sample=True` (PPO only — the off-policy actors are
    deterministic and serve their greedy action). Built from the SAME
    network factories the trainers use, so a served action is bitwise
    the trainer's eval action for the same params/obs."""
    import jax

    if algo == "ppo":
        from actor_critic_tpu.algos import ppo

        if sample:
            net = ppo.make_network(spec, cfg)

            def act(params, obs, key):
                dist, _ = net.apply(params, obs)
                return dist.sample(key)

            return jax.jit(act)
        return jax.jit(ppo.make_greedy_act(spec, cfg))
    if sample:
        raise ValueError(
            f"sample-mode serving is PPO-only ({algo!r} serves a "
            "deterministic actor — its greedy action IS its policy)"
        )
    if algo in ("ddpg", "td3"):
        from actor_critic_tpu.algos import ddpg

        return jax.jit(ddpg.make_greedy_act(spec.action_dim, cfg))
    if algo == "sac":
        from actor_critic_tpu.algos import sac

        return jax.jit(sac.make_greedy_act(spec.action_dim, cfg))
    raise ValueError(
        f"unsupported serving algo {algo!r}; supported: {SUPPORTED_ALGOS}"
    )


def init_params(spec, cfg, algo: str = "ppo", seed: int = 0):
    """Freshly initialized params for this architecture (the tree the
    act program consumes — actor params only for the off-policy algos).
    Serves as the restore TEMPLATE for params-only checkpoints and as
    the --random-init policy for benches/demos."""
    import jax

    key = jax.random.key(seed)
    if algo == "ppo":
        from actor_critic_tpu.algos import ppo

        return ppo.init_host_params(spec, cfg, key)[0]
    if algo in ("ddpg", "td3"):
        from actor_critic_tpu.algos import ddpg

        return ddpg.init_learner(
            tuple(spec.obs_shape), spec.action_dim, cfg, key
        ).actor_params
    if algo == "sac":
        from actor_critic_tpu.algos import sac

        return sac.init_learner(
            tuple(spec.obs_shape), spec.action_dim, cfg, key
        ).actor_params
    raise ValueError(
        f"unsupported serving algo {algo!r}; supported: {SUPPORTED_ALGOS}"
    )


def abstract_params(spec, cfg, algo: str = "ppo"):
    """The act program's param tree as ShapeDtypeStructs (eval_shape —
    no device allocation), for AOT-compiling buckets before any
    checkpoint has been restored."""
    import jax

    return jax.eval_shape(lambda: init_params(spec, cfg, algo, 0))


class PolicyEngine:
    """Bucket-stabilized act dispatch for ONE policy architecture
    (spec + config + algo). Multiple resident policies of the same
    architecture share one engine — and therefore one set of compiled
    programs; hot-swapping params never changes the program.

    `act` may be called concurrently from the micro-batcher's flight
    workers (overlapped dispatch, ISSUE 17): jit dispatch is
    thread-safe, the mirror closes over frozen numpy, and the
    sample-mode flush counter is `itertools.count` (GIL-atomic) — no
    other engine state is written after construction/warmup, which
    happen on the owning thread before any dispatcher starts.

    `backend="auto"` (ISSUE 17) defers the XLA-vs-mirror choice to
    `resolve_backend(params)`: batch-1 dispatch walls of both paths
    are measured against concrete params and the faster one is fixed —
    batch-1 is the decisive shape because it is where the jit
    dispatch envelope dominates an MLP forward (the same trade the
    training loops make per-architecture, now measured per-host).
    """

    def __init__(
        self,
        spec,
        cfg,
        algo: str = "ppo",
        buckets: tuple[int, ...] = DEFAULT_BUCKETS,
        sample: bool = False,
        seed: int = 0,
        backend: str = "xla",
    ):
        buckets = tuple(sorted({int(b) for b in buckets}))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets!r}")
        if backend not in ("xla", "mirror", "auto"):
            raise ValueError(
                f"backend must be 'xla', 'mirror' or 'auto', got {backend!r}"
            )
        self.spec = spec
        self.cfg = cfg
        self.algo = algo
        self.sample = bool(sample)
        self.buckets = buckets
        if backend == "auto" and self.sample:
            # Mirror serves greedy only, so there is nothing to choose.
            backend = "xla"
        self.backend = backend
        # resolve_backend's measurement record ({'backend', 'xla_ms',
        # 'mirror_ms'}); None until (unless) an auto choice runs.
        self.auto_choice: Optional[dict] = None
        if backend == "mirror":
            # CPU-only serving hosts: the numpy greedy mirror
            # (models/host_actor) beats a batch-1 XLA dispatch on
            # MLP-torso policies — the same trade the training loops
            # make. No compiled programs, so buckets only bound the
            # per-flush row budget (ragged batches dispatch as-is).
            if self.sample:
                raise ValueError(
                    "backend='mirror' serves greedy actions only"
                )
            from actor_critic_tpu.models import host_actor

            self._program = None
            self._mirror = host_actor.greedy_mirror_for(spec, cfg, algo)
        else:
            self._mirror = None
            self._program = make_act_program(
                spec, cfg, algo, sample=self.sample
            )
        self._seed = int(seed)
        self._base_key = None  # lazy: jax.random.key allocates on-device
        # jaxlint: thread-owned=dispatcher (itertools.count — next() is
        # GIL-atomic, so concurrent flight workers each draw a unique
        # flush key; the counter exists to give each sampled flush a
        # fresh fold_in key)
        self._flush_counter = itertools.count()

    @property
    def max_rows(self) -> int:
        """Largest bucket — the micro-batcher's per-flush row budget."""
        return self.buckets[-1]

    def prepare_params(self, params):
        """Install-normalize a param tree for serving. XLA backend:
        every leaf becomes an uncommitted, XLA-owned device buffer
        (`checkpoint.uncommit`), so a hot-swapped checkpoint lowers the
        same HLO as the warmed programs and steady-state stays
        0-recompile (numpy trees — e.g. a learner's published snapshot
        — are placed on device by the same path). Mirror backend: a
        frozen numpy snapshot (PolicyPublisher's contract) after a
        `supports_mirror` structure check."""
        if self.backend == "auto":
            raise RuntimeError(
                "backend='auto' is unresolved — call "
                "resolve_backend(params) before installing policies"
            )
        if self.backend == "mirror":
            import jax

            from actor_critic_tpu.models import host_actor

            # np.array COPIES (device_get of numpy input is a no-copy
            # alias): freezing must land on our snapshot, never the
            # caller's buffers — PolicyPublisher's contract verbatim.
            np_params = jax.tree.map(np.array, jax.device_get(params))
            if not host_actor.supports_mirror(np_params):
                raise ValueError(
                    "backend='mirror' needs an MLP-torso param tree "
                    "(conv torsos keep the XLA acting path)"
                )
            for leaf in jax.tree.leaves(np_params):
                leaf.flags.writeable = False
            return np_params
        from actor_critic_tpu.utils import checkpoint

        return checkpoint.uncommit(params)

    def resolve_backend(self, params, trials: int = 7) -> str:
        """Fix `backend='auto'` from measured batch-1 dispatch walls:
        time `trials` single-row acts through the compiled XLA bucket-1
        program and through the numpy greedy mirror (min-of-trials —
        the envelope floor, robust to scheduler noise), pick the
        faster, and record both walls on `self.auto_choice`. Params
        whose structure the mirror cannot serve (conv torsos) resolve
        to XLA without measuring. The bucket-1 compile happens OUTSIDE
        the timed region, so the choice compares steady-state
        dispatch, not compilation. Idempotent no-op on an already
        concrete backend."""
        if self.backend != "auto":
            return self.backend
        import time as _time

        import jax

        from actor_critic_tpu.models import host_actor

        obs = np.zeros(
            (1, *self.spec.obs_shape), np.dtype(self.spec.obs_dtype)
        )
        np_params = jax.tree.map(np.array, jax.device_get(params))
        if not host_actor.supports_mirror(np_params):
            self.backend = "xla"
            self.auto_choice = {"backend": "xla", "reason": "no mirror"}
            return self.backend
        for leaf in jax.tree.leaves(np_params):
            leaf.flags.writeable = False
        mirror = host_actor.greedy_mirror_for(self.spec, self.cfg, self.algo)
        from actor_critic_tpu.utils import checkpoint

        xla_params = checkpoint.uncommit(params)
        padded, _ = compile_cache.pad_to_bucket(obs, self.buckets)

        def xla_once():
            # jaxlint: disable=mask-propagation (timing-only dispatch:
            # the output is discarded after the wall-clock read, so the
            # junk lanes never feed math or a response)
            out = self._program(xla_params, jax.device_put(padded))
            return jax.device_get(out)

        xla_once()  # bucket-1 compile + dispatch-cache warm, untimed

        def wall(fn) -> float:
            best = float("inf")
            for _ in range(max(1, int(trials))):
                t0 = _time.perf_counter()
                fn()
                best = min(best, _time.perf_counter() - t0)
            return best

        xla_ms = wall(xla_once) * 1e3
        mirror_ms = wall(lambda: mirror(np_params, obs)) * 1e3
        if mirror_ms < xla_ms:
            self.backend = "mirror"
            self._mirror = mirror
        else:
            self.backend = "xla"
        self.auto_choice = {
            "backend": self.backend,
            "xla_ms": round(xla_ms, 4),
            "mirror_ms": round(mirror_ms, 4),
        }
        return self.backend

    def _key_for_flush(self):
        import jax

        if self._base_key is None:
            self._base_key = jax.random.key(self._seed)
        return jax.random.fold_in(self._base_key, next(self._flush_counter))

    def act(self, params, obs: np.ndarray) -> np.ndarray:
        """Dispatch one micro-batch: pad [n, *obs_shape] to its bucket,
        run the jitted program, return the first n actions as numpy.

        Both crossings are EXPLICIT (`jax.device_put` in,
        `jax.device_get` out — ISSUE 15 transfer discipline): the act
        path's transfer bytes are a serving-budget line item perfsan
        counts, and the dispatch runs clean under
        `jax.transfer_guard("disallow")` — an implicit coercion
        sneaking into this path fails the sanitizer instead of silently
        paying another crossing."""
        import jax

        obs = np.asarray(obs, dtype=np.dtype(self.spec.obs_dtype))
        n = obs.shape[0]
        if self.backend == "mirror":
            out = self._mirror(params, obs)
        else:
            padded, _ = compile_cache.pad_to_bucket(obs, self.buckets)
            staged = jax.device_put(padded)
            if self.sample:
                out = self._program(
                    params, staged, self._key_for_flush()
                )
            else:
                out = self._program(params, staged)
            out = jax.device_get(out)
        return np.asarray(out)[:n]

    def warm(self, params) -> int:
        """Dispatch every bucket once with concrete params so the live
        jit cache is hot before traffic arrives (with the persistent
        cache enabled these re-traces HIT what the registry planner
        AOT-compiled). Returns the number of programs dispatched (0 for
        the mirror backend — nothing compiles)."""
        if self.backend == "mirror":
            return 0
        for b in self.buckets:
            self.act(params, np.zeros((b, *self.spec.obs_shape), np.float32))
        return len(self.buckets)

    def warmup_thunk(self, params_abs=None):
        """AOT-compile thunk over ABSTRACT params for the warmup
        registry: `.lower(...).compile()` of every bucket (plus the
        sample-mode key arg), feeding the persistent cache on the
        background warmup thread."""

        if self.backend == "mirror":
            return lambda: None  # nothing compiles on the mirror path

        def thunk():
            p_abs = params_abs
            if p_abs is None:
                p_abs = abstract_params(self.spec, self.cfg, self.algo)
            for b in self.buckets:
                obs = compile_cache.array_struct(
                    (b, *self.spec.obs_shape), self.spec.obs_dtype
                )
                if self.sample:
                    compile_cache.aot_compile(
                        self._program, p_abs, obs, compile_cache.key_struct()
                    )
                else:
                    compile_cache.aot_compile(self._program, p_abs, obs)

        return thunk


@compile_cache.register_warmup("engine.make_act_program", serving=True)
def _warmup_act_buckets(ctx) -> Optional[Any]:
    """Serving-side planner: AOT-compile every act bucket for the
    gateway's architecture. Runs only for serving contexts
    (ctx.serving_buckets non-empty — plan_warmup's registry gate)."""
    if not ctx.serving_buckets:
        return None
    engine = PolicyEngine(
        ctx.spec,
        ctx.cfg,
        algo=ctx.algo,
        buckets=ctx.serving_buckets,
        sample=ctx.serving_sample,
    )
    return engine.warmup_thunk()
