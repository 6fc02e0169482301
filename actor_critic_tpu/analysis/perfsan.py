"""perfsan: dispatch/transfer budget sanitizer (ISSUE 15 runtime half).

racesan made thread interleavings seeded and replayable, fleetsan
lifted that to processes, numsan to numeric faults; this module applies
the same contract to the PERFORMANCE dimension. The repo's headline
perf claims are contracts — PR 13's device plane promises "steady-state
consumption transfers zero bytes", PR 10's gateway promises "a swap
never recompiles" — and until now they were pinned by hand-written
per-test assertions. perfsan runs the REAL steady-state programs and
meters four quantities per steady-state block:

- **dispatches** — every XLA execution, counted at the C++ jit
  fastpath's `post_hook` (the seam `jax_debug_nans` uses): steady-state
  jit calls AND warmed eager ops fire it, with the program name, at
  nanoseconds of overhead. A Python-level reduction or stray eager op
  inside a hot loop shows up as extra dispatches no static pass can
  miss-count.
- **transfers / transferred bytes** — explicit host↔device crossings,
  counted by patching the `jax.device_put` / `jax.device_get` /
  `jnp.array` / `jnp.asarray` seams for the measured block (numpy-input
  uploads and device-array downloads contribute their `nbytes`).
- **recompiles** — the compile-funnel listener's monotonic event count
  (`telemetry.profiler`, ISSUE 3), the same counter the 0-recompile
  tests index.

Measured scopes additionally run under `jax.transfer_guard`: the
device-plane learner and the fused mixture step run "disallow", so any
IMPLICIT crossing (a numpy argument riding a dispatch, host scalars
uploaded per step) raises instead of silently paying a crossing —
which is why the exercisers stage the slot-index scalar with an
explicit `device_put`: the one sanctioned transfer becomes a metered
4-byte line item instead of an invisible implicit upload.

Each steady-state program is checked against the committed
`perf_budgets.json` manifest (max dispatches / transfers / transferred
bytes / recompiles per steady-state block). The four programs:

    ppo_update_host     the async V-trace learner consuming host-plane
                        blocks (jnp.array upload per block — budgeted,
                        not forbidden: that upload IS the host plane)
    ppo_update_device   the same learner on the HBM DeviceTrajRing —
                        gather+decode in-jit; budget pins 1 dispatch,
                        1 transfer (the slot scalar), 4 bytes, 0
                        recompiles per consumed block, and the actor's
                        int8 enqueue bytes ride a sibling budget
    offpolicy_ingest    DDPG's fused gather+scatter+update program
                        (device_replay.make_device_ingest_update)
    serving_dispatch    PolicyEngine.act on a warmed bucket, including
                        a mid-stream hot-swap (prepare_params →
                        checkpoint.uncommit) that must not recompile
    serving_overlap     the same act budget measured through a RUNNING
                        MicroBatcher with max_inflight=2 (ISSUE 17):
                        flight workers dispatch, so the overlapped
                        machinery must add NO device work per act
    serving_proxy_hop   one FleetProxy relay to a stub-engine replica
                        gateway: an ALL-ZERO budget — the proxy hop
                        carries no device state at all
    mixture_fleet_step  the heterogeneous mixture fleet's fused scan
                        block — zero transfers, one dispatch per call

**Reverted modes** prove the meter works, deterministically on every
run: `revert="host-gather"` re-introduces the pre-PR-13 per-block host
gather (device_get + re-upload inside the learner scope) and must blow
the device plane's transfer budget; `revert="unfused"` splits the
ISSUE-19 fused consume back into an advantage program plus an update
program per block and must blow the fused plane's dispatch budget;
`revert="uncommit"` installs an
orbax-restored (committed) tree into the gateway with `prepare=False`
— dropping `checkpoint.uncommit` from the swap — and the next dispatch
must blow the 0-recompile budget (committed arrays lower byte-different
HLO; the PR 4/PR 10 class).

`quick_profile` is the sweep `scripts/tier1.sh` runs between numsan and
pytest, under its own timeout.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
from typing import Iterable, Optional

import numpy as np

PROGRAMS = (
    "ppo_update_host",
    "ppo_update_device",
    "ppo_update_fused",
    "offpolicy_ingest",
    "serving_dispatch",
    "serving_overlap",
    "serving_proxy_hop",
    "mixture_fleet_step",
)

BUDGET_KEYS = (
    "max_dispatches_per_block",
    "max_transfers_per_block",
    "max_transferred_bytes_per_block",
    "max_recompiles",
)

DEFAULT_MANIFEST_BASENAME = "perf_budgets.json"


class PerfSanError(RuntimeError):
    """A steady-state program exceeded its committed budget — or a
    reverted mode's regression was detected (the sanitizer working)."""


class ManifestError(PerfSanError):
    """The budget manifest itself is missing/malformed — a crash
    (exit 2), never a detection: a lost manifest must not read as a
    caught regression."""


def default_manifest_path(repo_root: Optional[str] = None) -> str:
    if repo_root is None:
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    return os.path.join(repo_root, DEFAULT_MANIFEST_BASENAME)


def load_manifest(path: str) -> dict:
    """The budget manifest; a missing/malformed file is a PerfSanError
    (the budgets are part of the contract — absence must not read as a
    clean run)."""
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"budget manifest {path}: {e}") from e
    if not isinstance(data, dict) or not isinstance(
        data.get("programs"), dict
    ):
        raise ManifestError(
            f"budget manifest {path}: expected "
            "{'version': 1, 'programs': {...}}"
        )
    # Strict key validation: a typo'd or dropped max_* key would
    # silently UN-GATE that counter forever — refuse loudly instead.
    allowed = set(BUDGET_KEYS) | {"transfer_guard"}
    for name, entry in data["programs"].items():
        if not isinstance(entry, dict):
            raise ManifestError(
                f"budget manifest {path}: program {name!r} entry must "
                "be an object"
            )
        unknown = sorted(set(entry) - allowed)
        missing = sorted(set(BUDGET_KEYS) - set(entry))
        if unknown or missing:
            raise ManifestError(
                f"budget manifest {path}: program {name!r} has "
                + (f"unknown key(s) {unknown} " if unknown else "")
                + (f"missing budget key(s) {missing}" if missing else "")
            )
    return data["programs"]


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Counters:
    """What one measured scope observed."""

    dispatches: int = 0
    transfers: int = 0
    transferred_bytes: int = 0
    recompiles: int = 0
    dispatch_names: dict = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        return {
            "dispatches": self.dispatches,
            "transfers": self.transfers,
            "transferred_bytes": self.transferred_bytes,
            "recompiles": self.recompiles,
            "dispatch_names": dict(
                sorted(self.dispatch_names.items())
            ),
        }


def worst_of(counters: Iterable[Counters]) -> Counters:
    """Component-wise max across measured blocks — the value a `max_*`
    budget gates (a block exceeding ONE counter must not hide behind a
    sibling block that maxed a different one)."""
    out = Counters()
    for c in counters:
        out.dispatches = max(out.dispatches, c.dispatches)
        out.transfers = max(out.transfers, c.transfers)
        out.transferred_bytes = max(
            out.transferred_bytes, c.transferred_bytes
        )
        out.recompiles = max(out.recompiles, c.recompiles)
        for name, n in c.dispatch_names.items():
            out.dispatch_names[name] = max(
                out.dispatch_names.get(name, 0), n
            )
    return out


def _tree_nbytes(tree) -> int:
    import jax

    return sum(
        int(getattr(leaf, "nbytes", 0))
        for leaf in jax.tree.leaves(tree)
    )


def _host_nbytes(tree) -> int:
    """Bytes of HOST-side leaves only — numpy arrays/scalars AND bare
    Python numbers (jax.tree.leaves flattens lists/tuples into them):
    an upload seam fed an already-device array moves nothing, but a
    per-block `jnp.asarray(env_steps)` built from a Python int crosses
    just the same and must not be invisible to the meter."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, (np.ndarray, np.generic)):
            total += int(leaf.nbytes)
        elif isinstance(leaf, (bool, int, float, complex)):
            total += int(np.asarray(leaf).nbytes)
    return total


@contextlib.contextmanager
def measure(guard: Optional[str] = None):
    """Count dispatches/transfers/bytes/recompiles for the enclosed
    block, optionally under a `jax.transfer_guard(guard)` scope.
    Yields a live `Counters` the caller reads after the block. Not
    reentrant (one funnel, one meter)."""
    import jax
    import jax.numpy as jnp
    # The jit fastpath's post-dispatch hook lives in jax's own config
    # state in the installed jax (the seam `jax_debug_nans` sets). It is
    # set globally (flight workers dispatch on their own threads) AND on
    # this thread: leaving a `jax.debug_nans(...)` scope pins the
    # thread-local value to None, which masks the global one for good.
    from jax._src.api import _post_hook_state

    from actor_critic_tpu.telemetry import profiler

    profiler.ensure_compile_introspection()
    c = Counters()
    prev_hook = _post_hook_state.get_global()

    def hook(fun, *args, **kwargs):
        c.dispatches += 1
        name = getattr(fun, "__name__", None) or "?"
        c.dispatch_names[name] = c.dispatch_names.get(name, 0) + 1
        if prev_hook is not None:
            prev_hook(fun, *args, **kwargs)

    orig_put, orig_get = jax.device_put, jax.device_get
    orig_array, orig_asarray = jnp.array, jnp.asarray

    def counting_put(x, *a, **k):
        # Only HOST-side input bytes cross; a defensive re-placement
        # of an already-device tree moves nothing and must not burn
        # the transfer budget.
        nbytes = _host_nbytes(x)
        if nbytes:
            c.transfers += 1
            c.transferred_bytes += nbytes
        return orig_put(x, *a, **k)

    def counting_get(x, *a, **k):
        # Only DEVICE-side leaves cross on a get; host numpy passed
        # through device_get is a no-op copy-out.
        nbytes = _tree_nbytes(x) - _host_nbytes(x)
        if nbytes:
            c.transfers += 1
            c.transferred_bytes += nbytes
        return orig_get(x, *a, **k)

    def counting_array(x, *a, **k):
        nbytes = _host_nbytes(x)
        if nbytes:
            c.transfers += 1
            c.transferred_bytes += nbytes
        return orig_array(x, *a, **k)

    def counting_asarray(x, *a, **k):
        nbytes = _host_nbytes(x)
        if nbytes:
            c.transfers += 1
            c.transferred_bytes += nbytes
        return orig_asarray(x, *a, **k)

    n0 = profiler.compile_event_count()
    _post_hook_state.set_global(hook)
    prev_local = _post_hook_state.swap_local(hook)
    jax.device_put, jax.device_get = counting_put, counting_get
    jnp.array, jnp.asarray = counting_array, counting_asarray
    try:
        ctx = (
            jax.transfer_guard(guard)
            if guard is not None
            else contextlib.nullcontext()
        )
        with ctx:
            yield c
    finally:
        _post_hook_state.set_global(prev_hook)
        _post_hook_state.swap_local(prev_local)
        jax.device_put, jax.device_get = orig_put, orig_get
        jnp.array, jnp.asarray = orig_array, orig_asarray
        c.recompiles = profiler.compile_event_count() - n0


def check_budget(program: str, counters: Counters, budgets: dict) -> None:
    """Raise PerfSanError when any counter exceeds the program's
    committed budget (an absent program entry is itself a violation —
    a new steady-state program must commit a budget)."""
    budget = budgets.get(program)
    if budget is None:
        raise PerfSanError(
            f"{program}: no budget entry in the manifest — every "
            "steady-state program must commit max dispatches/"
            "transfers/bytes/recompiles per block"
        )
    actuals = {
        "max_dispatches_per_block": counters.dispatches,
        "max_transfers_per_block": counters.transfers,
        "max_transferred_bytes_per_block": counters.transferred_bytes,
        "max_recompiles": counters.recompiles,
    }
    over = [
        (key, actuals[key], budget[key])
        for key in BUDGET_KEYS
        if key in budget and actuals[key] > int(budget[key])
    ]
    if over:
        detail = "; ".join(
            f"{k}: measured {a} > budget {b}" for k, a, b in over
        )
        names = ", ".join(
            f"{n}x{c}" for n, c in sorted(counters.dispatch_names.items())
        )
        raise PerfSanError(
            f"BUDGET VIOLATION in {program}: {detail} "
            f"(dispatches by program: {names or 'none'}) — either a "
            "regression re-entered the steady-state path, or a "
            "deliberate change must recommit perf_budgets.json"
        )


# ---------------------------------------------------------------------------
# shared fixtures (tiny REAL programs, compiled once per process)
# ---------------------------------------------------------------------------

_PPO_FIXTURE = None


def _ppo_fixture():
    global _PPO_FIXTURE
    if _PPO_FIXTURE is not None:
        return _PPO_FIXTURE
    import jax

    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.envs.jax_env import EnvSpec

    spec = EnvSpec(
        obs_shape=(4,), action_dim=2, discrete=True,
        obs_dtype=np.float32, can_truncate=True,
    )
    cfg = ppo.PPOConfig(
        num_envs=4, rollout_steps=8, epochs=1, num_minibatches=1,
        hidden=(16,),
    )
    key = jax.random.key(0)
    params, opt_state = ppo.init_host_params(spec, cfg, key)
    _PPO_FIXTURE = (spec, cfg, params, opt_state, key)
    return _PPO_FIXTURE


def _ppo_block(cfg, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    T, E = cfg.rollout_steps, cfg.num_envs
    obs = rng.normal(size=(T, E, 4)).astype(np.float32)
    return {
        "obs": obs,
        "action": rng.integers(0, 2, (T, E)),
        "log_prob": (rng.normal(size=(T, E)) * 0.1 - 0.69).astype(
            np.float32
        ),
        "value": rng.normal(size=(T, E)).astype(np.float32),
        "reward": np.ones((T, E), np.float32),
        "done": np.zeros((T, E), np.float32),
        "terminated": np.zeros((T, E), np.float32),
        "final_obs": obs.copy(),
        "last_obs": rng.normal(size=(E, 4)).astype(np.float32),
    }


_BLOCK_ORDER = (
    "obs", "action", "log_prob", "value", "reward", "done",
    "terminated", "final_obs", "last_obs",
)


# ---------------------------------------------------------------------------
# program exercisers
# ---------------------------------------------------------------------------


def exercise_ppo_update_host(blocks: int = 3, seed: int = 0) -> dict:
    """The async V-trace learner consuming HOST-plane blocks: the
    jnp.array per-block upload (the PR 6 copy-on-transfer contract) is
    the budgeted transfer — this program's budget PRICES the host
    plane, the device twin below removes it."""
    import jax
    import jax.numpy as jnp

    from actor_critic_tpu.algos import ppo

    spec, cfg, params, opt_state, key = _ppo_fixture()
    update = ppo.make_async_update_step(spec, cfg, correction="vtrace")

    def consume(block):
        arrays = {k: jnp.array(v) for k, v in block.items()}
        return update(
            params, opt_state, *(arrays[k] for k in _BLOCK_ORDER), key
        )

    out = consume(_ppo_block(cfg, seed))  # warm
    jax.block_until_ready(out)
    per_block = []
    for i in range(blocks):
        block = _ppo_block(cfg, seed + 1 + i)
        with measure() as c:
            out = consume(block)
            jax.block_until_ready(out)
        per_block.append(c)
    worst = worst_of(per_block)
    return {"program": "ppo_update_host", "blocks": blocks,
            "counters": worst, "per_block": per_block}


def exercise_ppo_update_device(
    blocks: int = 3, seed: int = 0, revert: Optional[str] = None
) -> dict:
    """The device-plane twin: actors enqueue int8-encoded blocks into
    the HBM ring (enqueue bytes measured separately — they are the
    actor's cost, off the learner's critical path); the learner's
    measured scope runs under transfer_guard("disallow") and must
    dispatch ONE program transferring only the explicitly staged slot
    scalar. `revert="host-gather"` re-introduces the pre-PR-13 host
    gather inside the learner scope — caught on every run."""
    import jax
    import jax.numpy as jnp

    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.data_plane import ring as dp_ring

    spec, cfg, params, opt_state, key = _ppo_fixture()
    block_spec = ppo.async_block_spec(spec, cfg, 1, "vtrace")
    ring = dp_ring.DeviceTrajRing(
        depth=2, block_spec=block_spec, codec="int8",
        register_gauge=False,
    )
    try:
        update = ppo.make_device_update_step(
            spec, cfg, ring.codecs, correction="vtrace"
        )

        def learner_consume(lease, c_slot):
            return ring.run(
                lambda state: update(
                    params, opt_state, state, c_slot, key
                )
            )

        # warm both halves
        ring.put(_ppo_block(cfg, seed), version=0)
        lease = ring.get(timeout=5.0)
        out = learner_consume(lease, jax.device_put(np.int32(lease.slot)))
        jax.block_until_ready(out)
        ring.release(lease)

        enqueue_counters, consume_counters = [], []
        for i in range(blocks):
            block = _ppo_block(cfg, seed + 1 + i)
            with measure() as ce:
                ring.put(block, version=i + 1)
            enqueue_counters.append(ce)
            lease = ring.get(timeout=5.0)
            if revert == "host-gather":
                try:
                    with measure(guard="disallow") as cc:
                        # The pre-PR-13 learner: gather the consumed
                        # slot to HOST and re-upload it — one
                        # device_get + nine jnp.array transfers per
                        # block, exactly what the device ring removed.
                        host = {
                            k: jax.device_get(v[lease.slot])
                            for k, v in ring._state.storage.items()
                        }
                        arrays = {
                            k: jnp.array(v) for k, v in host.items()
                        }
                        jax.block_until_ready(arrays)
                except PerfSanError:
                    raise
                except Exception as e:
                    # An implicit crossing tripping the transfer guard
                    # IS the detection (deterministic per program
                    # structure, like the counter path below).
                    raise PerfSanError(
                        "REVERTED MODE DETECTED: the pre-PR-13 host "
                        "gather crossed the transfer guard inside the "
                        f"device-plane learner scope ({type(e).__name__})"
                    ) from e
            else:
                slot_dev = None
                with measure(guard="disallow") as cc:
                    # The ONE sanctioned transfer: the slot index,
                    # staged explicitly so the meter sees its 4 bytes
                    # (the production driver ships the same scalar
                    # implicitly on the dispatch).
                    slot_dev = jax.device_put(np.int32(lease.slot))
                    out = learner_consume(lease, slot_dev)
                    jax.block_until_ready(out)
            ring.release(lease)
            consume_counters.append(cc)
        worst = worst_of(consume_counters)
        return {
            "program": "ppo_update_device",
            "blocks": blocks,
            "counters": worst,
            "per_block": consume_counters,
            "enqueue": worst_of(enqueue_counters),
            "enqueue_bytes_per_block": ring.bytes_per_block(),
            "host_bytes_per_block": ring.raw_bytes_per_block(),
        }
    finally:
        ring.close()


def exercise_ppo_update_fused(
    blocks: int = 3, seed: int = 0, revert: Optional[str] = None
) -> dict:
    """ISSUE 19's fused consume: gather + decode + ADVANTAGES (the
    `common.gae_targets` seam lowering through the Pallas layer) +
    update as ONE program under `correction="none"` — the same budget
    shape as ppo_update_device, now with the advantage scan inside the
    measured dispatch. `revert="unfused"` splits the advantage
    computation back out into its own jitted dispatch per block (the
    pre-ISSUE-19 two-program consume) — 2 dispatches against a budget
    of 1, caught on every run."""
    import jax
    import jax.numpy as jnp

    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.algos.common import gae_targets
    from actor_critic_tpu.data_plane import ring as dp_ring

    spec, cfg, params, opt_state, key = _ppo_fixture()
    block_spec = ppo.async_block_spec(spec, cfg, 1, "none")
    ring = dp_ring.DeviceTrajRing(
        depth=2, block_spec=block_spec, codec="fp32",
        register_gauge=False,
    )
    try:
        update = ppo.make_device_update_step(
            spec, cfg, ring.codecs, correction="none"
        )

        @jax.jit
        def advantages_only(state, c_slot):
            # The split-out advantage program the fused path removed:
            # its existence per consumed block IS the regression.
            block = dp_ring.gather_block(state, c_slot, ring.codecs)
            return gae_targets(
                block["reward"], block["value"], block["done"],
                block["bootstrap_value"], cfg.gamma, cfg.gae_lambda,
            )

        def block_for(i):
            rng = np.random.default_rng(seed + i)
            block = _ppo_block(cfg, seed + i)
            T, E = cfg.rollout_steps, cfg.num_envs
            block["final_values"] = rng.normal(size=(T, E)).astype(
                np.float32
            )
            block["bootstrap_value"] = rng.normal(size=(E,)).astype(
                np.float32
            )
            return block

        # warm both programs (the dispatch meter fires on cache hits)
        ring.put(block_for(0), version=0)
        lease = ring.get(timeout=5.0)
        slot_dev = jax.device_put(np.int32(lease.slot))
        if revert == "unfused":
            adv = ring.run(lambda s: advantages_only(s, slot_dev))
            jax.block_until_ready(adv)
        out = ring.run(
            lambda s: update(params, opt_state, s, slot_dev, key)
        )
        jax.block_until_ready(out)
        ring.release(lease)

        per_block = []
        for i in range(blocks):
            ring.put(block_for(i + 1), version=i + 1)
            lease = ring.get(timeout=5.0)
            with measure(guard="disallow") as c:
                # jaxlint: disable=transfer-discipline (the ONE
                # sanctioned transfer — the staged slot scalar, priced
                # by the meter: this IS the measurement)
                slot_dev = jax.device_put(np.int32(lease.slot))
                if revert == "unfused":
                    adv = ring.run(
                        lambda s: advantages_only(s, slot_dev)
                    )
                    # jaxlint: disable=transfer-discipline (the
                    # reverted two-dispatch shape under test — its
                    # extra fence is the regression being metered)
                    jax.block_until_ready(adv)
                out = ring.run(
                    lambda s: update(params, opt_state, s, slot_dev, key)
                )
                # jaxlint: disable=transfer-discipline (measurement
                # fence: the counter window must close on a finished
                # block, not an enqueued one)
                jax.block_until_ready(out)
            ring.release(lease)
            per_block.append(c)
        worst = worst_of(per_block)
        return {
            "program": "ppo_update_fused",
            "blocks": blocks,
            "counters": worst,
            "per_block": per_block,
        }
    finally:
        ring.close()


def exercise_offpolicy_ingest(blocks: int = 3, seed: int = 0) -> dict:
    """DDPG's fused device-plane ingest: gather + decode + scatter into
    the donated replay ring + the whole update loop, ONE program per
    consumed block (device_replay.make_device_ingest_update)."""
    import jax

    from actor_critic_tpu.algos import ddpg
    from actor_critic_tpu.data_plane import codecs as np_codecs
    from actor_critic_tpu.data_plane import device_replay
    from actor_critic_tpu.data_plane import ring as dp_ring
    from actor_critic_tpu.envs.jax_env import EnvSpec

    spec = EnvSpec(
        obs_shape=(3,), action_dim=1, discrete=False,
        obs_dtype=np.float32, can_truncate=True,
    )
    cfg = ddpg.DDPGConfig(
        num_envs=2, steps_per_iter=4, batch_size=8, warmup_steps=0,
        buffer_capacity=256, updates_per_iter=1,
    )
    block_spec = device_replay.offpolicy_block_spec(spec, cfg, 1)
    kinds = np_codecs.traj_codecs("int8", block_spec)
    ring = dp_ring.DeviceTrajRing(
        depth=2, block_spec=block_spec, codec="int8",
        register_gauge=False,
    )
    try:
        ingest = device_replay.make_device_ingest_update(
            ddpg.make_update_loop, spec.action_dim, cfg, kinds,
            max(cfg.batch_size, cfg.nstep),
        )
        learner = ddpg.init_learner((3,), 1, cfg, jax.random.key(seed))
        rng = np.random.default_rng(seed)

        def block_for(i):
            K, E = cfg.steps_per_iter, cfg.num_envs
            obs = rng.normal(size=(K, E, 3)).astype(np.float32)
            return {
                "obs": obs,
                "action": rng.uniform(-1, 1, (K, E, 1)).astype(np.float32),
                "reward": np.ones((K, E), np.float32),
                "done": np.zeros((K, E), np.float32),
                "terminated": np.zeros((K, E), np.float32),
                "final_obs": obs.copy(),
                "last_obs": obs[0].copy(),
            }

        ring.put(block_for(0), version=0)
        lease = ring.get(timeout=5.0)
        staged = jax.device_put(
            (np.int32(lease.slot), np.int32(cfg.steps_per_iter))
        )
        learner, _ = ring.run(
            lambda s: ingest(learner, s, staged[0], staged[1])
        )
        jax.block_until_ready(learner)
        ring.release(lease)

        per_block = []
        env_steps = cfg.steps_per_iter
        for i in range(blocks):
            ring.put(block_for(i + 1), version=i + 1)
            lease = ring.get(timeout=5.0)
            env_steps += cfg.steps_per_iter
            with measure(guard="disallow") as c:
                # jaxlint: disable=transfer-discipline (the sanctioned
                # slot/env-steps scalars, staged explicitly so the
                # meter prices them — this IS the measurement)
                staged = jax.device_put(
                    (np.int32(lease.slot), np.int32(env_steps))
                )
                learner, metrics = ring.run(
                    lambda s: ingest(learner, s, staged[0], staged[1])
                )
                # jaxlint: disable=transfer-discipline (measurement
                # fence: the counter window must close on a finished
                # block, not an enqueued one)
                jax.block_until_ready(learner)
            ring.release(lease)
            per_block.append(c)
        worst = worst_of(per_block)
        return {"program": "offpolicy_ingest", "blocks": blocks,
                "counters": worst, "per_block": per_block}
    finally:
        ring.close()


def exercise_serving_dispatch(
    acts: int = 4, seed: int = 0, revert: Optional[str] = None
) -> dict:
    """PolicyEngine.act on warmed buckets, including a mid-stream
    hot-swap: the budget pins dispatches/transfers/bytes per act and
    ZERO recompiles across the swap (prepare_params routes the install
    through checkpoint.uncommit). `revert="uncommit"` installs an
    orbax-restored COMMITTED tree with prepare=False — the dropped
    uncommit — and the next dispatch's recompile is caught on every
    run."""
    import tempfile

    from actor_critic_tpu.serving import engine as serving_engine
    from actor_critic_tpu.serving.policy_store import PolicyStore

    spec, cfg, _, _, _ = _ppo_fixture()
    engine = serving_engine.PolicyEngine(
        spec, cfg, algo="ppo", buckets=(1, 4), seed=seed
    )
    params = serving_engine.init_params(spec, cfg, "ppo", seed=seed)
    store = PolicyStore()
    store.register("default", engine, params, version=1)
    engine.warm(store.get("default").params)

    rng = np.random.default_rng(seed)
    sizes = [1, 4, 1, 4][:acts] or [1]

    per_act = []
    for n in sizes:
        obs = rng.normal(size=(n, 4)).astype(np.float32)
        handle = store.get("default")
        with measure(guard="disallow") as c:
            out = engine.act(handle.params, obs)
        assert out.shape[0] == n
        per_act.append(c)

    # Mid-stream hot-swap through a REAL orbax checkpoint: restore ->
    # prepare_params (uncommit) -> swap -> act, still zero recompiles.
    swap_params = serving_engine.init_params(spec, cfg, "ppo", seed=seed + 1)
    with tempfile.TemporaryDirectory(prefix="perfsan_") as root:
        from actor_critic_tpu.utils.checkpoint import Checkpointer

        with Checkpointer(root, max_to_keep=1) as ck:
            ck.save(0, {"params": swap_params}, force=True)
            ck.wait()
            restored = ck.restore({"params": params}, 0)["params"]
        store.swap(
            "default", restored,
            prepare=(revert != "uncommit"),
        )
        obs = rng.normal(size=(1, 4)).astype(np.float32)
        handle = store.get("default")
        with measure(guard="disallow") as c_swap:
            out = engine.act(handle.params, obs)
        per_act.append(c_swap)
    worst = worst_of(per_act)
    return {"program": "serving_dispatch", "acts": len(per_act),
            "counters": worst, "per_act": per_act}


def exercise_serving_overlap(acts: int = 4, seed: int = 0) -> dict:
    """The overlapped-dispatch act path (ISSUE 17 leg c): the SAME
    per-act budget as serving_dispatch, measured through a RUNNING
    `MicroBatcher` with `max_inflight=2` — packing, the 1-deep flight
    handoff, shed checks and SLO accounting are all pure host work, so
    the overlap machinery must add zero device work per act.

    Requests are serialized (one outstanding at a time), so each
    measured window holds exactly one single-row flush — the counters
    stay structural/deterministic. The dispatch runs on a FLIGHT
    thread: `jax.transfer_guard` scopes are thread-local, so the
    disallow guard is applied process-globally for the measured windows
    (explicit put/get stay sanctioned; an implicit coercion on the
    flight thread raises there and surfaces as the request's error)."""
    import jax

    from actor_critic_tpu.serving import engine as serving_engine
    from actor_critic_tpu.serving.batcher import MicroBatcher
    from actor_critic_tpu.serving.policy_store import PolicyStore

    spec, cfg, _, _, _ = _ppo_fixture()
    engine = serving_engine.PolicyEngine(
        spec, cfg, algo="ppo", buckets=(1, 4), seed=seed
    )
    params = serving_engine.init_params(spec, cfg, "ppo", seed=seed)
    store = PolicyStore()
    store.register("default", engine, params, version=1)
    engine.warm(store.get("default").params)
    batcher = MicroBatcher(store, max_wait_us=200.0, max_inflight=2)

    rng = np.random.default_rng(seed)
    per_act = []
    jax.config.update("jax_transfer_guard", "disallow")
    try:
        for _ in range(max(acts, 1)):
            obs = rng.normal(size=(1, 4)).astype(np.float32)
            with measure() as c:
                req = batcher.submit(obs, "default")
                if not req.done.wait(timeout=30.0):
                    raise PerfSanError(
                        "serving_overlap: flight dispatch never "
                        "completed (overlap machinery wedged)"
                    )
                if req.error is not None:
                    raise req.error
            per_act.append(c)
    finally:
        jax.config.update("jax_transfer_guard", "allow")
        batcher.close()
    worst = worst_of(per_act)
    return {"program": "serving_overlap", "acts": len(per_act),
            "counters": worst, "per_act": per_act}


def exercise_serving_proxy_hop(relays: int = 4, seed: int = 0) -> dict:
    """One FleetProxy relay to a single stub-engine replica gateway,
    over real HTTP on loopback: the budget is ALL-ZERO — the fronting
    proxy carries no device state, so a dispatch, transfer, or
    recompile showing up in a relay window means device work leaked
    into the scale-out hop (the whole point of fronting with a dumb
    relay instead of a second engine)."""
    import http.client
    import json as _json

    from actor_critic_tpu.serving.fleet_proxy import FleetProxy
    from actor_critic_tpu.serving.gateway import ServeGateway
    from actor_critic_tpu.serving.policy_store import PolicyStore

    class _StubEngine:
        max_rows = 8

        def prepare_params(self, params):
            return params

        def act(self, params, obs):
            return np.asarray(obs)[:, 0]

    store = PolicyStore()
    store.register("default", _StubEngine(), {"w": np.ones((1,), np.float32)})
    gateway = ServeGateway(store, port=0)
    proxy = FleetProxy([gateway.url], port=0, probe=False)
    rng = np.random.default_rng(seed)
    per_relay = []
    try:
        conn = http.client.HTTPConnection(proxy.host, proxy.port, timeout=10)
        body0 = _json.dumps(
            {"obs": rng.normal(size=(1, 4)).astype(np.float32).tolist()}
        )
        # Unmetered warm relay: first contact pays connection setup on
        # both hops; steady-state is what the budget prices.
        conn.request("POST", "/v1/act", body0,
                     {"Content-Type": "application/json"})
        conn.getresponse().read()
        for _ in range(max(relays, 1)):
            body = _json.dumps(
                {"obs": rng.normal(size=(1, 4)).astype(np.float32).tolist()}
            )
            with measure() as c:
                conn.request("POST", "/v1/act", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                payload = _json.loads(resp.read())
            if resp.status != 200:
                raise PerfSanError(
                    f"serving_proxy_hop: relay answered {resp.status}: "
                    f"{payload}"
                )
            per_relay.append(c)
        conn.close()
    finally:
        proxy.close()
        gateway.close()
    worst = worst_of(per_relay)
    return {"program": "serving_proxy_hop", "relays": len(per_relay),
            "counters": worst, "per_relay": per_relay}


def exercise_mixture_fleet_step(
    calls: int = 3, seed: int = 0, iters_per_call: int = 4
) -> dict:
    """The heterogeneous mixture fleet's fused scan block (ISSUE 11's
    one-XLA-program contract): the whole train state stays device-
    resident and donated — one dispatch per call, zero transfers, under
    transfer_guard("disallow")."""
    from functools import partial

    import jax

    from actor_critic_tpu.algos import a2c
    from actor_critic_tpu.envs import make_mixture

    env = make_mixture("cartpole,pendulum")
    cfg = a2c.A2CConfig(num_envs=8, rollout_steps=4)
    state = a2c.init_state(env, cfg, jax.random.key(seed))
    train_step = a2c.make_train_step(env, cfg)

    @partial(jax.jit, donate_argnums=0)
    def block(s):
        def body(carry, _):
            carry, _m = train_step(carry)
            return carry, None

        s, _ = jax.lax.scan(body, s, None, length=iters_per_call)
        return s

    state = block(state)  # warm
    jax.block_until_ready(state)
    per_call = []
    for _ in range(calls):
        with measure(guard="disallow") as c:
            state = block(state)
            # jaxlint: disable=transfer-discipline (measurement fence:
            # the counter window must close on a finished block)
            jax.block_until_ready(state)
        per_call.append(c)
    worst = worst_of(per_call)
    return {"program": "mixture_fleet_step", "calls": calls,
            "counters": worst, "per_call": per_call}


# ---------------------------------------------------------------------------
# the budgeted sweep + reverted modes
# ---------------------------------------------------------------------------

_EXERCISERS = {
    "ppo_update_host": exercise_ppo_update_host,
    "ppo_update_device": exercise_ppo_update_device,
    "ppo_update_fused": exercise_ppo_update_fused,
    "offpolicy_ingest": exercise_offpolicy_ingest,
    "serving_dispatch": exercise_serving_dispatch,
    "serving_overlap": exercise_serving_overlap,
    "serving_proxy_hop": exercise_serving_proxy_hop,
    "mixture_fleet_step": exercise_mixture_fleet_step,
}


def run_program(
    name: str, budgets: dict, seed: int = 0
) -> dict:
    """One program end to end: exercise, then gate on its budget. The
    device-plane program additionally gates its actor-side enqueue
    bytes (`ppo_update_device.enqueue` manifest entry)."""
    report = _EXERCISERS[name](seed=seed)
    check_budget(name, report["counters"], budgets)
    if name == "ppo_update_device" and "ppo_update_device.enqueue" in budgets:
        check_budget(
            "ppo_update_device.enqueue", report["enqueue"], budgets
        )
    return report


def quick_profile(
    manifest_path: Optional[str] = None,
    seed: int = 0,
    programs: Iterable[str] = PROGRAMS,
) -> dict:
    """The tier-1 sweep: every steady-state program measured against
    the committed manifest. Counters are structural (fixed shapes,
    fixed programs), so the actuals are bit-identical run to run — a
    violation names the program, the counter, and the per-program
    dispatch breakdown."""
    budgets = load_manifest(
        manifest_path or default_manifest_path()
    )
    out: dict = {"programs": {}, "violations": 0}
    for name in programs:
        report = run_program(name, budgets, seed=seed)
        entry = {
            "actuals": report["counters"].as_dict(),
            "budget": budgets.get(name, {}),
        }
        if "enqueue" in report:
            entry["enqueue_actuals"] = report["enqueue"].as_dict()
            entry["enqueue_bytes_per_block"] = report[
                "enqueue_bytes_per_block"
            ]
            entry["host_bytes_per_block"] = report[
                "host_bytes_per_block"
            ]
        out["programs"][name] = entry
    return out


def run_reverted(mode: str, manifest_path: Optional[str] = None) -> None:
    """Reverted-regression modes — each must raise PerfSanError on
    EVERY run (the deterministic detection the ISSUE requires):

    - "host-gather": the pre-PR-13 per-block host gather inside the
      device-plane learner scope → transfer-budget violation;
    - "unfused": the pre-ISSUE-19 two-program consume (advantage scan
      dispatched separately from the update) → dispatch-budget
      violation;
    - "uncommit": a gateway swap installing a committed orbax restore
      with prepare=False → recompile-budget violation.
    """
    budgets = load_manifest(manifest_path or default_manifest_path())
    if mode == "host-gather":
        report = exercise_ppo_update_device(revert="host-gather")
        check_budget("ppo_update_device", report["counters"], budgets)
        raise PerfSanError(
            "host-gather revert escaped the transfer budget — the "
            "meter is blind"
        )
    if mode == "unfused":
        report = exercise_ppo_update_fused(revert="unfused")
        check_budget("ppo_update_fused", report["counters"], budgets)
        raise PerfSanError(
            "unfused revert escaped the dispatch budget — the "
            "meter is blind"
        )
    if mode == "uncommit":
        report = exercise_serving_dispatch(revert="uncommit")
        check_budget("serving_dispatch", report["counters"], budgets)
        raise PerfSanError(
            "uncommit revert escaped the recompile budget — the "
            "meter is blind"
        )
    raise PerfSanError(f"unknown reverted mode {mode!r}")
