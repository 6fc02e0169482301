"""Compile-once subsystem (utils/compile_cache.py, ISSUE 4): persistent
cache warm/cold behavior, AOT warmup signature-exactness (the loop's own
first dispatch must HIT what warmup compiled), shape-stabilized chunking
(two programs total, bit-compatible semantics), and the steady-state
compile-count regression contract: after warmup + first dispatch, zero
recompiles."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from actor_critic_tpu.telemetry import profiler
from actor_critic_tpu.utils import compile_cache


from conftest import new_compile_records as _new_records


def _require_introspection():
    if not profiler.ensure_compile_introspection():
        pytest.skip("jax compile funnel unavailable in this jax version")


# ---------------------------------------------------------------- utilities

def test_bucket_size_and_pad_to_bucket():
    assert compile_cache.bucket_size(5, (4, 8, 16)) == 8
    assert compile_cache.bucket_size(8, (4, 8, 16)) == 8
    assert compile_cache.bucket_size(0, (4,)) == 4
    with pytest.raises(ValueError):
        compile_cache.bucket_size(17, (4, 8, 16))
    with pytest.raises(ValueError):
        compile_cache.bucket_size(-1, (4,))

    x = np.arange(12, dtype=np.float32).reshape(6, 2)
    padded, mask = compile_cache.pad_to_bucket(x, (4, 8))
    assert padded.shape == (8, 2) and mask.shape == (8,)
    np.testing.assert_array_equal(padded[:6], x)
    np.testing.assert_array_equal(padded[6:], 0.0)
    np.testing.assert_array_equal(mask, [1, 1, 1, 1, 1, 1, 0, 0])
    # Exact fit: no copy semantics promised, but shape/mask must be right.
    same, mask = compile_cache.pad_to_bucket(x, (6,))
    assert same.shape == (6, 2) and mask.sum() == 6


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_resolve_cache_dir_policy(tmp_path, monkeypatch):
    """The one rule: JAX_COMPILATION_CACHE_DIR when the environment sets
    it (whatever the flag says), else the flag, else <checkout>/.jax_cache
    from ANY working directory; 'none'/'off'/'' enable nothing."""
    resolve = compile_cache.resolve_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    assert resolve() == resolve(None) == os.path.join(REPO, ".jax_cache")
    assert resolve("/x/y") == "/x/y"
    for off in ("none", "NONE", "off", ""):
        assert resolve(off) is None

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert resolve() == "/placed/from/outside"
    assert resolve("/x/y") == "/placed/from/outside"
    assert resolve("none") is None


def test_train_main_sets_no_other_cache_dir(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, a run WITH a checkpoint dir
    (the old `<ckpt-dir>/xla_cache` trigger) and an explicit
    --compile-cache-dir points jax at the environment's directory and at
    nothing else."""
    import train

    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    seen = []
    real_update = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            seen.append(value)
        return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    # temporary_cache only as the snapshot/restore of this process's cache
    # configuration, which train.main changes for good.
    with compile_cache.temporary_cache(tmp_path / "snapshot"):
        seen.clear()
        rc = train.main([
            "--algo", "a2c", "--env", "jax:two_state", "--iterations", "1",
            "--set", "num_envs=4", "--set", "rollout_steps=2",
            "--set", "hidden=8", "--quiet", "--no-warmup",
            "--metrics", str(tmp_path / "m.jsonl"),
            "--ckpt-dir", str(tmp_path / "ck"),
            "--compile-cache-dir", str(tmp_path / "flag"),
        ])
        during_main = list(seen)
    assert rc == 0
    assert during_main == [placed]
    assert not (tmp_path / "ck" / "xla_cache").exists()
    assert not (tmp_path / "flag").exists()


def test_run_resumable_fresh_spares_an_external_cache(tmp_path):
    """`run_resumable.sh --fresh` wipes the compile cache the run would
    use — unless the environment named it: a directory placed from
    outside is never this script's to delete."""
    import subprocess

    fake_bin = tmp_path / "bin"
    fake_bin.mkdir()
    (fake_bin / "python").write_text("#!/bin/sh\nexit 0\n")  # stands in for train.py
    (fake_bin / "python").chmod(0o755)
    script = os.path.join(REPO, "scripts", "run_resumable.sh")
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PATH"] = f"{fake_bin}:{env['PATH']}"

    def run(extra_env, *args):
        cache = tmp_path / "cache"
        cache.mkdir(exist_ok=True)
        (cache / "entry").write_text("compiled")
        r = subprocess.run(
            ["bash", script, "--fresh", "--preset", "a2c_cartpole", *args],
            env={**env, **extra_env}, capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0, r.stderr
        return (cache / "entry").exists()

    cache = str(tmp_path / "cache")
    # Named by the flag only: --fresh wipes it (the wipe itself works).
    assert not run({}, "--compile-cache-dir", cache)
    # Named by the environment: spared, with or without the same flag.
    assert run({"JAX_COMPILATION_CACHE_DIR": cache})
    assert run({"JAX_COMPILATION_CACHE_DIR": cache}, "--compile-cache-dir", cache)
    # 'none' never resolves to a literal directory named "none".
    assert run({}, "--compile-cache-dir", "none")


# ------------------------------------------------------- persistent cache

def test_persistent_cache_cold_then_warm(tmp_path):
    """Cold compile writes the cache (miss counted); after clearing the
    in-memory jit caches, the same program deserializes (hit counted) —
    the cross-leg mechanism `run_resumable.sh` relies on."""
    import os

    with compile_cache.temporary_cache(tmp_path / "cc") as cc_dir:
        stats0 = compile_cache.cache_stats()

        def fn(x):
            return jnp.tanh(x @ x.T).sum() + x.sum()

        x = jnp.ones((97, 53))  # unlikely-collision shape for this process
        jax.block_until_ready(jax.jit(fn)(x))
        stats1 = compile_cache.cache_stats()
        assert stats1["misses"] > stats0["misses"]
        assert any(f.endswith("-cache") for f in os.listdir(cc_dir))

        jax.clear_caches()  # "new process": in-memory jit caches gone
        jax.block_until_ready(jax.jit(fn)(x))
        stats2 = compile_cache.cache_stats()
        assert stats2["hits"] > stats1["hits"]


# --------------------------------------------------- shape-stable chunking

def _tiny_a2c():
    from actor_critic_tpu.algos import a2c
    from actor_critic_tpu.envs import make_two_state_mdp

    env = make_two_state_mdp()
    cfg = a2c.A2CConfig(num_envs=8, rollout_steps=4, hidden=(16,))
    return a2c, env, cfg


def test_chunked_step_masked_tail_matches_per_iteration():
    """The n_valid-masked bucket must advance exactly k iterations —
    same trajectory as k per-iteration dispatches from the same state —
    and report the LAST VALID iteration's metrics."""
    a2c, env, cfg = _tiny_a2c()
    raw = a2c.make_train_step(env, cfg)
    step = compile_cache.make_chunked_step(raw, 4)

    sA, _ = step(a2c.init_state(env, cfg, jax.random.key(0)), 4)
    sA, mA = step(sA, 3)  # masked: 3 valid of 4 slots

    sB, _ = step(a2c.init_state(env, cfg, jax.random.key(0)), 4)
    per_iter = jax.jit(raw)
    for _ in range(3):
        sB, mB = per_iter(sB)

    for a, b in zip(jax.tree.leaves(sA), jax.tree.leaves(sB)):
        if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )
    for k in mB:
        np.testing.assert_allclose(
            np.asarray(mA[k]), np.asarray(mB[k]), rtol=1e-4, atol=1e-6
        )


def test_chunked_step_compiles_exactly_two_programs():
    """Every partial k shares ONE masked program (the PR 3 attribution
    table's top recompile source was a fresh program per distinct static
    tail k)."""
    _require_introspection()
    a2c, env, cfg = _tiny_a2c()
    step = compile_cache.make_chunked_step(a2c.make_train_step(env, cfg), 4)
    state = a2c.init_state(env, cfg, jax.random.key(1))

    n0 = profiler.compile_event_count()
    state, _ = step(state, 4)   # full program
    state, _ = step(state, 3)   # masked program
    mid = profiler.compile_event_count()
    state, _ = step(state, 1)   # masked REUSED
    state, _ = step(state, 2)   # masked REUSED
    state, _ = step(state, 4)   # full REUSED
    assert profiler.compile_event_count() == mid, [
        r["name"] for r in _new_records(n0)
    ]
    names = [r["name"] for r in _new_records(n0)]
    assert names.count("jit_full") == 1 and names.count("jit_masked") == 1


# ------------------------------------------------------------- AOT warmup

def test_warmup_runner_contains_thunk_errors():
    ok = []
    runner = compile_cache.WarmupRunner(
        [("boom", lambda: 1 / 0), ("fine", lambda: ok.append(1))]
    ).start()
    assert runner.wait(30)
    assert "error" in runner.results[0]
    assert ok and "compile_s" in runner.results[1]


def test_fused_warmup_makes_first_dispatch_a_cache_hit(tmp_path):
    """The warmup thread AOT-compiles the chunked programs from ABSTRACT
    state; the loop's own jit objects must then funnel through as
    persistent-cache HITS — i.e. each entry point really compiles once
    (0 recompiles after warmup)."""
    _require_introspection()
    a2c, env, cfg = _tiny_a2c()
    with compile_cache.temporary_cache(tmp_path / "cc"):
        ctx = compile_cache.WarmupContext(
            algo="a2c", fused=True, spec=env.spec, cfg=cfg, env=env,
            chunk=3, iterations=7, eval_every=0,
        )
        plan = compile_cache.plan_warmup(ctx)
        assert [n for n, _ in plan] == ["a2c.make_train_step"]
        n0 = profiler.compile_event_count()
        runner = compile_cache.WarmupRunner(plan).start()
        assert runner.wait(300) and "error" not in runner.results[0], (
            runner.results
        )

        # The "live" loop builds its OWN step (fresh jit objects, same
        # HLO) — exactly what train.py's run_fused does.
        step = compile_cache.make_chunked_step(
            a2c.make_train_step(env, cfg), 3
        )
        state = a2c.init_state(env, cfg, jax.random.key(0))
        from actor_critic_tpu.utils.checkpoint import checkpointed_train

        state, _ = checkpointed_train(step, state, 7, stride=3)

    records = _new_records(n0)
    for name in ("jit_full", "jit_masked"):
        evs = [r for r in records if r["name"] == name]
        real = [r for r in evs if not r.get("cache_hit")]
        hits = [r for r in evs if r.get("cache_hit")]
        assert len(real) == 1, (name, evs)   # warmup's one true compile
        assert hits, (name, evs)             # the loop hit the cache


def test_mixture_fleet_one_program_zero_steady_state_recompiles(tmp_path):
    """ISSUE 11 acceptance: a heterogeneous mixture fleet of THREE env
    types (CartPole + Pendulum + Acrobot behind the padded shared
    interface) steps inside ONE fused XLA program — the registered
    planners AOT-compile the train step and the per-type eval, the live
    loop's first dispatches are persistent-cache hits, and steady state
    (more train iterations + typed evals across EVERY type) compiles
    NOTHING: the per-instance `lax.switch` and the traced type-id eval
    keep the whole universe on a fixed program set."""
    _require_introspection()
    from actor_critic_tpu.algos import a2c
    from actor_critic_tpu.envs import make_mixture
    from actor_critic_tpu.envs import mixture as mx

    env = make_mixture("cartpole,pendulum,acrobot", randomize=0.2)
    cfg = a2c.A2CConfig(num_envs=8, rollout_steps=2, hidden=(8,))
    with compile_cache.temporary_cache(tmp_path / "cc"):
        ctx = compile_cache.WarmupContext(
            algo="a2c", fused=True, spec=env.spec, cfg=cfg, env=env,
            eval_every=2,
        )
        plan = compile_cache.plan_warmup(ctx)
        assert [n for n, _ in plan] == [
            "a2c.make_eval_fn", "a2c.make_train_step",
            "mixture.make_typed_eval",
        ]
        n0 = profiler.compile_event_count()
        runner = compile_cache.WarmupRunner(plan).start()
        assert runner.wait(600), runner.results
        assert not [r for r in runner.results if "error" in r], runner.results

        # The live loop's own jit objects (fresh, same HLO), exactly as
        # train.py's run_fused builds them.
        step = jax.jit(a2c.make_train_step(env, cfg), donate_argnums=0)
        ev = jax.jit(a2c.make_eval_fn(env, cfg), static_argnums=(2, 3))
        typed = jax.jit(
            mx.make_typed_eval(env, a2c.make_network(env, cfg)),
            static_argnums=(3, 4),
        )
        state = a2c.init_state(env, cfg, jax.random.key(0))
        key = jax.random.key(1)
        state, _ = step(state)
        float(ev(state, key))
        for t in range(env.n_types):
            float(typed(state, key, jnp.asarray(t, jnp.int32)))
        c0 = profiler.compile_event_count()
        # Steady state: more iterations, the aggregate eval, and the
        # typed eval across every member type — zero compile events.
        for _ in range(3):
            state, _ = step(state)
        float(ev(state, key))
        for t in range(env.n_types):
            float(typed(state, key, jnp.asarray(t, jnp.int32)))
        steady = profiler.compile_event_count() - c0
        assert steady == 0, [
            r["name"] for r in profiler.compile_records()[-steady:]
        ]

    # Warmup's one true compile of the mixture train step (the ONE
    # program the whole heterogeneous fleet steps in); the live loop's
    # dispatch funneled through as a persistent-cache hit.
    records = _new_records(n0)
    step_evs = [r for r in records if "train_step" in r["name"]]
    real = [r for r in step_evs if not r.get("cache_hit")]
    assert len(real) == 1, [
        (r["name"], r.get("cache_hit")) for r in step_evs
    ]
    assert any(r.get("cache_hit") for r in step_evs), step_evs


def test_host_ppo_steady_state_zero_recompiles(tmp_path):
    """ISSUE 4 acceptance: a short host loop under the compile listener —
    every registered entry point compiles exactly once (warmup), the
    loop's first dispatch is a cache hit, and steady state (iterations
    past the second) triggers ZERO further compile events."""
    pytest.importorskip("gymnasium")
    _require_introspection()
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.envs.host_pool import HostEnvPool

    cfg = ppo.PPOConfig(
        num_envs=4, rollout_steps=8, epochs=1, num_minibatches=2,
        hidden=(16,),
    )
    pool = HostEnvPool("CartPole-v1", num_envs=4, seed=0)
    try:
        with compile_cache.temporary_cache(tmp_path / "cc"):
            ctx = compile_cache.WarmupContext(
                algo="ppo", fused=False, spec=pool.spec, cfg=cfg,
                eval_every=0, overlap=True,
            )
            plan = compile_cache.plan_warmup(ctx)
            # CartPole's MLP mirrors acting/eval on the host, so the only
            # device entry point this run dispatches is the update.
            assert [n for n, _ in plan] == ["ppo.make_host_update_step"]
            n0 = profiler.compile_event_count()
            runner = compile_cache.WarmupRunner(plan).start()
            assert runner.wait(300) and "error" not in runner.results[0], (
                runner.results
            )

            counts = {}

            def log_fn(it, m):
                counts[it] = profiler.compile_event_count()

            ppo.train_host(
                pool, cfg, num_iterations=4, log_every=1, log_fn=log_fn,
            )
    finally:
        pool.close()

    records = _new_records(n0)
    update_evs = [r for r in records if r["name"] == "jit_update"]
    real = [r for r in update_evs if not r.get("cache_hit")]
    assert len(real) == 1, update_evs   # warmup compiled it exactly once
    assert any(r.get("cache_hit") for r in update_evs), update_evs
    # Steady state: whatever one-time micro-jits iteration 1/2 paid
    # (PRNG split etc.), iterations 3..4 must compile NOTHING.
    assert counts[4] == counts[2], records


def test_quantized_ingest_warmup_steady_state_zero_recompiles(tmp_path):
    """ISSUE 8: the QUANTIZED off-policy ingest+update path keeps the
    compile-once contract — the registered `ddpg.make_host_ingest_update`
    planner derives the abstract learner tree WITH QuantStats leaves
    (replay_dtype rides the config), warmup's one true compile makes the
    live loop's first dispatch a persistent-cache hit, and repeat
    dispatches compile nothing."""
    _require_introspection()
    import jax.numpy as jnp

    from actor_critic_tpu.algos import ddpg
    from actor_critic_tpu.algos.common import OffPolicyTransition
    from actor_critic_tpu.envs.jax_env import EnvSpec

    cfg = ddpg.DDPGConfig(
        num_envs=2, steps_per_iter=4, updates_per_iter=1,
        buffer_capacity=256, batch_size=8, warmup_steps=0, hidden=(16,),
        replay_dtype="mixed",
    )
    spec = EnvSpec(obs_shape=(3,), action_dim=1, discrete=False)
    with compile_cache.temporary_cache(tmp_path / "cc"):
        ctx = compile_cache.WarmupContext(
            algo="ddpg", fused=False, spec=spec, cfg=cfg,
            eval_every=0, overlap=False,
        )
        plan = compile_cache.plan_warmup(ctx)
        ingest_entries = [
            n for n, _ in plan if n == "ddpg.make_host_ingest_update"
        ]
        assert ingest_entries, [n for n, _ in plan]
        n0 = profiler.compile_event_count()
        runner = compile_cache.WarmupRunner(
            [e for e in plan if e[0] == "ddpg.make_host_ingest_update"]
        ).start()
        assert runner.wait(300) and "error" not in runner.results[0], (
            runner.results
        )

        # The live loop's own jit objects (fresh trace, same HLO).
        ingest = ddpg.make_host_ingest_update(1, cfg)
        learner = ddpg.init_learner((3,), 1, cfg, jax.random.key(0))
        assert learner.replay.storage.obs.dtype == jnp.int8
        K, E = cfg.steps_per_iter, cfg.num_envs

        def block(seed):
            r = np.random.default_rng(seed)
            return OffPolicyTransition(
                obs=jnp.asarray(r.normal(size=(K, E, 3)), jnp.float32),
                action=jnp.asarray(r.uniform(-1, 1, (K, E, 1)), jnp.float32),
                reward=jnp.asarray(r.normal(size=(K, E)), jnp.float32),
                next_obs=jnp.asarray(r.normal(size=(K, E, 3)), jnp.float32),
                terminated=jnp.zeros((K, E), jnp.float32),
                done=jnp.zeros((K, E), jnp.float32),
            )

        counts = []
        for it in range(4):
            learner, _ = ingest(
                learner, block(it), jnp.asarray(64, jnp.int32)
            )
            jax.block_until_ready(learner.replay.quant)
            counts.append(profiler.compile_event_count())

    records = _new_records(n0)
    evs = [r for r in records if r["name"] == "jit_ingest_update"]
    real = [r for r in evs if not r.get("cache_hit")]
    assert len(real) == 1, evs          # warmup's one true compile
    assert any(r.get("cache_hit") for r in evs), evs  # live loop hit it
    # Steady state: iterations past the first compile NOTHING.
    assert counts[-1] == counts[1], records


def test_fused_device_update_steady_state_zero_recompiles(tmp_path):
    """ISSUE 19: the FUSED device-plane consume (ring gather + codec
    decode + the `common.gae_targets` advantage seam + update, one
    program under correction='none') keeps the compile-once contract —
    the registered `ppo.make_device_update_step` planner derives the
    abstract ring state, warmup's one true compile makes the live
    loop's first dispatch a persistent-cache hit, and consuming more
    blocks compiles NOTHING."""
    _require_introspection()
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.data_plane import ring as dp_ring
    from actor_critic_tpu.envs.jax_env import EnvSpec

    spec = EnvSpec(
        obs_shape=(4,), action_dim=2, discrete=True,
        obs_dtype=np.float32, can_truncate=True,
    )
    cfg = ppo.PPOConfig(
        num_envs=4, rollout_steps=8, epochs=1, num_minibatches=1,
        hidden=(16,),
    )
    with compile_cache.temporary_cache(tmp_path / "cc"):
        ctx = compile_cache.WarmupContext(
            algo="ppo", fused=False, spec=spec, cfg=cfg,
            eval_every=0, overlap=True, async_actors=1,
            async_correction="none", data_plane="device",
            plane_codec="fp32", queue_depth=2,
        )
        plan = compile_cache.plan_warmup(ctx)
        fused_entries = [
            e for e in plan if e[0] == "ppo.make_device_update_step"
        ]
        assert fused_entries, [n for n, _ in plan]
        n0 = profiler.compile_event_count()
        runner = compile_cache.WarmupRunner(fused_entries).start()
        assert runner.wait(300) and "error" not in runner.results[0], (
            runner.results
        )

        # The live loop's own jit object (fresh trace, same HLO).
        block_spec = ppo.async_block_spec(spec, cfg, 1, "none")
        ring = dp_ring.DeviceTrajRing(
            depth=2, block_spec=block_spec, codec="fp32",
            register_gauge=False,
        )
        try:
            update = ppo.make_device_update_step(
                spec, cfg, ring.codecs, correction="none"
            )
            key = jax.random.key(0)
            params, opt_state = ppo.init_host_params(spec, cfg, key)
            T, E = cfg.rollout_steps, cfg.num_envs

            def block_for(i):
                rng = np.random.default_rng(i)
                obs = rng.normal(size=(T, E, 4)).astype(np.float32)
                return {
                    "obs": obs,
                    "action": rng.integers(0, 2, (T, E)),
                    "log_prob": (
                        rng.normal(size=(T, E)) * 0.1 - 0.69
                    ).astype(np.float32),
                    "value": rng.normal(size=(T, E)).astype(np.float32),
                    "reward": np.ones((T, E), np.float32),
                    "done": np.zeros((T, E), np.float32),
                    "terminated": np.zeros((T, E), np.float32),
                    "final_obs": obs.copy(),
                    "last_obs": rng.normal(size=(E, 4)).astype(
                        np.float32
                    ),
                    "final_values": rng.normal(size=(T, E)).astype(
                        np.float32
                    ),
                    "bootstrap_value": rng.normal(size=(E,)).astype(
                        np.float32
                    ),
                }

            counts = []
            for i in range(4):
                ring.put(block_for(i), version=i)
                lease = ring.get(timeout=5.0)
                slot_dev = jax.device_put(np.int32(lease.slot))
                out = ring.run(
                    lambda s: update(params, opt_state, s, slot_dev, key)
                )
                jax.block_until_ready(out)
                ring.release(lease)
                counts.append(profiler.compile_event_count())
        finally:
            ring.close()

    records = _new_records(n0)
    evs = [r for r in records if r["name"] == "jit_device_update"]
    real = [r for r in evs if not r.get("cache_hit")]
    assert len(real) == 1, evs          # warmup's one true compile
    assert any(r.get("cache_hit") for r in evs), evs  # live loop hit it
    # Steady state: blocks past the first compile NOTHING.
    assert counts[-1] == counts[0], records


def test_restore_normalizes_for_compile_cache(tmp_path):
    """A restored state must (a) carry UNCOMMITTED, XLA-owned leaves —
    orbax's committed arrays lower byte-different HLO (per-arg
    mhlo.sharding attrs) that misses every cache entry a fresh process
    wrote, and donating restore-aliased buffers into deserialized
    executables corrupts the heap — and (b) therefore lower EXACTLY the
    fresh process's module, so resumed legs hit the fresh leg's cache."""
    from actor_critic_tpu.utils.checkpoint import Checkpointer

    a2c, env, cfg = _tiny_a2c()
    state = a2c.init_state(env, cfg, jax.random.key(0))
    with Checkpointer(tmp_path / "ck") as ck:
        ck.save(1, state, force=True)
        ck.wait()
        # Normalization is gated on a live cache (its 2x transient
        # device materialization must not tax cache-less restores of
        # replay-ring-sized states).
        with compile_cache.temporary_cache(tmp_path / "cc"):
            restored = ck.restore(state, 1)
    for leaf in jax.tree.leaves(restored):
        assert not leaf.committed
    step = compile_cache.make_chunked_step(a2c.make_train_step(env, cfg), 2)
    fresh_hlo = step.full.lower(state).as_text()
    restored_hlo = step.full.lower(restored).as_text()
    assert fresh_hlo == restored_hlo
    # And the restored values round-tripped exactly despite the clone.
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- telemetry

def test_exporter_reports_compile_cache_counters(tmp_path):
    from actor_critic_tpu.telemetry.exporter import render_metrics
    from actor_critic_tpu.telemetry.session import TelemetrySession

    s = TelemetrySession(
        tmp_path / "t", sample_resources=False, profile=False
    )
    try:
        text = render_metrics(s)
    finally:
        s.close()
    assert "actor_critic_compile_cache_hits_total" in text
    assert "actor_critic_compile_cache_misses_total" in text
    assert "actor_critic_compile_cache_enabled" in text


def test_run_report_cache_hit_attribution(tmp_path):
    import importlib.util
    import json
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "run_report",
        Path(__file__).parent.parent / "scripts" / "run_report.py",
    )
    run_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_report)

    (tmp_path / "events.jsonl").write_text(
        "".join(
            json.dumps(r) + "\n"
            for r in [
                {"ts": 1.0, "kind": "session_start"},
                {"ts": 2.0, "kind": "compile", "name": "jit_update",
                 "compile_s": 2.0},
                {"ts": 3.0, "kind": "compile", "name": "jit_update",
                 "compile_s": 0.02, "cache_hit": True},
            ]
        )
    )
    report = run_report.render(str(tmp_path))
    assert "| `jit_update` | 2 | 1 | 2.02s" in report, report
    assert "persistent-cache hit(s)" in report


# ------------------------------------------------------- serving (ISSUE 10)

def test_serving_context_plans_only_serving_planners():
    """plan_warmup runs exactly one registry side per context: a
    serving context (serving_buckets non-empty) plans ONLY the serving
    act-bucket planner — never the training update/eval programs a
    gateway process would waste startup compiling — and a training
    context never plans the serving side."""
    from actor_critic_tpu.envs import make_cartpole
    from actor_critic_tpu.algos import ppo
    import actor_critic_tpu.serving  # noqa: F401 — planner registration

    spec = make_cartpole().spec
    cfg = ppo.PPOConfig(hidden=(8,))
    serve_ctx = compile_cache.WarmupContext(
        algo="ppo", fused=False, spec=spec, cfg=cfg,
        serving_buckets=(1, 4),
    )
    names = [n for n, _ in compile_cache.plan_warmup(serve_ctx)]
    assert names == ["engine.make_act_program"]
    train_ctx = compile_cache.WarmupContext(
        algo="ppo", fused=False, spec=spec, cfg=cfg, eval_every=0,
    )
    assert "engine.make_act_program" not in [
        n for n, _ in compile_cache.plan_warmup(train_ctx)
    ]


def test_serving_steady_state_zero_recompiles(tmp_path):
    """ISSUE 10 acceptance: after the serving warmup planner AOT-
    compiles every act bucket and the engine's concrete warm pass hits
    those cache entries, steady-state serving — requests across EVERY
    bucket size, through the micro-batcher, across a hot-swap — emits
    ZERO further compile-funnel events (not even deserializations)."""
    _require_introspection()
    import numpy as np

    from actor_critic_tpu import serving
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.envs import make_cartpole

    spec = make_cartpole().spec
    cfg = ppo.PPOConfig(hidden=(8, 8))
    buckets = (1, 2, 4, 8)
    with compile_cache.temporary_cache(tmp_path / "cc"):
        ctx = compile_cache.WarmupContext(
            algo="ppo", fused=False, spec=spec, cfg=cfg,
            serving_buckets=buckets,
        )
        plan = compile_cache.plan_warmup(ctx)
        # Count via the MONOTONIC event counter, not ring indices: in a
        # full-suite run the 256-entry record ring is already at
        # capacity, so records[n0:] silently misses new entries.
        c0 = profiler.compile_event_count()
        runner = compile_cache.WarmupRunner(plan).start()
        assert runner.wait(300) and "error" not in runner.results[0], (
            runner.results
        )
        engine = serving.PolicyEngine(
            spec, cfg, algo="ppo", buckets=buckets
        )
        store = serving.PolicyStore()
        store.register(
            "default", engine, serving.init_params(spec, cfg, "ppo", 0)
        )
        engine.warm(store.get().params)
        delta = profiler.compile_event_count() - c0
        warm_records = (
            profiler.compile_records()[-delta:] if delta else []
        )
        act_real = [
            r for r in warm_records
            if "act" in r["name"] and not r.get("cache_hit")
        ]
        # The planner's one true compile per bucket; the engine's warm
        # re-traces deserialize those entries (cache hits).
        assert len(act_real) == len(buckets), warm_records

        c1 = profiler.compile_event_count()
        batcher = serving.MicroBatcher(store, max_wait_us=0.0)
        try:
            for i, rows in enumerate((1, 2, 3, 4, 5, 6, 7, 8)):
                req = batcher.submit(
                    np.zeros((rows, *spec.obs_shape), np.float32)
                )
                batcher.wait(req, timeout=30)
                if i == 3:
                    # Hot-swap mid-stream: the uncommitted-restore
                    # install path must not change the lowered HLO.
                    store.swap(
                        "default",
                        serving.init_params(spec, cfg, "ppo", 1),
                    )
        finally:
            batcher.close()
        steady = profiler.compile_event_count() - c1
        assert steady == 0, (  # 0 recompiles after warmup
            steady, profiler.compile_records()[-steady:]
        )
