"""Per-config benchmark suite (BASELINE.json:7-11; BASELINE.md).

Each bench prints one JSON line {"metric", "value", "unit", ...}. The
headline A2C number is also what repo-root bench.py reports for the
driver. Usage:

    python bench/suite.py            # all throughput benches
    python bench/suite.py a2c impala # subset

Throughput benches fuse many train iterations per dispatch (lax.scan) so
the per-dispatch host cost is amortized; host-env benches measure
the real host-stepping path (the wall-clock-limiting one on this 1-core
host, SURVEY.md §7.0).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _fused_steps_per_sec(mod, env, cfg, steps_per_iter, iters_per_call=20, calls=5):
    state = mod.init_state(env, cfg, jax.random.key(0))
    train_step = mod.make_train_step(env, cfg)

    def block(s):
        def body(c, _):
            c, _m = train_step(c)
            return c, None

        s, _ = jax.lax.scan(body, s, None, length=iters_per_call)
        return s

    run = jax.jit(block, donate_argnums=0)
    state = run(state)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(calls):
        state = run(state)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    return calls * iters_per_call * steps_per_iter / dt


def _xla_flops_per_iter(mod, env, cfg):
    """Exact per-iteration FLOPs of the fused train step, from XLA's own
    cost model (`Compiled.cost_analysis()['flops']`) on the program that
    actually runs — no hand conv arithmetic to drift out of date
    (VERDICT round 4, missing #5: throughput rows must carry enough
    FLOPs accounting to be believed or disbelieved on sight). Raises
    when the program does not compile or the backend reports no FLOPs:
    a throughput row without its FLOPs is the row S1 exists to refuse."""
    state = mod.init_state(env, cfg, jax.random.key(0))
    compiled = jax.jit(mod.make_train_step(env, cfg)).lower(state).compile()
    flops = float(compiled.cost_analysis()["flops"])
    if flops <= 0:
        raise RuntimeError(f"cost_analysis() reports flops={flops}")
    return flops


def bench_a2c():
    from actor_critic_tpu.algos import a2c
    from actor_critic_tpu.envs import make_cartpole

    cfg = a2c.A2CConfig(num_envs=4096, rollout_steps=32)
    sps = _fused_steps_per_sec(
        a2c, make_cartpole(), cfg, cfg.num_envs * cfg.rollout_steps,
        iters_per_call=50,
    )
    return {
        "metric": "a2c_cartpole_fused_throughput",
        "value": round(sps, 1),
        "unit": "env-steps/sec/chip",
        "vs_baseline": round(sps / 1_000_000, 4),
    }


def bench_ppo():
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.envs import make_cartpole

    cfg = ppo.PPOConfig(num_envs=2048, rollout_steps=32)
    sps = _fused_steps_per_sec(
        ppo, make_cartpole(), cfg, cfg.num_envs * cfg.rollout_steps,
        iters_per_call=10,
    )
    return {
        "metric": "ppo_cartpole_fused_throughput",
        "value": round(sps, 1),
        "unit": "env-steps/sec/chip",
    }


def bench_impala():
    # Measured at the `impala_pong_learn` preset's exact settings
    # (opp_skill=0.5, frame_skip=4, 36px, E=64 T=20 — the config that
    # demonstrably learns; BASELINE.json:11), so the throughput row and
    # the learning curve describe the same program. One agent decision
    # drives frame_skip=4 physics frames.
    from actor_critic_tpu.algos import impala
    from actor_critic_tpu.config import PRESETS
    from actor_critic_tpu.envs import make_pong

    preset = PRESETS["impala_pong_learn"]
    cfg = preset.config
    env = make_pong(**preset.env_kwargs)
    sps = _fused_steps_per_sec(
        impala, env, cfg, cfg.num_envs * cfg.rollout_steps,
        iters_per_call=10, calls=3,
    )
    out = {
        # Renamed from impala_jaxpong_fused_throughput (which measured
        # default pong at E=64 T=32 in env-steps): same key would make
        # cross-round trackers compare different quantities.
        "metric": "impala_pong_learn_fused_throughput",
        "value": round(sps, 1),
        "unit": "agent-decisions/sec/chip "
                f"(x{preset.env_kwargs['frame_skip']} physics frames)",
        "config": {"num_envs": cfg.num_envs,
                   "rollout_steps": cfg.rollout_steps,
                   **preset.env_kwargs},
    }
    # Self-qualification: real conv FLOPs make this the one TPU
    # throughput row a skeptic can sanity-check. flops_per_decision
    # covers the WHOLE iteration (rollout fwd + env physics + V-trace +
    # learner fwd/bwd) straight from XLA's cost model.
    flops_iter = _xla_flops_per_iter(impala, env, cfg)
    per_decision = flops_iter / (cfg.num_envs * cfg.rollout_steps)
    implied_tflops = sps * per_decision / 1e12
    device = jax.devices()[0]
    out.update(
        platform=device.platform,
        device_kind=device.device_kind,
        flops_per_decision=round(per_decision),
        implied_tflops=round(implied_tflops, 3),
    )
    if device.platform == "tpu":
        # The program runs float32, whose silicon peak is below the bf16
        # figure — so implied_mfu against the bf16 peak is a LOWER bound
        # on implausibility: mfu >> 1 is impossible either way. A chip
        # that is not in the table raises.
        from actor_critic_tpu.utils.device_peaks import peak_bf16_tflops

        peak = peak_bf16_tflops(device.device_kind)
        out.update(
            peak_bf16_tflops=peak,
            implied_mfu=round(implied_tflops / peak, 4),
        )
    return out


def bench_sac_updates():
    """Off-policy update throughput: HBM replay sample + twin-Q/actor/alpha
    update, batch 256 (the device-side hot path of BASELINE.json:10)."""
    from actor_critic_tpu.algos import sac
    from actor_critic_tpu.envs import make_point_mass

    env = make_point_mass()
    cfg = sac.SACConfig(num_envs=32, steps_per_iter=4, batch_size=256)
    sps = _fused_steps_per_sec(
        sac, env, cfg, cfg.num_envs * cfg.steps_per_iter, iters_per_call=20
    )
    # steps/sec of the fused collect+update iteration; updates/sec is the
    # same rate divided by steps-per-iter.
    return {
        "metric": "sac_fused_env_steps",
        "value": round(sps, 1),
        "unit": "env-steps/sec/chip",
        "updates_per_sec": round(sps / (cfg.num_envs * cfg.steps_per_iter), 1),
    }


def bench_ddpg_updates():
    from actor_critic_tpu.algos import ddpg
    from actor_critic_tpu.envs import make_point_mass

    env = make_point_mass()
    cfg = ddpg.DDPGConfig(num_envs=32, steps_per_iter=4, batch_size=256)
    sps = _fused_steps_per_sec(
        ddpg, env, cfg, cfg.num_envs * cfg.steps_per_iter, iters_per_call=20
    )
    return {
        "metric": "ddpg_fused_env_steps",
        "value": round(sps, 1),
        "unit": "env-steps/sec/chip",
        "updates_per_sec": round(sps / (cfg.num_envs * cfg.steps_per_iter), 1),
    }


def bench_host_native():
    from actor_critic_tpu.envs.host_pool import HostEnvPool

    E, T = 256, 300
    out = {}
    for backend in ("native", "gym"):
        pool = HostEnvPool("CartPole-v1", E, backend=backend,
                           normalize_obs=False, normalize_reward=False)
        pool.reset()
        acts = np.zeros(E, np.int64)
        pool.step(acts)
        t0 = time.perf_counter()
        for _ in range(T):
            pool.step(acts)
        out[backend] = E * T / (time.perf_counter() - t0)
    return {
        "metric": "host_env_stepping",
        "value": round(out["native"], 1),
        "unit": "env-steps/sec (native C++)",
        "gym_baseline": round(out["gym"], 1),
        "speedup": round(out["native"] / out["gym"], 1),
    }


def bench_pallas_ops():
    """Per-op evidence for the Pallas scan kernels (round-2 verdict #5):
    time the lax.scan reference (`ops.returns`) against the Pallas
    kernels (`ops.pallas_scan`) under identical jit + block_until_ready
    fencing. The headline metric/value is the LONG-T V-trace speedup;
    the GAE pair and the short (headline-trainer) shape ride along in
    the extra fields. Every per-shape record carries the kernel tile
    each op would use (`*_kernel_block`, via pallas_scan.kernel_block) —
    0 there means the Pallas call silently fell back to lax.scan, and a
    'speedup' would be measurement noise, not kernel evidence."""
    from actor_critic_tpu.ops import pallas_scan, returns

    def timeit(fn, *args, reps=50):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps

    def shape_case(T, E):
        rng = np.random.default_rng(0)
        r = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
        d = jnp.asarray(rng.random((T, E)) < 0.02, jnp.float32)
        b = jnp.asarray(rng.normal(size=(E,)), jnp.float32)
        tlp = jnp.asarray(rng.normal(size=(T, E)) * 0.3, jnp.float32)
        blp = jnp.asarray(rng.normal(size=(T, E)) * 0.3, jnp.float32)

        gae_scan = jax.jit(lambda *a: returns.gae(*a, 0.99, 0.95))
        gae_pl = jax.jit(lambda *a: pallas_scan.gae(*a, 0.99, 0.95))
        vt_scan = jax.jit(lambda *a: returns.vtrace(*a, 0.99))
        vt_pl = jax.jit(lambda *a: pallas_scan.vtrace(*a, 0.99))
        return {
            "gae_kernel_block": pallas_scan.kernel_block("gae", T, E),
            "vtrace_kernel_block": pallas_scan.kernel_block("vtrace", T, E),
            "gae_scan_us": round(timeit(gae_scan, r, v, d, b) * 1e6, 1),
            "gae_pallas_us": round(timeit(gae_pl, r, v, d, b) * 1e6, 1),
            "vtrace_scan_us": round(timeit(vt_scan, tlp, blp, r, v, d, b) * 1e6, 1),
            "vtrace_pallas_us": round(timeit(vt_pl, tlp, blp, r, v, d, b) * 1e6, 1),
        }

    # Headline bench shape (T=32): both implementations sit at dispatch
    # latency — the Pallas win there is the FUSED trainer's elimination
    # of T sequential scan steps, not this isolated op. Long-T (the
    # IMPALA/seqpar regime) is where the per-op gap can show; T=1024 is
    # the longest T where the 11-array V-trace kernel still fits a
    # 128-lane tile in VMEM (kernel_block > 0 — larger T falls back).
    short = shape_case(32, 4096)
    long = shape_case(1024, 256)
    assert long["vtrace_kernel_block"] > 0, "vtrace kernel must engage"
    assert long["gae_kernel_block"] > 0, "gae kernel must engage"
    return {
        "metric": "pallas_vtrace_speedup_longT",
        "value": round(long["vtrace_scan_us"] / long["vtrace_pallas_us"], 2),
        "unit": "x over lax.scan (T=1024, E=256)",
        "T32_E4096": short,
        "T1024_E256": long,
        "gae_speedup_longT": round(
            long["gae_scan_us"] / long["gae_pallas_us"], 2
        ),
    }


def bench_host_pool_scaling():
    """Sharded host-pool scaling (ISSUE 2 acceptance row): steps/s of the
    SAME pool at workers ∈ {1, 2, 4} on the sleep-padded testbed env
    (envs/sleep_pad.py). The 10 ms/step sleep models a simulator bound by
    per-env WALL time (MuJoCo-shaped), not CPU, so worker overlap is
    measurable in CI on a single-core host with no accelerator — and it is
    long enough that sleep() timer slack and IPC costs (measured ~1 ms/env
    and ~5 ms/batch-step here) don't mask the overlap. The headline value
    is the workers=4 speedup over workers=1 (target >= 2x).
    """
    from actor_critic_tpu.envs.host_pool import HostEnvPool
    from actor_critic_tpu.envs.sleep_pad import QUALIFIED_ENV_ID

    E, T, sleep_s = 8, 30, 0.010
    rates = {}
    for W in (1, 2, 4):
        pool = HostEnvPool(
            QUALIFIED_ENV_ID, E, seed=0, workers=W,
            normalize_obs=False, normalize_reward=False,
            env_kwargs={"sleep_s": sleep_s},
        )
        pool.reset()
        acts = np.zeros(E, np.int64)
        pool.step(acts)  # warm the worker pipes / first-step costs
        t0 = time.perf_counter()
        for _ in range(T):
            pool.step(acts)
        rates[W] = E * T / (time.perf_counter() - t0)
        pool.close()
    return {
        "metric": "host_pool_scaling",
        "value": round(rates[4] / rates[1], 2),
        "unit": "x steps/s at workers=4 vs workers=1 (sleep-padded testbed)",
        "steps_per_s": {f"workers={w}": round(r, 1) for w, r in rates.items()},
        "speedup_w2": round(rates[2] / rates[1], 2),
        "config": {"num_envs": E, "steps": T, "sleep_s": sleep_s},
    }


def bench_async_decoupling():
    """Lockstep vs async actor–learner PPO under ONE sleep-padded
    straggler worker (ISSUE 6 acceptance row), on the CartPole/sleep_pad
    testbed (`envs/sleep_pad.py SleepPadCartPole-v0` — real CartPole
    dynamics, wall-padded steps).

    Lockstep: one sharded pool, worker 0's shard padded — every
    collection block waits for the straggler at the shard barrier, and
    every SGD step waits for collection. Async: the SAME env fleet
    partitioned per actor (actor 0 = the padded half), a bounded
    trajectory queue, V-trace-corrected learner. Both modes consume the
    same total env-steps (async runs 2x blocks at half width) and
    finish with a greedy eval, so the speedup is at comparable final
    return. The headline value is async/lockstep consumed env-steps/s
    (target >= 1.5x)."""
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.algos.host_loop import host_evaluate
    from actor_critic_tpu.envs.host_pool import HostEnvPool
    from actor_critic_tpu.envs.sleep_pad import QUALIFIED_CARTPOLE_ID
    from actor_critic_tpu.models import host_actor

    E, K, iters, pad = 8, 32, 60, 0.002
    cfg = ppo.PPOConfig(
        num_envs=E, rollout_steps=K, epochs=4, num_minibatches=4,
        lr=3e-3, hidden=(64, 64), entropy_coef=0.001,
    )

    def greedy_eval(spec, params, pool):
        greedy = host_actor.make_ppo_host_greedy(spec, cfg)
        np_params = jax.device_get(params)
        try:
            return host_evaluate(
                pool, lambda o: np.asarray(greedy(np_params, o)),
                max_steps=520,
            )
        finally:
            pool.close()

    # Lockstep: straggler worker 0 pads E/2 envs; the shard barrier
    # drags the whole batch to its pace.
    pool = HostEnvPool(
        QUALIFIED_CARTPOLE_ID, E, seed=0, workers=2,
        worker_env_kwargs=[{"sleep_s": pad}, None],
    )
    t0 = time.perf_counter()
    params, _, _ = ppo.train_host(
        pool, cfg, num_iterations=iters, seed=0, log_every=0
    )
    lock_wall = time.perf_counter() - t0
    lock_eval = greedy_eval(pool.spec, params, pool.eval_pool(8))
    pool.close()
    lock_sps = iters * K * E / lock_wall

    # Async: same fleet split per actor; the padded actor slows only
    # its own contribution. 2x blocks at E/2 = equal consumed steps.
    pools = [
        HostEnvPool(
            QUALIFIED_CARTPOLE_ID, E // 2, seed=0,
            env_kwargs={"sleep_s": pad},
        ),
        HostEnvPool(QUALIFIED_CARTPOLE_ID, E // 2, seed=100003),
    ]
    t0 = time.perf_counter()
    params, _, _ = ppo.train_host_async(
        pools, cfg, iters * 2, seed=0, log_every=0,
        updates_per_block=1, queue_depth=4, max_staleness=8,
        correction="vtrace",
    )
    async_wall = time.perf_counter() - t0
    async_eval = greedy_eval(pools[1].spec, params, pools[1].eval_pool(8))
    for p in pools:
        p.close()
    async_sps = iters * 2 * K * (E // 2) / async_wall
    return {
        "metric": "async_decoupling_speedup",
        "value": round(async_sps / lock_sps, 2),
        "unit": "x consumed env-steps/s, async vs lockstep, one "
                "sleep-padded straggler worker (equal consumed steps)",
        "lockstep": {
            "steps_per_s": round(lock_sps, 1),
            "wall_s": round(lock_wall, 2),
            "eval_return": round(float(lock_eval), 1),
        },
        "async": {
            "steps_per_s": round(async_sps, 1),
            "wall_s": round(async_wall, 2),
            "eval_return": round(float(async_eval), 1),
        },
        "config": {
            "num_envs": E, "rollout_steps": K, "iterations": iters,
            "sleep_s": pad, "correction": "vtrace",
        },
    }


def bench_update_wall():
    """Steady-state learner update wall at the host-PPO hot shape: the
    plain lockstep update program and the V-trace-corrected async one
    on an identical [K, E] CartPole-shaped block (epochs x minibatches
    in-jit), each timed with a block_until_ready fence — the
    denominator of every updates/s claim, and the corrected program's
    overhead made visible (ROADMAP 'Bench resilience': a CPU-measurable
    multi-metric record every round)."""
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.envs.jax_env import EnvSpec

    spec = EnvSpec(
        obs_shape=(4,), action_dim=2, discrete=True,
        obs_dtype=np.float32, can_truncate=True,
    )
    cfg = ppo.PPOConfig(
        num_envs=8, rollout_steps=64, epochs=4, num_minibatches=4,
        hidden=(64, 64),
    )
    T, E = cfg.rollout_steps, cfg.num_envs
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    params, opt_state = ppo.init_host_params(spec, cfg, key)
    obs = jnp.asarray(rng.normal(size=(T, E, 4)), jnp.float32)
    last_obs = jnp.asarray(rng.normal(size=(E, 4)), jnp.float32)
    args = dict(
        action=jnp.asarray(rng.integers(0, 2, (T, E))),
        log_prob=jnp.asarray(rng.normal(size=(T, E)) * 0.1 - 0.69, jnp.float32),
        value=jnp.asarray(rng.normal(size=(T, E)), jnp.float32),
        reward=jnp.ones((T, E), jnp.float32),
        done=jnp.zeros((T, E), jnp.float32),
        terminated=jnp.zeros((T, E), jnp.float32),
    )

    def timeit(update, reps=20):
        out = update(
            params, opt_state, obs, args["action"], args["log_prob"],
            args["value"], args["reward"], args["done"],
            args["terminated"], obs, last_obs, key,
        )
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = update(
                params, opt_state, obs, args["action"], args["log_prob"],
                args["value"], args["reward"], args["done"],
                args["terminated"], obs, last_obs, key,
            )
            jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps

    plain_update = ppo.make_host_update_step(spec, cfg)
    plain_s = timeit(plain_update)

    # Guard-overhead measurement (ISSUE 14 satellite): the SAME compiled
    # update plus the numguard finite-gate sweep over the updated params
    # — what a per-update gate costs. The async drivers DO pay a gate on
    # this cadence (PolicyPublisher.publish runs check_finite once per
    # published update); the checkpoint gate runs on the save cadence.
    # This row prices the per-update sweep directly so the overhead is
    # a measured number, not a guess. Trended as `update_wall.guarded_ms`.
    from actor_critic_tpu.utils import numguard

    def guarded_update(*args):
        out = plain_update(*args)
        numguard.check_finite(
            jax.device_get(out[0]), "bench finite-gate", name="params"
        )
        return out

    guarded_s = timeit(guarded_update)

    vtrace_s = timeit(
        ppo.make_async_update_step(spec, cfg, correction="vtrace")
    )

    # Device-data-plane re-measurement (ISSUE 13): the same V-trace
    # update with the block gathered + decoded from the HBM trajectory
    # ring INSIDE the program — the gather/decode prefix is the only
    # delta, so this wall is the honest denominator of the device
    # plane's updates/s (and its overhead vs the argument-fed program
    # is the in-jit staging cost).
    from actor_critic_tpu.data_plane import ring as dp_ring

    block_spec = ppo.async_block_spec(spec, cfg, 1, "vtrace")
    ring = dp_ring.DeviceTrajRing(
        depth=2, block_spec=block_spec, codec="fp32",
        register_gauge=False,
    )
    block = {
        "obs": np.asarray(obs), "action": np.asarray(args["action"]),
        "log_prob": np.asarray(args["log_prob"]),
        "value": np.asarray(args["value"]),
        "reward": np.asarray(args["reward"]),
        "done": np.asarray(args["done"]),
        "terminated": np.asarray(args["terminated"]),
        "final_obs": np.asarray(obs), "last_obs": np.asarray(last_obs),
    }
    ring.put(block, version=0)
    lease = ring.get(timeout=1.0)
    dev_update = ppo.make_device_update_step(
        spec, cfg, ring.codecs, correction="vtrace"
    )
    slot = np.int32(lease.slot)

    def dev_call():
        return ring.run(
            lambda state: dev_update(params, opt_state, state, slot, key)
        )

    out = dev_call()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    reps = 20
    for _ in range(reps):
        out = dev_call()
        jax.block_until_ready(out)
    device_s = (time.perf_counter() - t0) / reps

    # Budget-counter actuals (ISSUE 15): the SAME dispatch/transfer
    # meters perfsan gates tier-1 with, read on one fenced dispatch of
    # each program — so the wall rows above travel with the structural
    # counts that explain them (plain: 1 program, 0 transfers — the
    # args are device-resident; device-gather: 1 program, the staged
    # slot scalar's 4 bytes).
    from actor_critic_tpu.analysis import perfsan as _perfsan

    with _perfsan.measure() as c_plain:
        out = plain_update(
            params, opt_state, obs, args["action"], args["log_prob"],
            args["value"], args["reward"], args["done"],
            args["terminated"], obs, last_obs, key,
        )
        jax.block_until_ready(out)
    # Warm the staged-slot signature first: the meter reads the C++
    # fastpath's post_hook, which only fires on cache-hit dispatches —
    # a cold signature would read as zero dispatches.
    slot_dev = jax.device_put(np.int32(lease.slot))
    out = ring.run(
        lambda state: dev_update(params, opt_state, state, slot_dev, key)
    )
    jax.block_until_ready(out)
    with _perfsan.measure() as c_dev:
        slot_dev = jax.device_put(np.int32(lease.slot))
        out = ring.run(
            lambda state: dev_update(params, opt_state, state, slot_dev, key)
        )
        jax.block_until_ready(out)
    ring.release(lease)
    ring.close()

    return {
        "metric": "steady_state_update_wall",
        "value": round(plain_s * 1e3, 2),
        "dispatches_per_block": c_plain.dispatches,
        "transferred_bytes_per_block": c_plain.transferred_bytes,
        "device_dispatches_per_block": c_dev.dispatches,
        "device_transferred_bytes_per_block": c_dev.transferred_bytes,
        "unit": "ms per host-PPO update ([64, 8] block, 4 epochs x 4 "
                "minibatches, fenced)",
        "updates_per_s": round(1.0 / plain_s, 1),
        "guarded_ms": round(guarded_s * 1e3, 2),
        "guard_overhead_x": round(guarded_s / plain_s, 2),
        "vtrace_corrected_ms": round(vtrace_s * 1e3, 2),
        "vtrace_overhead_x": round(vtrace_s / plain_s, 2),
        "device_plane_ms": round(device_s * 1e3, 2),
        "device_gather_overhead_x": round(device_s / vtrace_s, 2),
    }


def bench_fused_update_wall():
    """ISSUE 19: the fused consume wall — gather + decode + advantages
    (the `common.gae_targets` seam lowering through ops/pallas_scan) +
    update as ONE device-plane program under `correction="none"` —
    against the same consume with the advantage scan split into its own
    dispatch (the pre-fusion two-program shape, perfsan's `--revert
    unfused`), plus the bf16-vs-fp32 host update walls behind
    `train.py --update-dtype`. CPU numbers run the lax fallback /
    interpret path (the *_auto contract); the TPU re-measure is the
    results/pallas_rows_tpu rider."""
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.algos.common import gae_targets
    from actor_critic_tpu.analysis import perfsan as _perfsan
    from actor_critic_tpu.data_plane import ring as dp_ring
    from actor_critic_tpu.envs.jax_env import EnvSpec

    spec = EnvSpec(
        obs_shape=(4,), action_dim=2, discrete=True,
        obs_dtype=np.float32, can_truncate=True,
    )
    cfg = ppo.PPOConfig(
        num_envs=8, rollout_steps=64, epochs=4, num_minibatches=4,
        hidden=(64, 64),
    )
    T, E = cfg.rollout_steps, cfg.num_envs
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    params, opt_state = ppo.init_host_params(spec, cfg, key)
    obs = np.asarray(rng.normal(size=(T, E, 4)), np.float32)
    block = {
        "obs": obs,
        "action": rng.integers(0, 2, (T, E)),
        "log_prob": np.asarray(rng.normal(size=(T, E)) * 0.1 - 0.69,
                               np.float32),
        "value": np.asarray(rng.normal(size=(T, E)), np.float32),
        "reward": np.ones((T, E), np.float32),
        "done": np.zeros((T, E), np.float32),
        "terminated": np.zeros((T, E), np.float32),
        "final_obs": obs.copy(),
        "last_obs": np.asarray(rng.normal(size=(E, 4)), np.float32),
        "final_values": np.asarray(rng.normal(size=(T, E)), np.float32),
        "bootstrap_value": np.asarray(rng.normal(size=(E,)), np.float32),
    }

    block_spec = ppo.async_block_spec(spec, cfg, 1, "none")
    ring = dp_ring.DeviceTrajRing(
        depth=2, block_spec=block_spec, codec="fp32",
        register_gauge=False,
    )
    ring.put(block, version=0)
    lease = ring.get(timeout=1.0)
    dev_update = ppo.make_device_update_step(
        spec, cfg, ring.codecs, correction="none"
    )
    slot = np.int32(lease.slot)

    @jax.jit
    def advantages_only(state, c_slot):
        blk = dp_ring.gather_block(state, c_slot, ring.codecs)
        return gae_targets(
            blk["reward"], blk["value"], blk["done"],
            blk["bootstrap_value"], cfg.gamma, cfg.gae_lambda,
        )

    def fused_call():
        return ring.run(
            lambda state: dev_update(params, opt_state, state, slot, key)
        )

    def unfused_call():
        adv = ring.run(lambda state: advantages_only(state, slot))
        jax.block_until_ready(adv)
        return fused_call()

    def timeit(call, reps=20):
        jax.block_until_ready(call())  # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(call())
        return (time.perf_counter() - t0) / reps

    fused_s = timeit(fused_call)
    unfused_s = timeit(unfused_call)

    # Budget-counter actuals on one fenced fused consume (the meters
    # perfsan gates tier-1 with). Warm the staged-slot signature first:
    # the meter reads the C++ fastpath's post_hook, which only fires on
    # cache-hit dispatches — the timing loop above fed a host scalar,
    # which is a different jit signature.
    slot_dev = jax.device_put(np.int32(lease.slot))
    out = ring.run(
        lambda state: dev_update(params, opt_state, state, slot_dev, key)
    )
    jax.block_until_ready(out)
    with _perfsan.measure() as c_fused:
        slot_dev = jax.device_put(np.int32(lease.slot))
        out = ring.run(
            lambda state: dev_update(params, opt_state, state, slot_dev, key)
        )
        jax.block_until_ready(out)
    ring.release(lease)
    ring.close()

    # bf16-vs-fp32 update compute (--update-dtype) on the HOST update
    # program at the same shape — params/accumulators fp32 both ways.
    dtype_walls = {}
    for mode, bf16 in (("fp32", False), ("bf16", True)):
        mcfg = dataclasses.replace(cfg, bf16_compute=bf16)
        mparams, mopt = ppo.init_host_params(spec, mcfg, key)
        update = ppo.make_host_update_step(spec, mcfg)
        # jaxlint: disable=transfer-discipline (one-time bench staging
        # per dtype mode, OUTSIDE the timed region)
        jobs = jnp.asarray(block["obs"])
        # jaxlint: disable=transfer-discipline (one-time bench staging
        # per dtype mode, OUTSIDE the timed region)
        jargs = (
            mparams, mopt, jobs, jnp.asarray(block["action"]),
            jnp.asarray(block["log_prob"]), jnp.asarray(block["value"]),
            jnp.asarray(block["reward"]), jnp.asarray(block["done"]),
            jnp.asarray(block["terminated"]), jobs,
            jnp.asarray(block["last_obs"]), key,
        )
        dtype_walls[mode] = timeit(lambda: update(*jargs))

    return {
        "metric": "fused_update_wall",
        "value": round(fused_s * 1e3, 2),
        "unit": "ms per fused device-plane consume ([64, 8] block, "
                "gather + decode + advantages + update, fenced)",
        "fused_ms": round(fused_s * 1e3, 2),
        "unfused_ms": round(unfused_s * 1e3, 2),
        "speedup_x": round(unfused_s / fused_s, 2),
        "dispatches_per_block": c_fused.dispatches,
        "transferred_bytes_per_block": c_fused.transferred_bytes,
        "fp32_ms": round(dtype_walls["fp32"] * 1e3, 2),
        "bf16_ms": round(dtype_walls["bf16"] * 1e3, 2),
        "bf16_speedup_x": round(
            dtype_walls["fp32"] / dtype_walls["bf16"], 2
        ),
    }


def bench_data_plane():
    """End-to-end async-pipeline A/B, host vs device data plane
    (ISSUE 13 acceptance row): the SAME async PPO run — two actor
    services, V-trace learner, identical consumed env-steps — once
    through the host-numpy TrajQueue (one host→device transfer per
    consumed block on the learner thread) and once through the HBM
    DeviceTrajRing (actors enqueue int8-encoded blocks at collection
    time; the learner gathers + decodes in-jit, transferring only the
    slot index).

    Testbed: every block transfer is padded with a 10 ms wall sleep
    (`transfer_pad_s`, the serving bench's dispatch-pad discipline
    standing in for a slow host<->device link) — on the host plane
    that wall lands on the LEARNER per consumed block; on the device
    plane it lands on ACTOR threads at collection time, overlapped
    with learning. That relocation is the architectural win a CPU-local
    jnp.asarray (~µs) cannot exhibit; the UNPADDED A/B rides along for
    transparency. Per-consumed-block transfer bytes are recorded for
    both planes (device consume = 0 by construction — acceptance), and
    a depth-1 `correction="none"` bitwise-equivalence check between the
    planes runs inside the record so the speed row and the correctness
    claim travel together."""
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.data_plane import ring as dp_ring
    from actor_critic_tpu.envs.host_pool import HostEnvPool

    E, K, iters, pad = 8, 32, 50, 0.010
    cfg = ppo.PPOConfig(
        num_envs=E, rollout_steps=K, epochs=4, num_minibatches=4,
        lr=3e-3, hidden=(64, 64),
    )

    def pools():
        return [
            HostEnvPool("CartPole-v1", E // 2, seed=0),
            HostEnvPool("CartPole-v1", E // 2, seed=100003),
        ]

    def run(plane: str, pad_s: float) -> float:
        ps = pools()
        try:
            t0 = time.perf_counter()
            ppo.train_host_async(
                ps, cfg, iters, seed=0, log_every=0,
                queue_depth=4, max_staleness=8, correction="vtrace",
                data_plane=plane,
                plane_codec="int8" if plane == "device" else "fp32",
                transfer_pad_s=pad_s,
            )
            return time.perf_counter() - t0
        finally:
            for p in ps:
                p.close()

    consumed = iters * K * (E // 2)

    def ab(pad_s: float) -> dict:
        host_wall = run("host", pad_s)
        device_wall = run("device", pad_s)
        host_sps = consumed / host_wall
        device_sps = consumed / device_wall
        return {
            "host": {
                "consumed_steps_per_s": round(host_sps, 1),
                "wall_s": round(host_wall, 2),
            },
            "device": {
                "consumed_steps_per_s": round(device_sps, 1),
                "wall_s": round(device_wall, 2),
            },
            "device_over_host_x": round(device_sps / host_sps, 2),
        }

    # Transfer-byte accounting straight from the ring (no estimates).
    ps = pools()
    spec = ps[0].spec
    for p in ps:
        p.close()
    block_spec = ppo.async_block_spec(spec, cfg, 2, "vtrace")
    acct = dp_ring.DeviceTrajRing(
        depth=1, block_spec=block_spec, codec="int8", register_gauge=False
    )
    bytes_row = {
        "host_per_consumed_block": acct.raw_bytes_per_block(),
        "device_per_consumed_block": 0,  # slot index only — acceptance
        "device_enqueue_per_block": acct.bytes_per_block(),
        "codec_mix": acct.codec_mix(),
    }
    # Measured actuals from perfsan's counters (ISSUE 15): the host
    # plane's per-block upload and the device plane's encoded enqueue,
    # METERED rather than computed — the same dispatch/transfer seams
    # tier-1's budget sanitizer gates, so the accounting row above and
    # the runtime meter can never drift apart silently.
    from actor_critic_tpu.analysis import perfsan as _perfsan
    from actor_critic_tpu.data_plane import ring as _ring_mod

    probe = {
        name: np.zeros(
            leaf.shape, _ring_mod.canonical_dtype(leaf.dtype)
        )
        for name, leaf in block_spec.items()
    }
    with _perfsan.measure() as c_host:
        staged = {k: jnp.array(v) for k, v in probe.items()}
        jax.block_until_ready(staged)
    with _perfsan.measure() as c_enq:
        acct.put(probe, version=0)
    bytes_row["host_measured"] = c_host.transferred_bytes
    bytes_row["host_upload_dispatches"] = c_host.dispatches
    bytes_row["enqueue_measured"] = c_enq.transferred_bytes
    acct.close()

    # Depth-1 bitwise equivalence rides in the record: the device plane
    # must be a pure relocation, not a silent algorithm change.
    eq_cfg = ppo.PPOConfig(
        num_envs=4, rollout_steps=8, epochs=2, num_minibatches=2,
        hidden=(16,),
    )

    def strict(plane: str):
        pool = HostEnvPool("CartPole-v1", 4, seed=0)
        try:
            p, o, _ = ppo.train_host_async(
                [pool], eq_cfg, 3, seed=0, log_every=0,
                queue_depth=1, correction="none", strict_lockstep=True,
                data_plane=plane, plane_codec="fp32",
            )
            return p, o
        finally:
            pool.close()

    ph, oh = strict("host")
    pd, od = strict("device")
    bitwise = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree.leaves((ph, oh)), jax.tree.leaves((pd, od))
        )
    )

    padded = ab(pad)
    raw = ab(0.0)
    return {
        "metric": "consumed_env_steps_per_s",
        "value": padded["device"]["consumed_steps_per_s"],
        "unit": "consumed env-steps/s, async PPO device data plane "
                f"({pad * 1e3:.0f} ms sleep-padded transfers; host "
                "TrajQueue A/B inline)",
        **padded,
        "raw_transfer": raw,
        "per_block_transfer_bytes": bytes_row,
        "depth1_bitwise_equal": bool(bitwise),
        "config": {
            "num_envs": E, "rollout_steps": K, "iterations": iters,
            "actors": 2, "transfer_pad_ms": pad * 1e3,
            "device_codec": "int8", "correction": "vtrace",
        },
    }


def bench_replay_sample_throughput():
    """On-device replay sampling rate, fp32 vs quantized (ROADMAP "Bench
    resilience" replay-sample-throughput; ISSUE 8 satellite): a filled
    Pendulum-shaped ring is sampled at batch 256, many draws scanned
    inside ONE jitted program (summed to force materialization), fenced
    with block_until_ready. The headline value is the MIXED-codec
    samples/s (gather + int8 decode — the path every quantized update
    pays); fp32 rides along so the decode overhead is visible, and the
    bytes/transition block carries the capacity-per-HBM-byte evidence."""
    from actor_critic_tpu import replay
    from actor_critic_tpu.algos.common import OffPolicyTransition
    from actor_critic_tpu.replay import quantize

    capacity, batch, draws, reps = 65536, 256, 64, 10
    rng = np.random.default_rng(0)
    n = capacity
    fill = OffPolicyTransition(
        obs=jnp.asarray(rng.normal(0, 2, (n, 3)), jnp.float32),
        action=jnp.asarray(np.tanh(rng.normal(size=(n, 1))), jnp.float32),
        reward=jnp.asarray(rng.normal(-5, 4, (n,)), jnp.float32),
        next_obs=jnp.asarray(rng.normal(0, 2, (n, 3)), jnp.float32),
        terminated=jnp.asarray(rng.random(n) < 0.05, jnp.float32),
        done=jnp.asarray(rng.random(n) < 0.05, jnp.float32),
    )
    example = jax.tree.map(lambda x: x[0], fill)

    def measure(mode: str) -> dict:
        # One jit per MODE (each codec spec is a different program by
        # construction); built here, outside any loop, per the
        # recompile-hazard discipline.
        codecs = quantize.offpolicy_codecs(mode)
        state = replay.add_batch(
            replay.init(example, capacity, codecs), fill, codecs
        )

        @jax.jit
        def run(state, key):
            def body(acc, k):
                s = replay.sample(state, k, batch, codecs)
                return acc + sum(
                    jnp.sum(leaf.astype(jnp.float32))
                    for leaf in jax.tree.leaves(s)
                ), None

            keys = jax.random.split(key, draws)
            acc, _ = jax.lax.scan(body, jnp.zeros(()), keys)
            return acc

        acc = run(state, jax.random.key(0))
        jax.block_until_ready(acc)
        t0 = time.perf_counter()
        for r in range(reps):
            acc = run(state, jax.random.key(r))
        jax.block_until_ready(acc)
        dt = time.perf_counter() - t0
        return {
            "samples_per_s": round(reps * draws * batch / dt, 1),
            **{k: v for k, v in quantize.capacity_report(state, codecs).items()
               if k != "capacity"},
        }

    out = {mode: measure(mode) for mode in ("fp32", "mixed")}
    return {
        "metric": "replay_sample_throughput",
        "value": out["mixed"]["samples_per_s"],
        "unit": f"sampled transitions/s (batch {batch}, mixed codec, "
                "gather+decode, fenced)",
        "fp32_samples_per_s": out["fp32"]["samples_per_s"],
        "decode_overhead_x": round(
            out["fp32"]["samples_per_s"] / out["mixed"]["samples_per_s"], 2
        ),
        "bytes_per_transition": {
            m: out[m]["bytes_per_transition"] for m in out
        },
        "capacity_multiplier_mixed": out["mixed"]["capacity_multiplier"],
        "config": {"capacity": capacity, "batch": batch, "draws": draws,
                   "reps": reps, "obs_dim": 3},
    }


def bench_scenario_fleet():
    """Scenario-universe fleet bench (ISSUE 8 + ISSUE 11 acceptance
    rows), three blocks in one record:

    1. The PR 8 homogeneous rows: >=1k CartPole instances with
       per-instance randomized physics step inside ONE fused A2C XLA
       program; uniform fleet on the same shape makes the randomization
       overhead visible.
    2. `mixture` (ISSUE 11): a heterogeneous 4-type fleet — CartPole +
       Pendulum + Acrobot + procedural maze behind the padded shared
       obs/action interface (envs/mixture.py) — in one program, plus
       each member as a homogeneous fleet at the same shape, so the
       per-type cost and the batched-`lax.switch` heterogeneity
       overhead (every instance pays the summed branch cost under vmap)
       are separately visible. `per_type_steps_per_s` feeds
       scripts/bench_trend.py's per-type sub-rows.
    3. `instance_sweep` (ISSUE 11): the mixture fleet's steps/s at
       doubling instance counts until throughput rolls over — the
       published steps/s-vs-instance-count curve. The sweep stops one
       doubling past the peak (or at BENCH_FLEET_MAX_E, default 8192)
       so a CPU run stays bounded; `truncated` records which."""
    from actor_critic_tpu.algos import a2c
    from actor_critic_tpu.envs import make_cartpole, make_mixture
    from actor_critic_tpu.envs import mixture as mixture_mod

    E, T = 2048, 32
    cfg = a2c.A2CConfig(num_envs=E, rollout_steps=T, hidden=(64,))
    rates = {}
    for name, env in (
        ("randomized", make_cartpole(randomize=0.3)),
        ("uniform", make_cartpole()),
    ):
        rates[name] = _fused_steps_per_sec(
            a2c, env, cfg, E * T, iters_per_call=10, calls=3
        )

    # --- mixture mode (ISSUE 11) ---
    members = "cartpole,pendulum,acrobot,maze"
    member_names = tuple(n for n, _ in mixture_mod.parse_mixture_spec(members))
    E_m = 1024
    cfg_m = a2c.A2CConfig(num_envs=E_m, rollout_steps=T, hidden=(64,))
    mix_env = make_mixture(members, randomize=0.3)
    mix_sps = _fused_steps_per_sec(
        a2c, mix_env, cfg_m, E_m * T, iters_per_call=5, calls=3
    )
    per_type = {}
    for name in member_names:
        env_t = mixture_mod.member_makers()[name](randomize=0.3)
        per_type[name] = round(_fused_steps_per_sec(
            a2c, env_t, cfg_m, E_m * T, iters_per_call=5, calls=3
        ), 1)
    mixture_block = {
        "steps_per_s": round(mix_sps, 1),
        "per_type_steps_per_s": per_type,
        "n_types": len(member_names),
        # Batched lax.switch computes every branch and selects, so the
        # honest overhead reference is the SUM of the members' costs at
        # this shape (1/sum(1/r_i) is the series rate of stepping each
        # homogeneous fleet in turn).
        "overhead_vs_series_x": round(
            # audited: the rates are measured steps/s of runs that
            # completed — strictly positive, neither division can be /0
            (1.0 / sum(1.0 / r for r in per_type.values())) / mix_sps, 2  # jaxlint: disable=nonfinite-hazard
        ),
    }

    # --- instance-count sweep (ISSUE 11 rollover curve) ---
    max_e = int(os.environ.get("BENCH_FLEET_MAX_E", "8192"))
    curve = {}
    peak_e, peak_sps = 0, 0.0
    e = 256
    truncated = True
    while e <= max_e:
        cfg_e = a2c.A2CConfig(num_envs=e, rollout_steps=T, hidden=(64,))
        sps = _fused_steps_per_sec(
            a2c, mix_env, cfg_e, e * T, iters_per_call=5, calls=2
        )
        curve[str(e)] = round(sps, 1)
        if sps > peak_sps:
            peak_e, peak_sps = e, sps
        elif sps < 0.85 * peak_sps:
            # Rolled over decisively: one more doubling would only
            # confirm the downslope at real CPU cost.
            truncated = False
            break
        e *= 2
    sweep = {
        "curve": curve,
        "peak_instances": peak_e,
        "peak_steps_per_s": round(peak_sps, 1),
        "truncated": truncated and e > max_e,
    }

    return {
        "metric": "scenario_fleet_throughput",
        "value": round(rates["randomized"], 1),
        "unit": f"env-steps/sec/chip ({E} domain-randomized CartPole "
                "instances, fused A2C, one XLA program)",
        "uniform_steps_per_s": round(rates["uniform"], 1),
        "randomization_overhead_x": round(
            rates["uniform"] / rates["randomized"], 2
        ),
        "mixture": mixture_block,
        "instance_sweep": sweep,
        "config": {"num_envs": E, "rollout_steps": T, "randomize": 0.3,
                   "mixture_members": members,
                   "mixture_num_envs": E_m},
    }


def bench_multihost_scaling():
    """Multi-host distributed learner scaling (ISSUE 9 acceptance row):
    the `scripts/launch_multihost.py --bench` grid — aggregate consumed
    env-steps/s of a CPU local cluster at 1/2/4 processes (sync
    all-reduce over the global mesh), the gossip/ring variant, and the
    straggler A/B in which the synchronous fleet stalls at the barrier
    while gossip degrades only the slow host. Wall-bounded runs on the
    sleep-padded CartPole testbed; headline value = sync aggregate
    speedup at 4 processes vs 1 (target >= 1.5x), with
    straggler.gossip_over_sync carrying the straggler-does-not-stall
    evidence. BENCH_MULTIHOST_DURATION overrides the per-run window
    (seconds; default 6 keeps the 6-run grid inside the cpu_metrics
    per-metric timeout)."""
    import subprocess

    launcher = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "launch_multihost.py",
    )
    duration = os.environ.get("BENCH_MULTIHOST_DURATION", "6")
    proc = subprocess.run(
        [sys.executable, launcher, "--bench", "--duration-s", duration],
        capture_output=True, text=True, check=True,
    )
    lines = [
        ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")
    ]
    return json.loads(lines[-1])


def bench_mujoco_host():
    """Raw MuJoCo host-stepping rate through HostEnvPool (E=8,
    HalfCheetah-v5) — the 1-core host bound that caps every host-env
    config's wall-clock (SURVEY.md §7.2 item 2); measured so the
    BASELINE.md MuJoCo rows have a reproducible denominator."""
    import importlib.util

    if importlib.util.find_spec("mujoco") is None:
        return {"metric": "mujoco_host_stepping", "value": 0.0,
                "unit": "env-steps/sec", "error": "mujoco not installed"}
    from actor_critic_tpu.envs.host_pool import HostEnvPool

    E, T = 8, 500
    pool = HostEnvPool(
        "HalfCheetah-v5", num_envs=E, seed=0,
        normalize_obs=True, normalize_reward=True,
    )
    pool.reset()
    acts = np.zeros((E, pool.spec.action_dim), np.float32)
    pool.step(acts)
    t0 = time.perf_counter()
    for _ in range(T):
        pool.step(acts)
    sps = E * T / (time.perf_counter() - t0)
    pool.close()
    return {
        "metric": "mujoco_host_stepping",
        "value": round(sps, 1),
        "unit": "env-steps/sec (HalfCheetah-v5, E=8, incl. normalization)",
    }


def _startup_leg(cache_dir: str) -> dict:
    """One subprocess leg of the startup bench: enable the persistent
    cache, then measure process-ready → first completed train step
    (env + state init, trace, XLA compile-or-cache-hit, first run).
    Interpreter/jax import is excluded — both legs pay it identically,
    and it is exactly the part the compile cache cannot help.

    The measured program is pixel PPO with the unrolled epoch/minibatch
    nest (the `should_unroll_update` XLA:CPU conv regime) — the
    compile-DOMINATED configuration this subsystem exists for; MLP-sized
    programs compile in ~3s against a ~4s trace+init floor the cache
    cannot touch, which would understate the win the flagship conv
    configs actually see."""
    from actor_critic_tpu.utils import compile_cache

    t0 = time.perf_counter()
    compile_cache.enable_persistent_cache(cache_dir)
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.envs import make_pong

    env = make_pong(opp_skill=0.5, frame_skip=4, size=36)
    cfg = ppo.PPOConfig(
        num_envs=8, rollout_steps=16, epochs=6, num_minibatches=2,
        hidden=(64,),
    )
    state = ppo.init_state(env, cfg, jax.random.key(0))
    step = jax.jit(ppo.make_train_step(env, cfg), donate_argnums=0)
    state, metrics = step(state)
    jax.block_until_ready(metrics)
    return {
        "first_step_s": round(time.perf_counter() - t0, 4),
        "cache": compile_cache.cache_stats(),
    }


def bench_startup_to_first_step():
    """Cold-vs-warm startup through the persistent compilation cache
    (ISSUE 4 acceptance row): two fresh subprocesses run the same
    env-init → first-train-step sequence against one cache dir — the
    first (cold) compiles and fills it, the second (warm) deserializes.
    The headline value is the cold/warm wall ratio (target >= 3x); this
    is exactly what a `run_resumable.sh` leg N>0 skips with the default
    <ckpt-dir>/xla_cache sidecar."""
    import subprocess
    import tempfile

    def leg(cache):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "_startup_leg", cache],
            capture_output=True, text=True, check=True,
        )
        return json.loads(out.stdout.strip().splitlines()[-1])

    with tempfile.TemporaryDirectory() as td:
        cache = os.path.join(td, "xla_cache")
        cold = leg(cache)
        warm = leg(cache)
    return {
        "metric": "startup_to_first_step",
        "value": round(cold["first_step_s"] / warm["first_step_s"], 2),
        "unit": "x cold/warm first-step wall (persistent XLA cache)",
        "cold_s": cold["first_step_s"],
        "warm_s": warm["first_step_s"],
        "cold_cache": cold["cache"],
        "warm_cache": warm["cache"],
    }


def bench_serving_latency():
    """Policy-serving gateway SLO bench (ISSUE 10 acceptance row):
    micro-batched act() over HTTP vs sequential batch=1 request
    handling, at saturating closed-loop concurrency on CPU.

    Both modes serve the SAME engine (PPO CartPole MLP, bucket ladder
    1..64) to the same closed-loop client fleet (scripts/serve_loadgen,
    its own subprocess so client and server Python don't share a GIL):
    micro-batched = the threaded gateway + GA3C dispatcher
    (max_wait_us=2000); sequential = `ServeGateway(threaded=False)` —
    one request handled end-to-end at a time, batch 1 per dispatch, the
    pre-GA3C predictor architecture. The headline value is
    micro/sequential actions/s (target >= 4x), with the p50/p99 curve
    of both modes and the steady-state compile count (must be 0 after
    warmup — the AOT-warm bucket contract).

    Testbed: each dispatch is padded with a 10 ms wall sleep
    (`PolicyEngine(dispatch_pad_s=...)`) modeling the host<->accelerator
    round trip of a serving deployment behind a slow link: a fixed
    per-DISPATCH cost a CPU-local jit (~0.3 ms) cannot exhibit;
    this is envs/sleep_pad.py's discipline (host_pool_scaling,
    async_decoupling) pointed at serving. The pad is exactly the cost
    micro-batching amortizes, so it is what makes the A/B meaningful on
    a 2-core host; the UNPADDED raw-dispatch A/B rides along as a
    secondary block for transparency (HTTP-envelope-bound on CPU, so
    its ratio understates the accelerator case)."""
    import subprocess

    from actor_critic_tpu import serving
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.envs import make_cartpole
    from actor_critic_tpu.telemetry import profiler

    scripts_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    )
    loadgen = os.path.join(scripts_dir, "serve_loadgen.py")
    pad_ms, concurrency, duration_s = 10.0, 32, 6.0
    buckets = (1, 2, 4, 8, 16, 32, 64)
    spec = make_cartpole().spec
    cfg = ppo.PPOConfig(hidden=(64, 64))
    params = serving.init_params(spec, cfg, "ppo", seed=0)
    profiler.ensure_compile_introspection()

    def drive(engine, threaded: bool) -> dict:
        from actor_critic_tpu.telemetry import histo

        store = serving.PolicyStore()
        # SLO class on the bench policy (ISSUE 16): the bench reports
        # the server-side burn rate and histogram-derived quantiles
        # next to the loadgen's client-side point percentiles, so a
        # trend regression shows up in the mergeable fleet metric too.
        store.register("default", engine, params, slo_ms=100.0)
        gw = serving.ServeGateway(
            store, port=0, max_wait_us=2000.0, threaded=threaded
        )
        try:
            out = subprocess.run(
                [sys.executable, loadgen, "--url", gw.url,
                 "--concurrency", str(concurrency),
                 "--duration", str(duration_s),
                 "--obs-dim", str(spec.obs_shape[0]),
                 "--json", "--timeout", "60"],
                capture_output=True, text=True, timeout=180,
            )
            if not out.stdout.strip():
                # loadgen legitimately exits non-zero when it COUNTED
                # request errors (still a measurement), so only a
                # missing report line means the subprocess itself died.
                raise RuntimeError(
                    f"loadgen produced no report (rc {out.returncode}): "
                    + (out.stderr or "").strip()[-500:]
                )
            rec = json.loads(out.stdout.strip().splitlines()[-1])
            gauge = gw.batcher.gauge()
            rec["batch_occupancy"] = gauge.get("batch_occupancy", 0.0)
            rec["slo_burn"] = gauge.get("slo_burn", 0.0)
            snap = gw.batcher.metrics.histogram_snapshots().get("default")
            for key, q in (("hist_p50_ms", 0.5), ("hist_p99_ms", 0.99)):
                v = histo.quantile(snap, q) if snap else None
                rec[key] = None if v is None else round(v, 3)
        finally:
            gw.close()
        return rec

    def ab(pad_s: float) -> dict:
        engine = serving.PolicyEngine(
            spec, cfg, algo="ppo", buckets=buckets, dispatch_pad_s=pad_s
        )
        engine.warm(engine.prepare_params(params))
        # Monotonic counter, NOT len(compile_records()): the record
        # ring caps at 256 entries and would silently undercount.
        c0 = profiler.compile_event_count()
        micro = drive(engine, threaded=True)
        seq = drive(engine, threaded=False)
        compiles = profiler.compile_event_count() - c0
        return {
            "speedup_x": round(
                micro["actions_per_s"] / max(seq["actions_per_s"], 1e-9), 2
            ),
            "micro_batched": {
                k: micro[k] for k in
                ("actions_per_s", "p50_ms", "p99_ms", "requests", "errors",
                 "batch_occupancy", "slo_burn", "hist_p50_ms",
                 "hist_p99_ms")
            },
            "sequential": {
                k: seq[k] for k in
                ("actions_per_s", "p50_ms", "p99_ms", "requests", "errors")
            },
            "steady_state_compiles": compiles,
        }

    padded = ab(pad_ms / 1e3)
    raw = ab(0.0)
    return {
        "metric": "serving_latency",
        "value": padded["speedup_x"],
        "unit": "x actions/s, micro-batched vs sequential batch=1 "
                f"({pad_ms:.0f} ms sleep-padded dispatch, closed-loop "
                f"concurrency {concurrency})",
        **padded,
        "raw_dispatch": raw,
        "config": {
            "dispatch_pad_ms": pad_ms,
            "concurrency": concurrency,
            "duration_s": duration_s,
            "buckets": list(buckets),
            "max_wait_us": 2000.0,
            "hidden": [64, 64],
        },
    }


def bench_serving_fleet_scaling():
    """Horizontal serving scale-out curve (ISSUE 17 acceptance row):
    fleet actions/s at N gateway replicas behind the `FleetProxy`
    fronting hop, same closed-loop client fleet, fixed concurrency.

    Each replica owns its engine + dispatcher (exactly the process
    shape of N `scripts/serve.py` instances; in-process here so one
    bench subprocess hosts the whole fleet), every dispatch padded with
    a 10 ms wall sleep modeling the host<->accelerator round trip
    (`serving_latency`'s testbed: the pad releases the GIL, so replica
    dispatchers genuinely overlap — what real device round trips do).
    Buckets cap at 8 rows so a single replica saturates at
    ~max_rows/pad actions/s and the curve measures DISPATCHER
    parallelism, not packing headroom. The headline value is the
    3-replica / 1-replica actions/s ratio (target >= 1.6x); per-point
    rows carry p50/p99, proxy relay stats, and the loadgen errors."""
    import subprocess

    from actor_critic_tpu import serving
    from actor_critic_tpu.algos import ppo
    from actor_critic_tpu.envs import make_cartpole

    scripts_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    )
    loadgen = os.path.join(scripts_dir, "serve_loadgen.py")
    pad_ms, concurrency, duration_s = 10.0, 32, 6.0
    buckets = (1, 2, 4, 8)
    replica_counts = (1, 2, 3)
    spec = make_cartpole().spec
    cfg = ppo.PPOConfig(hidden=(64, 64))
    params = serving.init_params(spec, cfg, "ppo", seed=0)

    def fleet_point(replicas: int) -> dict:
        gateways = []
        proxy = None
        try:
            for _ in range(replicas):
                engine = serving.PolicyEngine(
                    spec, cfg, algo="ppo", buckets=buckets,
                    dispatch_pad_s=pad_ms / 1e3,
                )
                engine.warm(engine.prepare_params(params))
                store = serving.PolicyStore()
                store.register("default", engine, params, slo_ms=100.0)
                gateways.append(
                    serving.ServeGateway(store, port=0, max_wait_us=2000.0)
                )
            proxy = serving.FleetProxy(
                [gw.url for gw in gateways], port=0, probe=False
            )
            out = subprocess.run(
                [sys.executable, loadgen, "--url", proxy.url,
                 "--concurrency", str(concurrency),
                 "--duration", str(duration_s),
                 "--obs-dim", str(spec.obs_shape[0]),
                 "--json", "--timeout", "60"],
                capture_output=True, text=True, timeout=180,
            )
            if not out.stdout.strip():
                raise RuntimeError(
                    f"loadgen produced no report (rc {out.returncode}): "
                    + (out.stderr or "").strip()[-500:]
                )
            rec = json.loads(out.stdout.strip().splitlines()[-1])
            stats = proxy.stats()
            return {
                "replicas": replicas,
                "actions_per_s": rec["actions_per_s"],
                "p50_ms": rec["p50_ms"],
                "p99_ms": rec["p99_ms"],
                "requests": rec["requests"],
                "errors": rec["errors"],
                "proxy_relayed": stats["relayed"],
                "proxy_failovers": stats["failovers"],
                "replica_forwards": [
                    r["forwards"] for r in stats["replicas"]
                ],
            }
        finally:
            if proxy is not None:
                proxy.close()
            for gw in gateways:
                gw.close()

    points = [fleet_point(r) for r in replica_counts]
    by_r = {p["replicas"]: p for p in points}
    scaling = round(
        by_r[3]["actions_per_s"] / max(by_r[1]["actions_per_s"], 1e-9), 2
    )
    return {
        "metric": "serving_fleet_scaling",
        "value": scaling,
        "unit": "x actions/s, 3 replicas vs 1 behind the fleet proxy "
                f"({pad_ms:.0f} ms sleep-padded dispatch, closed-loop "
                f"concurrency {concurrency})",
        "points": points,
        "config": {
            "dispatch_pad_ms": pad_ms,
            "concurrency": concurrency,
            "duration_s": duration_s,
            "buckets": list(buckets),
            "replica_counts": list(replica_counts),
            "max_wait_us": 2000.0,
            "hidden": [64, 64],
            "proxy_policy": "least_loaded",
        },
    }


def bench_pad_overhead():
    """Shape-stabilization tax (ISSUE 20 satellite): the dispatch wall
    of the REAL padded paths vs the same program at the exact aligned
    shape, at both pad seams padsan guards. Pallas side: `gae` at the
    ragged env batches E=7/96/200 (the kernel pads to the 128-lane tile
    and slices back) vs the already-aligned Ep width — the gap is what
    the pad/slice machinery plus the dead lanes cost. Serving side:
    `PolicyEngine.act` at a non-bucket n (backfill rows engage) vs an
    engine whose bucket IS n — the gap is what the bucket ladder costs
    per dispatch. The headline value is the WORST overhead ratio across
    all measured shapes, so a pad path quietly growing a copy (or a
    bucket ladder over-padding) trends as one number; the per-shape
    walls ride along for attribution."""
    from actor_critic_tpu import serving
    from actor_critic_tpu.algos.ddpg import DDPGConfig
    from actor_critic_tpu.envs.testbeds import make_point_mass
    from actor_critic_tpu.ops import pallas_scan

    def timeit(fn, *args, reps=30):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps * 1e6

    T = 32
    gae = jax.jit(lambda *a: pallas_scan.gae(*a, 0.99, 0.95))
    pallas = {}
    for E in (7, 96, 200):
        Ep = pallas_scan._pad_env(E)
        block = pallas_scan.kernel_block("gae", T, E)
        assert block > 0, f"gae kernel must engage at E={E}"

        def wall(width):
            rng = np.random.default_rng(width)
            r = jnp.asarray(rng.normal(size=(T, width)), jnp.float32)
            v = jnp.asarray(rng.normal(size=(T, width)), jnp.float32)
            d = jnp.asarray(rng.random((T, width)) < 0.02, jnp.float32)
            b = jnp.asarray(rng.normal(size=(width,)), jnp.float32)
            return timeit(gae, r, v, d, b)

        padded_us, exact_us = wall(E), wall(Ep)
        pallas[f"E{E}"] = {
            "padded_us": round(padded_us, 1),
            "exact_us": round(exact_us, 1),
            "pad_lanes": Ep - E,
            "kernel_block": block,
            "overhead_x": round(padded_us / max(exact_us, 1e-9), 2),
        }

    spec = make_point_mass().spec
    cfg = DDPGConfig(hidden=(16, 16))
    params = serving.init_params(spec, cfg, "ddpg", seed=0)
    ladder = (1, 2, 4, 8)
    bucketed = {}
    for n in (3, 5):
        bucket = next(b for b in ladder if b >= n)
        eng_pad = serving.PolicyEngine(
            spec, cfg, algo="ddpg", buckets=ladder
        )
        eng_exact = serving.PolicyEngine(
            spec, cfg, algo="ddpg", buckets=(n,)
        )
        eng_pad.warm(params)
        eng_exact.warm(params)
        rng = np.random.default_rng(n)
        obs = (rng.normal(size=(n, *spec.obs_shape)) * 0.7).astype(
            np.float32
        )

        def act_wall(eng, reps=50):
            eng.act(params, obs)  # act blocks (device_get), no fence
            t0 = time.perf_counter()
            for _ in range(reps):
                eng.act(params, obs)
            return (time.perf_counter() - t0) / reps * 1e6

        padded_us, exact_us = act_wall(eng_pad), act_wall(eng_exact)
        bucketed[f"n{n}"] = {
            "padded_us": round(padded_us, 1),
            "exact_us": round(exact_us, 1),
            "bucket": bucket,
            "backfill_rows": bucket - n,
            "overhead_x": round(padded_us / max(exact_us, 1e-9), 2),
        }

    worst_key, worst = max(
        [*((f"pallas {k}", v) for k, v in pallas.items()),
         *((f"serving {k}", v) for k, v in bucketed.items())],
        key=lambda kv: kv[1]["overhead_x"],
    )
    return {
        "metric": "pad_overhead",
        "value": worst["overhead_x"],
        "unit": "x padded vs exact-shape dispatch wall "
                f"(worst: {worst_key})",
        "pallas": pallas,
        "serving": bucketed,
    }


BENCHES = {
    "a2c": bench_a2c,
    "ppo": bench_ppo,
    "impala": bench_impala,
    "sac": bench_sac_updates,
    "ddpg": bench_ddpg_updates,
    "host": bench_host_native,
    "host_pool_scaling": bench_host_pool_scaling,
    "async_decoupling": bench_async_decoupling,
    "update_wall": bench_update_wall,
    "fused_update_wall": bench_fused_update_wall,
    "consumed_env_steps_per_s": bench_data_plane,
    "replay_sample_throughput": bench_replay_sample_throughput,
    "multihost_scaling": bench_multihost_scaling,
    "serving_latency": bench_serving_latency,
    "serving_fleet_scaling": bench_serving_fleet_scaling,
    "scenario_fleet": bench_scenario_fleet,
    "mujoco": bench_mujoco_host,
    "pallas": bench_pallas_ops,
    "pad_overhead": bench_pad_overhead,
    "startup_to_first_step": bench_startup_to_first_step,
}


def main(argv: list[str]) -> None:
    if argv and argv[0] == "_startup_leg":
        # Internal child entry of bench_startup_to_first_step: one
        # measured leg against the given cache dir, JSON on stdout.
        print(json.dumps(_startup_leg(argv[1])), flush=True)
        return
    names = argv or list(BENCHES)
    if len(names) > 1:
        # One subprocess per bench: sharing a process lets earlier benches'
        # device allocations depress later ones (measured 60x on the
        # replay-path benches when run after the E=4096 A2C bench).
        import subprocess

        for n in names:
            subprocess.run(
                [sys.executable, os.path.abspath(__file__), n], check=True
            )
        return
    print(json.dumps(BENCHES[names[0]]()), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
