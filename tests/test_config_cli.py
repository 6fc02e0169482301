"""Config/preset system + train.py CLI tests (SURVEY.md §5.6)."""

import json
import subprocess
import sys

import pytest

from actor_critic_tpu.config import (
    ALGO_CONFIGS,
    PRESETS,
    apply_overrides,
    parse_set_args,
    resolve,
)


def test_presets_cover_all_baseline_configs():
    """One preset per BASELINE.json:7-11 config (+ TD3 and A3C variants)."""
    algos = {p.algo for p in PRESETS.values()}
    assert {"a2c", "ppo", "ddpg", "td3", "sac", "impala", "a3c"} <= algos
    assert "a2c_cartpole" in PRESETS
    assert "ppo_halfcheetah" in PRESETS
    assert "sac_humanoid" in PRESETS
    assert "impala_pong" in PRESETS


def test_apply_overrides_coercion():
    from actor_critic_tpu.algos import a2c

    cfg = a2c.A2CConfig()
    out = apply_overrides(
        cfg,
        {"lr": "1e-4", "num_envs": "128", "hidden": "32,32,32",
         "normalize_adv": "true"},
    )
    assert out.lr == 1e-4
    assert out.num_envs == 128
    assert out.hidden == (32, 32, 32)
    assert out.normalize_adv is True
    assert cfg.lr != out.lr  # frozen original untouched


def test_apply_overrides_optional_and_errors():
    from actor_critic_tpu.algos import sac

    cfg = sac.SACConfig()
    out = apply_overrides(cfg, {"fixed_alpha": "0.2"})
    assert out.fixed_alpha == 0.2
    out = apply_overrides(out, {"fixed_alpha": "none"})
    assert out.fixed_alpha is None
    with pytest.raises(KeyError, match="no field"):
        apply_overrides(cfg, {"ler": "1e-4"})


def test_parse_set_args():
    assert parse_set_args(["a=1", "b=x=y"]) == {"a": "1", "b": "x=y"}
    with pytest.raises(ValueError):
        parse_set_args(["oops"])


def test_resolve_preset_with_override():
    pre = resolve("a2c_cartpole", None, None, {"num_envs": "64"})
    assert pre.algo == "a2c"
    assert pre.config.num_envs == 64


def test_resolve_algo_env_from_scratch():
    pre = resolve(None, "td3", "jax:point_mass", {})
    assert pre.config.twin_q is True  # td3_config applied
    pre = resolve(None, "a3c", "jax:pong", {})
    assert pre.config.correction == "none"
    with pytest.raises(ValueError):
        resolve(None, "a2c", None, {})
    with pytest.raises(KeyError):
        resolve("nope", None, None, {})


def test_algo_configs_constructible():
    for name, cls in ALGO_CONFIGS.items():
        cls()  # defaults must be valid


def test_env_kwargs_plumbing():
    """Preset env_kwargs flow to the env constructor; --env-set merges
    over them; changing the preset's env drops its env_kwargs."""
    from actor_critic_tpu.config import coerce_env_value, parse_env_set_args

    assert parse_env_set_args(["opp_skill=0.5", "frame_skip=4"]) == {
        "opp_skill": 0.5, "frame_skip": 4,
    }
    assert coerce_env_value("true") is True
    assert coerce_env_value("none") is None
    assert coerce_env_value("hello") == "hello"

    pre = resolve("impala_pong_learn", None, None, {})
    assert pre.env_kwargs == {"opp_skill": 0.5, "frame_skip": 4, "size": 36}
    pre = resolve("impala_pong_learn", None, None, {}, {"opp_skill": 0.75})
    assert pre.env_kwargs["opp_skill"] == 0.75
    assert pre.env_kwargs["frame_skip"] == 4
    # Pointing the preset at a different env keeps only CLI kwargs.
    pre = resolve("impala_pong_learn", None, "jax:cartpole", {}, {})
    assert pre.env_kwargs == {}

    import train as train_cli

    env, fused = train_cli.build_env(
        "jax:pong", "impala", pre.config, 0,
        env_kwargs={"opp_skill": 0.5, "frame_skip": 4, "size": 36},
    )
    assert fused
    assert env.spec.obs_shape[0] == 36  # size kwarg reached the maker
    with pytest.raises(SystemExit, match="bad --env-set"):
        train_cli.build_env(
            "jax:pong", "impala", pre.config, 0, env_kwargs={"nope": 1}
        )
    with pytest.raises(SystemExit, match="native"):
        train_cli.build_env(
            "native:CartPole-v1", "ppo", PRESETS["a2c_cartpole"].config, 0,
            env_kwargs={"x": 1},
        )


@pytest.mark.slow
def test_cli_end_to_end(tmp_path):
    """train.py runs a tiny fused job, writes JSONL + summary, resumes."""
    metrics = tmp_path / "m.jsonl"
    ckpt = tmp_path / "ck"
    cmd = [
        sys.executable, "train.py",
        "--algo", "a2c", "--env", "jax:two_state",
        "--iterations", "6", "--log-every", "2", "--quiet",
        "--set", "num_envs=8", "--set", "rollout_steps=4", "--set", "hidden=16",
        "--metrics", str(metrics),
        "--ckpt-dir", str(ckpt), "--save-every", "3",
    ]
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    import os

    env.update({k: v for k, v in os.environ.items() if k not in env})
    r = subprocess.run(cmd, capture_output=True, text=True, env=env, cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(l) for l in metrics.read_text().splitlines()]
    assert rows and rows[-1]["iter"] == 6
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["env_steps"] == 6 * 8 * 4

    # Resume: checkpoint at 6 exists, asking for 8 runs only 7..8.
    assert cmd[6] == "--iterations"
    r2 = subprocess.run(
        cmd[:7] + ["8"] + cmd[8:] + ["--resume"],
        capture_output=True, text=True, env=env, cwd="/root/repo",
    )
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from iteration 6" in r2.stdout


def test_cli_replay_dtype_flag(tmp_path):
    """--replay-dtype threads into the off-policy config (fused DDPG
    run completes with a quantized ring) and refuses algos without
    replay storage."""
    import os

    env = {"JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    env.update({k: v for k, v in os.environ.items() if k not in env})
    cmd = [
        sys.executable, "train.py",
        "--algo", "ddpg", "--env", "jax:point_mass",
        "--iterations", "2", "--log-every", "1", "--quiet",
        "--set", "num_envs=4", "--set", "steps_per_iter=2",
        "--set", "updates_per_iter=1", "--set", "buffer_capacity=64",
        "--set", "batch_size=4", "--set", "warmup_steps=0",
        "--set", "hidden=16",
        "--replay-dtype", "mixed",
        "--metrics", str(tmp_path / "m.jsonl"),
    ]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "replay_dtype': 'mixed'" in r.stdout  # config echo line

    bad = subprocess.run(
        [sys.executable, "train.py", "--algo", "a2c",
         "--env", "jax:two_state", "--iterations", "1",
         "--replay-dtype", "mixed"],
        capture_output=True, text=True, env=env, cwd="/root/repo",
    )
    assert bad.returncode != 0
    assert "no replay storage" in bad.stderr


@pytest.mark.slow
def test_cli_chunked_dispatch(tmp_path):
    """--chunk N scans N iterations per dispatch: same training
    trajectory as per-iteration dispatch (same seed, same step count),
    cadences snapped to chunk multiples, tail chunks + resume work."""
    import os

    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    env.update({k: v for k, v in os.environ.items() if k not in env})
    base = [
        sys.executable, "train.py",
        "--algo", "a2c", "--env", "jax:two_state",
        "--iterations", "8", "--log-every", "2", "--quiet",
        "--set", "num_envs=8", "--set", "rollout_steps=4", "--set", "hidden=16",
    ]

    def run(extra, metrics):
        r = subprocess.run(
            base + ["--metrics", str(metrics)] + extra,
            capture_output=True, text=True, env=env, cwd="/root/repo",
        )
        assert r.returncode == 0, r.stderr[-2000:]
        rows = [json.loads(l) for l in metrics.read_text().splitlines()]
        return r, rows

    _, rows1 = run([], tmp_path / "m1.jsonl")
    r4, rows4 = run(["--chunk", "4"], tmp_path / "m4.jsonl")
    # Cadence snap is announced and applied: rows at chunk boundaries.
    assert "log_every 2 -> 4" in r4.stdout
    assert [row["iter"] for row in rows4] == [4, 8]
    # Identical trajectory: the scanned and per-iteration loops apply
    # the same train step the same number of times from the same seed.
    last1 = {k: v for k, v in rows1[-1].items()
             if isinstance(v, float) and k != "wall_s"}
    last4 = {k: v for k, v in rows4[-1].items()
             if isinstance(v, float) and k != "wall_s"}
    assert last1.keys() == last4.keys()
    for k in last1:
        assert last1[k] == pytest.approx(last4[k], rel=2e-3, abs=1e-5), k

    # Misaligned resume: 3 done per-iteration, resume chunked to 10.
    # The first chunk realigns to stride boundaries (k=1, then 4, then a
    # tail of 2), so the snapped cadences keep firing: without
    # realignment every boundary would sit at 3 mod 4 and no
    # intermediate log/save would ever trigger again.
    ckpt = tmp_path / "ck"
    run(["--iterations", "3", "--ckpt-dir", str(ckpt), "--save-every", "3"],
        tmp_path / "mr1.jsonl")
    rr, rows_r = run(
        ["--iterations", "10", "--ckpt-dir", str(ckpt), "--save-every", "4",
         "--chunk", "4", "--resume"],
        tmp_path / "mr2.jsonl",
    )
    assert "resumed from iteration 3" in rr.stdout
    assert [row["iter"] for row in rows_r] == [4, 8, 10]


def test_resolve_preset_with_different_algo_specializes():
    """--preset X --algo Y must swap in Y's *specialized* defaults, not the
    base dataclass (td3 without twin_q would silently run DDPG)."""
    pre = resolve("ddpg_walker2d", "td3", None, {})
    assert pre.config.twin_q is True
    pre = resolve("impala_pong", "a3c", None, {})
    assert pre.config.correction == "none"


@pytest.mark.parametrize("algo,normalized", [
    ("ppo", True), ("ddpg", False), ("td3", False), ("sac", False),
])
def test_build_env_normalization_policy(algo, normalized):
    """train.py's host pools normalize obs/rewards for on-policy PPO only.
    Off-policy replay must see RAW frames: running-stat normalization
    rescales early-stored transitions differently than fresh ones and the
    critic bootstraps across inconsistent frames (observed as the SAC
    Humanoid-v5 Q/alpha runaway). Regression-pins train.py build_env."""
    import train as train_cli

    cfg = ALGO_CONFIGS[algo](num_envs=1)
    pool, fused = train_cli.build_env("host:CartPole-v1", algo, cfg, seed=0)
    try:
        assert fused is False
        assert pool.normalizes_obs is normalized
    finally:
        pool.close()


def test_build_env_scale_actions_tristate():
    """--scale-actions threads through to BOTH env families; None keeps
    each env's own convention (host pools clip, jax:pendulum scales)."""
    import train as train_cli
    from actor_critic_tpu.algos import sac

    cfg = sac.SACConfig(num_envs=1)
    pool, _ = train_cli.build_env("host:Pendulum-v1", "sac", cfg, 0)
    assert pool.scales_actions is False  # None → pool default (clip)
    pool.close()
    pool, _ = train_cli.build_env(
        "host:Pendulum-v1", "sac", cfg, 0, scale_actions=True
    )
    assert pool.scales_actions is True
    pool.close()

    import jax
    import jax.numpy as jnp
    import numpy as np

    # jax:pendulum: None → scaled (env default); False → raw torque.
    scaled, fused = train_cli.build_env("jax:pendulum", "sac", cfg, 0)
    raw, _ = train_cli.build_env(
        "jax:pendulum", "sac", cfg, 0, scale_actions=False
    )
    assert fused
    s1, _ = scaled.reset(jax.random.key(0))
    s2, _ = raw.reset(jax.random.key(0))
    o1 = scaled.step(s1, jnp.asarray([0.5], jnp.float32))  # torque 1.0
    o2 = raw.step(s2, jnp.asarray([1.0], jnp.float32))     # torque 1.0
    np.testing.assert_allclose(np.asarray(o1.obs), np.asarray(o2.obs), rtol=1e-6)


def test_check_env_convention_sidecar(tmp_path):
    """Fused-path action-convention guard: first run records the flag in
    a ckpt-dir sidecar; a resume with a flipped flag warns; matched and
    legacy (no sidecar) resumes stay silent."""
    import warnings

    import train as train_cli

    d = str(tmp_path / "ck")
    train_cli.check_env_convention(d, "jax:pendulum", None, resume=False)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        train_cli.check_env_convention(d, "jax:pendulum", None, resume=True)
        # None and explicit True are the SAME effective convention on
        # pendulum (the env scales by default) — neither may warn.
        train_cli.check_env_convention(d, "jax:pendulum", True, resume=True)
    assert not caught
    with pytest.warns(UserWarning, match="other action convention"):
        train_cli.check_env_convention(d, "jax:pendulum", False, resume=True)
    # A fresh (non-resume) run into the same dir overwrites the stale
    # sidecar, so its own resumes are checked against ITS convention.
    train_cli.check_env_convention(d, "jax:pendulum", False, resume=False)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        train_cli.check_env_convention(d, "jax:pendulum", False, resume=True)
    assert not caught
    with pytest.warns(UserWarning, match="other action convention"):
        train_cli.check_env_convention(d, "jax:pendulum", None, resume=True)
    # Legacy dir without a sidecar: resume is silent (tolerant).
    legacy = str(tmp_path / "legacy")
    import os

    os.makedirs(legacy)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        train_cli.check_env_convention(legacy, "jax:pendulum", True, resume=True)
    assert not caught
    # No ckpt dir at all: no-op.
    train_cli.check_env_convention(None, "jax:pendulum", True, resume=True)


def test_check_env_convention_env_kwargs(tmp_path):
    """The sidecar also guards env-constructor kwargs: a resume that
    changes the env's difficulty knobs warns; matched kwargs and legacy
    (pre-env-kwargs) sidecars stay silent; --env-set scale_actions on
    pendulum counts as the real convention."""
    import warnings

    import train as train_cli

    d = str(tmp_path / "ck")
    kw = {"opp_skill": 0.5, "frame_skip": 4, "size": 36}
    train_cli.check_env_convention(d, "jax:pong", None, False, env_kwargs=kw)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        train_cli.check_env_convention(d, "jax:pong", None, True, env_kwargs=kw)
    assert not caught
    with pytest.warns(UserWarning, match="different environment"):
        train_cli.check_env_convention(
            d, "jax:pong", None, True, env_kwargs={**kw, "opp_skill": 1.0}
        )
    with pytest.warns(UserWarning, match="different environment"):
        train_cli.check_env_convention(d, "jax:pong", None, True, env_kwargs={})
    # Legacy sidecar without the env_kwargs key: tolerant.
    import json as json_mod
    import os

    legacy = str(tmp_path / "legacy")
    os.makedirs(legacy)
    with open(os.path.join(legacy, "env_convention.json"), "w") as f:
        json_mod.dump({"env": "jax:pong", "scale_actions": None}, f)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        train_cli.check_env_convention(legacy, "jax:pong", None, True, env_kwargs=kw)
    assert not caught
    # --env-set scale_actions=false on pendulum IS the effective
    # convention when no CLI flag is given (mirrors build_env).
    d2 = str(tmp_path / "pend")
    train_cli.check_env_convention(
        d2, "jax:pendulum", None, False, env_kwargs={"scale_actions": False}
    )
    with pytest.warns(UserWarning, match="other action convention"):
        train_cli.check_env_convention(d2, "jax:pendulum", None, True)
    # ...and spelling the SAME convention via the CLI flag instead of
    # --env-set must stay silent (scale_actions is excluded from the
    # kwargs comparison).
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        train_cli.check_env_convention(d2, "jax:pendulum", False, True)
    assert not caught
    # Resuming into a different ENV warns even with matching kwargs.
    with pytest.warns(UserWarning, match="different environment|belongs to"):
        train_cli.check_env_convention(d2, "jax:cartpole", None, True)
    # Host runs: the scale flip is host_loop's checkpoint-metric guard's
    # job — the sidecar must NOT double-warn it (env/kwargs only).
    d3 = str(tmp_path / "host")
    train_cli.check_env_convention(d3, "host:Pendulum-v1", True, False)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        train_cli.check_env_convention(d3, "host:Pendulum-v1", None, True)
    assert not caught


def test_build_env_mixture_spec():
    """'mixture:<members>' builds the heterogeneous fleet env (ISSUE
    11): per-type weights parse from the spec, --env-set reaches the
    mixture maker, and bad members/kwargs exit with the friendly
    message."""
    import train as train_cli
    from actor_critic_tpu.envs.mixture import MixtureEnv

    cfg = PRESETS["a2c_cartpole"].config
    env, fused = train_cli.build_env(
        "mixture:cartpole*2,pendulum,acrobot", "a2c", cfg, 0,
        env_kwargs={"randomize": 0.2, "action_bins": 7},
    )
    assert fused and isinstance(env, MixtureEnv)
    assert env.member_names == ("cartpole", "pendulum", "acrobot")
    assert env.init_weights == (2.0, 1.0, 1.0)
    assert env.spec.action_dim == 7  # action_bins reached the maker
    with pytest.raises(SystemExit, match="bad mixture env"):
        train_cli.build_env("mixture:cartpole,frogger", "a2c", cfg, 0)
    with pytest.raises(SystemExit, match="bad --env-set"):
        train_cli.build_env(
            "mixture:cartpole,maze", "a2c", cfg, 0, env_kwargs={"nope": 1}
        )


def test_mixture_preset_resolves():
    pre = resolve("a2c_mixture", None, None, {})
    assert pre.env.startswith("mixture:")
    assert pre.env_kwargs == {"randomize": 0.2}


@pytest.mark.slow
def test_cli_data_plane_device_end_to_end(tmp_path):
    """train.py runs a tiny async PPO job through the device data plane
    (--data-plane device --data-plane-codec int8) end to end, and the
    summary line carries real learner metrics. Marked slow (a full
    train.py subprocess is ~10 s of mostly jax import): tier-1 covers
    the same driver path in-process (test_data_plane ckpt e2e,
    test_async_host device tests) and the flag plumbing via
    test_data_plane_flag_validation."""
    metrics = tmp_path / "m.jsonl"
    cmd = [
        sys.executable, "train.py",
        "--algo", "ppo", "--env", "host:CartPole-v1",
        "--iterations", "3", "--log-every", "1", "--quiet",
        "--set", "num_envs=4", "--set", "rollout_steps=8",
        "--set", "epochs=1", "--set", "num_minibatches=1",
        "--set", "hidden=16",
        "--async-actors", "2", "--data-plane", "device",
        "--data-plane-codec", "int8",
        "--metrics", str(metrics),
    ]
    env = {"JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    import os

    env.update({k: v for k, v in os.environ.items() if k not in env})
    r = subprocess.run(
        cmd, capture_output=True, text=True, env=env, cwd="/root/repo"
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert rows[-1]["iter"] == 3
    assert "consumed_env_steps" in rows[-1]
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["loss"] is not None


def test_data_plane_flag_validation():
    """--data-plane device exits early (before any env/device work) on
    every doomed combination: no actor services to relocate, and the
    multi-host learner (host-array global batches) — ISSUE 13."""
    import train as train_cli

    base = ["--iterations", "1", "--quiet"]
    with pytest.raises(SystemExit, match="async-actors"):
        train_cli.main(
            ["--algo", "ppo", "--env", "host:CartPole-v1",
             "--data-plane", "device"] + base
        )
    with pytest.raises(SystemExit, match="single-host"):
        train_cli.main(
            ["--algo", "ppo", "--env", "host:CartPole-v1",
             "--data-plane", "device", "--async-actors", "2",
             "--distributed", "--gossip", "--mailbox-dir", "/tmp/mb"]
            + base
        )
    with pytest.raises(SystemExit):
        # argparse rejects unknown plane codecs at parse time.
        train_cli.main(
            ["--algo", "ppo", "--env", "host:CartPole-v1",
             "--data-plane-codec", "bf16"] + base
        )


def test_curriculum_flag_validation():
    """--curriculum exits early (before any env/device work) on every
    doomed combination: non-mixture env, no eval cadence, bad spec."""
    import train as train_cli

    base = ["--iterations", "1", "--quiet"]
    with pytest.raises(SystemExit, match="mixture"):
        train_cli.main(
            ["--algo", "a2c", "--env", "jax:cartpole",
             "--curriculum", "10:1"] + base
        )
    with pytest.raises(SystemExit, match="eval-every"):
        train_cli.main(
            ["--algo", "a2c", "--env", "mixture:cartpole,maze",
             "--curriculum", "10:1,2"] + base
        )
    with pytest.raises(SystemExit, match="bad --curriculum"):
        train_cli.main(
            ["--algo", "a2c", "--env", "mixture:cartpole,maze",
             "--curriculum", "10:1,2,3", "--eval-every", "1"] + base
        )
    with pytest.raises(SystemExit, match="bad --curriculum"):
        train_cli.main(
            ["--algo", "a2c", "--env", "mixture:cartpole,maze",
             "--curriculum", "garbage", "--eval-every", "1"] + base
        )
