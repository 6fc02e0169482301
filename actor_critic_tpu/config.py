"""Config system: named presets + `key=value` overrides (SURVEY.md §5.6).

The reference genre configures each algorithm through per-script argparse
flags (reference mount empty at survey, SURVEY.md §0). The TPU build
replaces that with frozen dataclass configs (each algorithm module owns
its own) plus this registry of named presets — one per reference config
in BASELINE.json:7-11 — and a typed `--set key=value` override parser, so
one `train.py` CLI drives every algorithm.
"""

from __future__ import annotations

import dataclasses
import typing
from typing import Any, Optional, Union

from actor_critic_tpu.algos import a2c, ddpg, impala, ppo, sac
from actor_critic_tpu.models.seq_policy import SeqPolicyConfig


@dataclasses.dataclass(frozen=True)
class Preset:
    """A runnable training setup: algorithm + environment + config."""

    algo: str        # a2c | ppo | ddpg | td3 | sac | impala | a3c
    env: str         # "jax:<name>" (pure-JAX, fused) or "host:<gym id>"
    config: Any      # the algorithm's frozen config dataclass
    iterations: int  # default --iterations
    description: str
    # Keyword arguments for the ENV constructor (the jax:* maker, or
    # gym.make for host pools) — the difficulty/shape knobs that define
    # a runnable result, e.g. pong's opp_skill/frame_skip. CLI
    # `--env-set key=value` merges over these.
    env_kwargs: dict = dataclasses.field(default_factory=dict)


PRESETS: dict[str, Preset] = {
    # BASELINE.json:7 — the ≥1M env-steps/sec north-star config.
    # lr+entropy annealed to 0 over the run: the flat-coefficient config
    # oscillated at eval ≤429 and never converged (round-2 verdict #1).
    # Round 4 closed the last 10 points to the 475 solve bar in two
    # moves (scripts/a2c_anneal_sweep.py): double the rollout to T=64
    # (halves GAE truncation bias; solved 3/4 seeds at E=256 but still
    # ceilinged ~465 at E=4096), then scale lr with the 16× batch —
    # lr=3e-3 reaches greedy eval 491/500 at iters 300/400 at THIS
    # shape (E=4096, CPU calibration; 1.5e-3 and 2e-3 underfit at
    # 418-458). Certification (results/a2c_cartpole_solve_*, threshold
    # 475 on 2 consecutive independent evals): seeds 0/1 solve at iters
    # 300/325 (finals 491/500); seed 2 oscillates at this lr — a
    # measured A2C ceiling (no trust region), not a tuning gap: the
    # sweep also rejected normalize_adv (collapse), lr 2.5e-3 (noisier)
    # and max_grad_norm 0.25 (still 2/3); PPO (ppo_cartpole) is the
    # 3/3 solver. tests/test_a2c.py guards a reduced E=256 shape.
    "a2c_cartpole": Preset(
        algo="a2c",
        env="jax:cartpole",
        config=a2c.A2CConfig(
            num_envs=4096, rollout_steps=64, lr=3e-3,
            anneal_iters=400, lr_final=0.0,
            entropy_coef=0.01, entropy_coef_final=0.0,
        ),
        iterations=400,
        description="A2C on pure-JAX CartPole-v1, fully fused (BASELINE.json:7)",
    ),
    # BASELINE.json:7 again, tuned to SOLVE (greedy eval ≥475) rather than
    # maximize raw throughput: PPO's clipped updates + lr/entropy annealing
    # and long (T=128) rollouts converge where flat-coefficient A2C
    # oscillates (round-2 verdict #1). clip-ε is NOT annealed here.
    "ppo_cartpole": Preset(
        algo="ppo",
        env="jax:cartpole",
        config=ppo.PPOConfig(
            num_envs=256, rollout_steps=128, epochs=4, num_minibatches=8,
            lr=2.5e-4, entropy_coef=0.01, gae_lambda=0.95, gamma=0.99,
            anneal_iters=100, lr_final=0.0, entropy_coef_final=0.0,
        ),
        iterations=100,
        description="PPO on pure-JAX CartPole-v1, fused, solve-tuned (BASELINE.json:7)",
    ),
    # BASELINE.json:8 — continuous control via the host-env pool.
    "ppo_halfcheetah": Preset(
        algo="ppo",
        env="host:HalfCheetah-v5",
        config=ppo.PPOConfig(
            num_envs=8, rollout_steps=256, epochs=10, num_minibatches=32,
            entropy_coef=0.0, lr=3e-4,
            anneal_iters=1000, lr_final=0.0,
        ),
        iterations=1000,
        description="PPO-clip on MuJoCo HalfCheetah-v5 (BASELINE.json:8)",
    ),
    # BASELINE.json:9 — off-policy with the HBM replay ring.
    # Default budgets are the real 1M-env-step runs (64 steps/iter × 16k
    # iterations; 1 update per env step) with a 10k-step uniform-random
    # warmup — the standard TD3/SAC MuJoCo regime.
    "ddpg_walker2d": Preset(
        algo="ddpg",
        env="host:Walker2d-v5",
        config=ddpg.DDPGConfig(
            num_envs=1, steps_per_iter=64, updates_per_iter=64,
            warmup_steps=10_000,
        ),
        iterations=16_000,
        description="DDPG on MuJoCo Walker2d-v5 (BASELINE.json:9)",
    ),
    "td3_walker2d": Preset(
        algo="td3",
        env="host:Walker2d-v5",
        config=ddpg.td3_config(
            num_envs=1, steps_per_iter=64, updates_per_iter=64,
            warmup_steps=10_000,
        ),
        iterations=16_000,
        description="TD3 on MuJoCo Walker2d-v5 (BASELINE.json:9)",
    ),
    # BASELINE.json:10.
    "sac_humanoid": Preset(
        algo="sac",
        env="host:Humanoid-v5",
        config=sac.SACConfig(
            num_envs=1, steps_per_iter=64, updates_per_iter=64,
            warmup_steps=10_000,
        ),
        iterations=16_000,
        description="SAC on MuJoCo Humanoid-v5 (BASELINE.json:10)",
    ),
    # BASELINE.json:11 — ale-py is unavailable; the JAX-native Pong-like
    # pixel env stands in (SURVEY.md §2.2, envs/pong.py docstring).
    "impala_pong": Preset(
        algo="impala",
        env="jax:pong",
        config=impala.ImpalaConfig(
            num_envs=64, rollout_steps=20, actor_refresh_every=4
        ),
        iterations=2000,
        description="IMPALA/V-trace on JAX Pong-like pixels (BASELINE.json:11)",
    ),
    # The config-5 setup that PROVABLY LEARNS (round 3, BASELINE.md:
    # eval −3.78 → +2.41 over 51.2M decisions): same learner as
    # impala_pong, env at the learnable difficulty — opponent tracking
    # at half speed (placed shots score within ~100 steps instead of
    # hundreds), ALE-style frame_skip=4 (ball velocity visible in the
    # 2-frame stack), 36px frames. 40k iterations ≈ 51.2M decisions.
    # Entropy-collapse timing is strongly seed-dependent: eval crosses 0
    # anywhere in the ~27M–130M decision band (observed across seeds /
    # hosts — BASELINE.md's variance note), so plateau runs budget
    # 160k iterations ≈ 205M decisions.
    "impala_pong_learn": Preset(
        algo="impala",
        env="jax:pong",
        config=impala.ImpalaConfig(
            num_envs=64, rollout_steps=20, actor_refresh_every=4
        ),
        iterations=40_000,
        description="IMPALA on JAX Pong at the learnable difficulty "
        "(opp_skill=0.5, frame_skip=4, 36px — BASELINE.json:11)",
        env_kwargs={"opp_skill": 0.5, "frame_skip": 4, "size": 36},
    ),
    # ISSUE 11 — the scenario universe: a heterogeneous fleet of four
    # env TYPES (domain-randomized per instance AND per episode)
    # stepping inside one fused XLA program behind the padded shared
    # obs/action interface (envs/mixture.py). Pair with
    # `--curriculum "200:1,2,2,2;400:0,1,2,4" --eval-every 25` to shift
    # the type draw toward the harder members as CartPole-dominated
    # progress crosses the thresholds.
    "a2c_mixture": Preset(
        algo="a2c",
        env="mixture:cartpole,pendulum,acrobot,maze",
        config=a2c.A2CConfig(
            num_envs=1024, rollout_steps=32, lr=1e-3,
            anneal_iters=400, lr_final=0.0,
            entropy_coef=0.01, entropy_coef_final=0.0,
        ),
        iterations=400,
        description="A2C on the 4-type scenario-mixture fleet, fused "
        "(ISSUE 11 scenario universe)",
        env_kwargs={"randomize": 0.2},
    ),
    # Token-level IMPALA over one chip's share of JoyAI-LLM-Flash
    # (https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json;
    # SeqPolicyConfig's defaults are its published widths): 16 chips share
    # each layer, so this one holds 16 of the 256 routed experts and an
    # eighth of the vocabulary (16,160 rows, the env's ids), and 1 dense +
    # 4 expert layers of the 40 (further layers lie on further stages). 565M
    # parameters, 9.0 GB with the actor copy, gradients and RMSProp's moment.
    # An episode is one unroll: `horizon` must equal `rollout_steps`.
    "impala_joyai_flash": Preset(
        algo="impala",
        env="jax:token_task",
        config=impala.ImpalaConfig(
            num_envs=64, rollout_steps=512, actor_refresh_every=4,
            gamma=1.0, lr=1e-4, entropy_coef=0.001,
            seq=SeqPolicyConfig(
                num_hidden_layers=5, experts_held=16, expert_offset=0,
            ),
        ),
        iterations=200,
        description="Token-level IMPALA over a chip's share of "
        "JoyAI-LLM-Flash (MLA, 16 of 256 sigmoid-routed experts, 5 layers) "
        "on the prompt-copy task",
        env_kwargs={"vocab_size": 16160, "horizon": 512,
                    "prompt_min": 16, "prompt_max": 128},
    ),
    # The same program at widths a CPU runs in seconds (tests, a first
    # drive): every mechanism of the block, none of its sizes.
    "impala_joyai_flash_tiny": Preset(
        algo="impala",
        env="jax:token_task",
        config=impala.ImpalaConfig(
            num_envs=32, rollout_steps=16, actor_refresh_every=2,
            gamma=1.0, lr=3e-3, rms_eps=1e-5, entropy_coef=0.003,
            seq=SeqPolicyConfig(
                hidden_size=64, num_attention_heads=4, q_lora_rank=32,
                kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
                v_head_dim=8, intermediate_size=128,
                moe_intermediate_size=32, n_routed_experts=16,
                num_experts_per_tok=2, num_hidden_layers=2, experts_held=4,
                compute_dtype="float32",
            ),
        ),
        iterations=300,
        description="impala_joyai_flash at toy widths (CPU tests and drives)",
        env_kwargs={"vocab_size": 8, "horizon": 16,
                    "prompt_min": 1, "prompt_max": 2},
    ),
    # Token-level IMPALA over one chip's share of Mellum2-12B-A2.5B-Instruct
    # (https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json):
    # grouped-query attention, 32 query heads over 4 key/value heads of 128,
    # three layers with a causal window of 1,024 and plain RoPE to one full
    # layer with YaRN-scaled RoPE; 64 softmax-routed experts a layer, top-8,
    # renormalised, no shared expert, no dense layer. 4 chips share each
    # layer: this one holds 16 of the 64 experts and a quarter of the
    # vocabulary (24,576 rows), and one period, 4 of the 28 layers. 595.3M
    # parameters, 9.5 GB with the actor copy, gradients and RMSProp's moment.
    # Rows of 4,096: prompts of 3,072-3,584 tokens whose first 3,072 go
    # through one causal pass (`prefill_len`), the answer fills the row.
    "impala_mellum2": Preset(
        algo="impala",
        env="jax:token_task",
        config=impala.ImpalaConfig(
            num_envs=8, rollout_steps=4096, actor_refresh_every=4,
            gamma=1.0, lr=1e-4, entropy_coef=0.001,
            seq=SeqPolicyConfig(
                hidden_size=2304, num_attention_heads=32,
                moe_intermediate_size=896, n_routed_experts=64,
                num_experts_per_tok=8, scoring_func="softmax",
                routed_scaling_factor=1.0, n_shared_experts=0,
                first_k_dense_replace=0, num_hidden_layers=4,
                layer_types=("sliding_attention",) * 3 + ("full_attention",),
                num_key_value_heads=4, head_dim=128, sliding_window=1024,
                rope_theta=500000.0, rope_factor=16.0,
                rope_original_max_position_embeddings=8192,
                rope_beta_fast=32.0, rope_beta_slow=1.0,
                rope_attention_factor=1.2772588722239782,
                experts_held=16, expert_offset=0,
            ),
        ),
        iterations=200,
        description="Token-level IMPALA over a chip's share of "
        "Mellum2-12B-A2.5B (window and full GQA layers 3:1, 16 of 64 "
        "softmax-routed experts, 4 layers), prompts prefilled in one pass",
        env_kwargs={"vocab_size": 24576, "horizon": 4096, "prompt_min": 3072,
                    "prompt_max": 3584, "prefill_len": 3072},
    ),
    # The same program at toy widths: a window of 4 in rows of 16, so the
    # rings wrap three times.
    "impala_mellum2_tiny": Preset(
        algo="impala",
        env="jax:token_task",
        config=impala.ImpalaConfig(
            num_envs=32, rollout_steps=16, actor_refresh_every=2,
            gamma=1.0, lr=3e-3, rms_eps=1e-5, entropy_coef=0.003,
            seq=SeqPolicyConfig(
                hidden_size=64, num_attention_heads=4,
                moe_intermediate_size=32, n_routed_experts=16,
                num_experts_per_tok=2, scoring_func="softmax",
                routed_scaling_factor=1.0, n_shared_experts=0,
                first_k_dense_replace=0, num_hidden_layers=4,
                layer_types=("sliding_attention",) * 3 + ("full_attention",),
                num_key_value_heads=2, head_dim=8, sliding_window=4,
                rope_theta=500000.0, rope_factor=16.0,
                rope_original_max_position_embeddings=8,
                rope_attention_factor=1.2772588722239782,
                experts_held=4, compute_dtype="float32",
            ),
        ),
        iterations=300,
        description="impala_mellum2 at toy widths (CPU tests and drives)",
        env_kwargs={"vocab_size": 8, "horizon": 16, "prompt_min": 2,
                    "prompt_max": 3, "prefill_len": 2},
    ),
    "a3c_pong": Preset(
        algo="a3c",
        env="jax:pong",
        config=impala.ImpalaConfig(
            num_envs=64, rollout_steps=20, actor_refresh_every=4,
            correction="none", lam=0.95,
        ),
        iterations=2000,
        description="A3C-style (no IS correction) on JAX Pong (BASELINE.json:11)",
    ),
}

# Algorithm name → config dataclass type, for --algo without --preset.
ALGO_CONFIGS: dict[str, Any] = {
    "a2c": a2c.A2CConfig,
    "ppo": ppo.PPOConfig,
    "ddpg": ddpg.DDPGConfig,
    "td3": ddpg.DDPGConfig,
    "sac": sac.SACConfig,
    "impala": impala.ImpalaConfig,
    "a3c": impala.ImpalaConfig,
}


def _coerce(raw: str, typ: Any) -> Any:
    """Parse a CLI string into the annotated field type."""
    origin = typing.get_origin(typ)
    if origin is Union:  # Optional[T]
        args = [a for a in typing.get_args(typ) if a is not type(None)]
        if raw.lower() in ("none", "null"):
            return None
        return _coerce(raw, args[0])
    if origin is tuple:
        elem = typing.get_args(typ)[0]
        if raw.strip() == "":
            return ()
        return tuple(_coerce(p.strip(), elem) for p in raw.split(","))
    if typ is bool:
        if raw.lower() in ("1", "true", "yes", "on"):
            return True
        if raw.lower() in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"not a bool: {raw!r}")
    if typ is int:
        return int(raw)
    if typ is float:
        return float(raw)
    if typ is str:
        return raw
    raise ValueError(f"unsupported field type {typ} for value {raw!r}")


def apply_overrides(config: Any, overrides: dict[str, str]) -> Any:
    """`dataclasses.replace` with string values coerced to field types.

    Unknown keys raise with the list of valid fields (typo safety). A
    dotted key reaches into a field that is itself a config
    (`seq.hidden_size=64`); the group must be set in the preset.
    """
    if not overrides:
        return config
    hints = typing.get_type_hints(type(config))
    fields = {f.name for f in dataclasses.fields(config)}
    updates = {}
    nested: dict[str, dict[str, str]] = {}
    for key, raw in overrides.items():
        group, dot, rest = key.partition(".")
        if dot and group in fields:
            if not dataclasses.is_dataclass(getattr(config, group)):
                raise KeyError(
                    f"{type(config).__name__}.{group} is not set in this "
                    f"preset, so {key!r} has nothing to override"
                )
            nested.setdefault(group, {})[rest] = raw
            continue
        if key not in fields:
            raise KeyError(
                f"{type(config).__name__} has no field {key!r}; "
                f"valid: {sorted(fields)}"
            )
        updates[key] = _coerce(raw, hints[key])
    for group, sub in nested.items():
        updates[group] = apply_overrides(getattr(config, group), sub)
    return dataclasses.replace(config, **updates)


def parse_set_args(pairs: list[str]) -> dict[str, str]:
    """['lr=1e-3', 'hidden=64,64'] → {'lr': '1e-3', 'hidden': '64,64'}."""
    out: dict[str, str] = {}
    for pair in pairs:
        if "=" not in pair:
            raise ValueError(f"--set expects key=value, got {pair!r}")
        key, _, value = pair.partition("=")
        out[key.strip()] = value.strip()
    return out


def coerce_env_value(raw: str) -> Any:
    """Parse an `--env-set` value. Env-maker kwargs are not dataclass
    fields, so there is no annotation to coerce against — use literal
    syntax: bools/None by keyword, then int, then float, else string."""
    low = raw.lower()
    if low in ("true", "yes", "on"):
        return True
    if low in ("false", "no", "off"):
        return False
    if low in ("none", "null"):
        return None
    for typ in (int, float):
        try:
            return typ(raw)
        except ValueError:
            pass
    return raw


def parse_env_set_args(pairs: list[str]) -> dict[str, Any]:
    """['opp_skill=0.5', 'frame_skip=4'] → {'opp_skill': 0.5, 'frame_skip': 4}."""
    return {k: coerce_env_value(v) for k, v in parse_set_args(pairs).items()}


def default_config(algo: str) -> Any:
    """The algorithm's default config, with variant specialization applied
    (td3 → twin-Q/delay/smoothing; a3c → no importance correction)."""
    if algo not in ALGO_CONFIGS:
        raise KeyError(f"unknown algo {algo!r}; valid: {sorted(ALGO_CONFIGS)}")
    if algo == "td3":
        return ddpg.td3_config()
    cfg = ALGO_CONFIGS[algo]()
    if algo == "a3c":
        cfg = dataclasses.replace(cfg, correction="none")
    return cfg


def resolve(
    preset: Optional[str],
    algo: Optional[str],
    env: Optional[str],
    overrides: dict[str, str],
    env_overrides: Optional[dict[str, Any]] = None,
) -> Preset:
    """Resolve CLI selections into a concrete Preset.

    Either `--preset name` (optionally overridden by --algo/--env) or
    `--algo` + `--env` from scratch with that algorithm's default config.
    `env_overrides` (from --env-set) merge over the preset's env_kwargs;
    changing the env drops the preset's env_kwargs (they belong to the
    preset's env), keeping only the CLI ones.
    """
    env_overrides = env_overrides or {}
    if preset is not None:
        if preset not in PRESETS:
            raise KeyError(f"unknown preset {preset!r}; valid: {sorted(PRESETS)}")
        base = PRESETS[preset]
        algo = algo or base.algo
        base_env_kwargs = base.env_kwargs if env in (None, base.env) else {}
        env = env or base.env
        # Changing the algo drops the preset's config (it belongs to the
        # preset's algorithm) in favor of the new algo's specialized
        # defaults — so e.g. `--preset ddpg_walker2d --algo td3` really
        # runs TD3, not vanilla DDPG under a td3 label.
        cfg = base.config if algo == base.algo else default_config(algo)
        return Preset(
            algo=algo, env=env, config=apply_overrides(cfg, overrides),
            iterations=base.iterations, description=base.description,
            env_kwargs={**base_env_kwargs, **env_overrides},
        )
    if algo is None or env is None:
        raise ValueError("need --preset, or both --algo and --env")
    cfg = default_config(algo)
    return Preset(
        algo=algo, env=env, config=apply_overrides(cfg, overrides),
        iterations=1000, description=f"{algo} on {env}",
        env_kwargs=dict(env_overrides),
    )
