"""The sequence policy (`models/seq_policy.py`), the token env and the policy
seam of the fused trainers, at toy widths on the CPU: step through the cache
= one causal pass = the plain reference; the chip's share of the experts adds
up; no routing drops a token; masked steps carry no gradient; the causal
pass's checkpoints change no gradient and run attention forward twice; the
decode's attention kernel (`ops/mla_decode.py`) through the Pallas interpreter
equals the einsums, reads nothing past the slot, engages by shape, and in a
rollout compiled for a described v5e leaves the cache where it is."""

import dataclasses
import functools
import json
import os
import re
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import train  # noqa: E402
from actor_critic_tpu import config as config_mod  # noqa: E402
from actor_critic_tpu.algos import common, impala  # noqa: E402
from actor_critic_tpu.envs import make_token_task  # noqa: E402
from actor_critic_tpu.models import seq_policy as sp  # noqa: E402
from actor_critic_tpu.ops import mla_decode, pallas_scan  # noqa: E402
from benchmark import harness  # noqa: E402

TINY = "impala_joyai_flash_tiny"
GQA_TINY = "impala_mellum2_tiny"   # window 4 in rows of 16: the rings wrap three times
SPARSE_TINY = "impala_keye_vl2_tiny"  # 5 keys a query in rows of 16
BOTH = pytest.mark.parametrize("preset", [TINY, GQA_TINY, SPARSE_TINY])
HP = dict(gamma=1.0, rho_bar=1.0, c_bar=1.0, lam=1.0, value_coef=0.5,
          entropy_coef=0.003)


@pytest.fixture(autouse=True)
def toy_blocking(monkeypatch):
    """The blocking constants of `seq_policy` at the toy widths' scale, so
    that a decode step takes the batched matmuls and a causal pass of a few
    rows the grouped ones in several trips, as at the shipped sizes."""
    monkeypatch.setattr(sp, "MOE_ROWS", 256)
    monkeypatch.setattr(sp, "MOE_DENSE_TOKENS", 32)
    # Blocks of 6 queries in rows of 16 against a window of 4: three blocks,
    # the last one short, the second and third cut to their band.
    monkeypatch.setattr(sp, "ATTN_QUERIES", 6)


def _network(seq: sp.SeqPolicyConfig) -> dict:
    """What the benchmark's configuration file would say of `seq`."""
    net = dataclasses.asdict(seq)
    if seq.sa_config is not None:
        # mRoPE's sections split the head's frequencies as the source's 16 /
        # 24 / 24 split 64 of them.
        half = seq.head_dim // 2
        net["rope_scaling"] = {"mrope_section": [half // 4, half // 4, half - half // 2]}
    if seq.layer_types:
        net["rope_parameters"] = {
            "sliding_attention": {"rope_type": "default", "rope_theta": seq.rope_theta},
            "full_attention": {
                "rope_type": "yarn", "rope_theta": seq.rope_theta,
                "factor": seq.rope_factor,
                "original_max_position_embeddings":
                    seq.rope_original_max_position_embeddings,
                "beta_fast": seq.rope_beta_fast, "beta_slow": seq.rope_beta_slow,
                "attention_factor": seq.rope_attention_factor}}
    return net


def _reference(seq=None):
    """The plain reference of the configuration whose block `seq` is."""
    if seq is not None and seq.sa_config is not None:
        return harness.load_module("reference", "impala_keye_vl2")
    gqa = seq is not None and seq.layer_types
    return harness.load_module(
        "reference", "impala_mellum2" if gqa else "impala_joyai_flash")


def _setup(sets=None, env_sets=None, seed=0, preset=TINY):
    """A tiny preset; with `env_sets`, over a vocabulary of 64."""
    if env_sets is not None:
        env_sets = {"vocab_size": 64, **env_sets}
        if preset in (GQA_TINY, SPARSE_TINY):
            env_sets.setdefault("prefill_len", min(2, env_sets.get("prompt_min", 2)))
    preset = config_mod.resolve(preset, None, None, sets or {}, env_overrides=env_sets)
    cfg = preset.config
    env, _ = train.build_env(preset.env, preset.algo, cfg, seed,
                             env_kwargs=preset.env_kwargs)
    return env, cfg, impala.make_policy(env, cfg)


def _rollout(env, cfg, policy, behaviour, seed=3):
    rstate = common.init_rollout(env, jax.random.key(seed), cfg.num_envs)
    return jax.jit(lambda p, r, k: common.rollout_scan(
        env, policy, p, r, k, cfg.rollout_steps))(
            behaviour, rstate, jax.random.key(seed + 1))


# -- the env ---------------------------------------------------------------

def test_token_task_feeds_the_prompt_then_the_action_and_pays_at_the_end():
    env = make_token_task(vocab_size=32, horizon=8, prompt_min=3, prompt_max=3)
    state, obs = env.reset(jax.random.key(0))
    prompt = [int(t) for t in state.prompt]
    assert 0 not in prompt and int(state.prompt_len) == 3
    seen, rewards, dones = [np.asarray(obs)], [], []
    # A perfect copier: the response repeats the prompt cyclically.
    for t in range(8):
        out = env.step(state, jnp.asarray(prompt[(t + 1 - 3) % 3]))
        state = out.state
        seen.append(np.asarray(out.obs)); rewards.append(float(out.reward))
        dones.append(float(out.done))
    tokens = [int(o[0]) for o in seen[:8]]
    assert tokens == prompt + [prompt[i % 3] for i in range(5)]
    assert [int(o[1]) for o in seen[:8]] == list(range(8))
    assert [int(o[2]) for o in seen[:8]] == [1, 1, 0, 0, 0, 0, 0, 0]
    assert rewards == [0.0] * 7 + [1.0] and dones == [0.0] * 7 + [1.0]
    assert int(seen[8][1]) == 0 and float(out.info["terminated"]) == 1.0


def test_token_task_eos_ends_the_episode_and_pads_the_row():
    env = make_token_task(vocab_size=32, horizon=8, prompt_min=2, prompt_max=2)
    state, _ = env.reset(jax.random.key(1))
    prompt = [int(t) for t in state.prompt]
    actions = [5, prompt[0], prompt[1], 0, 7, 7, 7, 7]  # EOS at position 3
    outs = []
    for a in actions:
        out = env.step(state, jnp.asarray(a)); state = out.state; outs.append(out)
    assert [float(o.done) for o in outs] == [0, 0, 0, 1, 0, 0, 0, 0]
    # Three response tokens, two of them right (the EOS is not the prompt's).
    assert outs[3].reward == pytest.approx(2 / 3)
    assert all(float(o.reward) == 0 for i, o in enumerate(outs) if i != 3)
    # Padding: the EOS id under is_prompt = 1 until the row resets.
    pads = [np.asarray(o.info["final_obs"]) for o in outs[3:]]
    assert all(int(p[0]) == 0 and int(p[2]) == 1 for p in pads)
    assert int(outs[-1].obs[1]) == 0 and not bool(outs[-1].state.finished)


def test_token_task_refuses_a_prompt_that_fills_the_row():
    with pytest.raises(ValueError, match="prompt_max < horizon"):
        make_token_task(vocab_size=32, horizon=8, prompt_max=8)


# -- step = unroll = reference ---------------------------------------------

def _with_an_eos(env, cfg, policy, behaviour):
    """A rollout in which some row holds an EOS before its end."""
    for seed in range(3, 40):
        _, traj = _rollout(env, cfg, policy, behaviour, seed)
        early = np.asarray(traj.done)[:-1].sum()
        if early > 0:
            return traj
    raise AssertionError("no seed gave an EOS mid-row")


@BOTH
@pytest.mark.parametrize("compute_dtype, tol", [("float32", 2e-5), ("bfloat16", 6e-2)])
def test_step_through_the_cache_equals_unroll_equals_reference(
        compute_dtype, tol, preset):
    """The grouped-query preset's rollout prefills one position, then decodes
    through rings of 4 slots that wrap three times in the row of 16."""
    env, cfg, policy = _setup({"num_envs": "6", "seq.compute_dtype": compute_dtype},
                              {"prompt_min": 1, "prompt_max": 6}, preset=preset)
    behaviour = impala.init_params(env, cfg, jax.random.key(1))
    traj = _with_an_eos(env, cfg, policy, behaviour)
    prompts = np.asarray(traj.obs[..., 2]).sum(axis=0)
    assert len(set(prompts.tolist())) > 1, "uneven prompts"
    # The rollout decoded token by token through the latent cache; the causal
    # pass over the same rows gives the same log-probabilities and values.
    out = policy.unroll(behaviour, traj)
    live = np.asarray(out.mask) > 0
    assert np.abs(np.asarray(out.log_prob - traj.log_prob))[live].max() < tol
    assert np.abs(np.asarray(out.value - traj.value)).max() < tol
    if compute_dtype != "float32":
        # At toy widths one rounded router score moves a token to another
        # expert and its logits with it; the reference is held in float32.
        return
    # And the plain reference's full forward pass gives the same logits.
    logits, values = sp.logits_and_values(
        behaviour, jnp.swapaxes(traj.obs, 0, 1), cfg.seq)
    want_logits, want_values = _reference(cfg.seq).forward(
        behaviour, traj.obs, _network(cfg.seq))
    scale = float(jnp.max(jnp.abs(want_logits)))
    assert float(jnp.max(jnp.abs(jnp.swapaxes(logits, 0, 1) - want_logits))) < tol * scale
    assert float(jnp.max(jnp.abs(values.T - want_values))) < tol
    assert np.abs(np.asarray(out.value - want_values)).max() < tol


@BOTH
def test_loss_and_gradients_agree_with_the_reference(monkeypatch, preset):
    monkeypatch.setattr(sp, "MOE_ROWS", 16)
    monkeypatch.setattr(sp, "GQA_ROWS", 4)  # two trips of the causal pass's attention
    env, cfg, policy = _setup({"num_envs": "6"}, {"prompt_min": 1, "prompt_max": 6},
                              preset=preset)
    params = impala.init_params(env, cfg, jax.random.key(0))
    behaviour = impala.init_params(env, cfg, jax.random.key(1))
    rstate, traj = _rollout(env, cfg, policy, behaviour)
    hp = {**HP, "entropy_coef": cfg.entropy_coef, "value_coef": cfg.value_coef}

    def program(p):
        return impala.impala_loss(p, policy, traj, rstate.obs, cfg, False)

    (loss, metrics), grads = jax.value_and_grad(program, has_aux=True)(params)
    ref = _reference(cfg.seq)
    net = _network(cfg.seq)
    # (The grouped-query reference also wants the seam's second rollout.)
    want_all = ref.loss_and_targets(
        params, {**traj._asdict(), "decode_obs": traj.obs, "decode_action": traj.action},
        rstate.obs, hp, net)
    want = jnp.sum(want_all["loss"])   # the reference gives the loss as its terms

    def surrogate(p):
        """The reference's loss with its targets held fixed, as the program
        holds them (`stop_gradient`): from the reference's own forward pass."""
        logits, values = ref.forward(p, traj.obs, net)
        logp = jax.nn.log_softmax(logits, axis=-1)
        lp = jnp.take_along_axis(logp, traj.action[..., None], axis=-1)[..., 0]
        mask = 1.0 - traj.obs[..., 2].astype(jnp.float32)
        mean = lambda x: jnp.sum(x * mask) / jnp.sum(mask)  # noqa: E731
        entropy = mean(-jnp.sum(jnp.exp(logp) * logp, axis=-1))
        return (-mean(want_all["pg_advantages"] * lp)
                + hp["value_coef"] * 0.5 * mean((values - want_all["value_targets"]) ** 2)
                - hp["entropy_coef"] * entropy)

    again, want_grads = jax.value_and_grad(surrogate)(params)
    assert float(again) == pytest.approx(float(want), rel=1e-5, abs=1e-6)
    assert float(loss) == pytest.approx(float(want), rel=1e-5, abs=1e-6)
    assert float(metrics["moe_dropped"]) == 0.0
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-6)
        assert float(jnp.max(jnp.abs(g - w))) < 2e-4 * scale + 1e-7, \
            jax.tree_util.keystr(path)
    # The frozen bias (where the router has one) gets no gradient, the
    # router does.
    moe = grads["params"]["layer_1"]["moe"]
    assert float(jnp.max(jnp.abs(moe.get("bias", 0.0)))) == 0.0
    assert float(jnp.max(jnp.abs(moe["router"]))) > 0.0


def test_masked_prompt_steps_give_no_gradient():
    """Where the env ignored the action (prompt, padding), the action taken
    reaches neither the loss nor any gradient."""
    env, cfg, policy = _setup({"num_envs": "6"}, {"prompt_min": 2, "prompt_max": 6})
    params = impala.init_params(env, cfg, jax.random.key(0))
    behaviour = impala.init_params(env, cfg, jax.random.key(1))
    rstate, traj = _rollout(env, cfg, policy, behaviour)
    ignored = traj.obs[..., 2] > 0
    assert 0 < int(ignored.sum()) < ignored.size
    other = traj._replace(
        action=jnp.where(ignored, (traj.action + 7) % env.spec.action_dim, traj.action),
        log_prob=jnp.where(ignored, traj.log_prob - 1.0, traj.log_prob))
    grad = jax.grad(lambda p, t: impala.impala_loss(
        p, policy, t, rstate.obs, cfg, False)[0])
    a, b = grad(params, traj), grad(params, other)
    assert all(bool(jnp.array_equal(x, y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    # A live step's action does reach the gradient.
    moved = traj._replace(action=jnp.where(ignored, traj.action,
                                           (traj.action + 7) % env.spec.action_dim))
    c = grad(params, moved)
    assert not all(bool(jnp.array_equal(x, y))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(c)))


# -- what the causal pass rematerializes ------------------------------------

@functools.lru_cache(maxsize=None)
def _unroll_gradient(compute_dtype: str, checkpointed: bool):
    """The gradient of a loss over `unroll`'s log-probability, entropy and
    value at three layers (one dense, two expert) and `ATTN_ROWS` < E, so
    that the attention's trips exist: (gradient, products with a `[.., T, T]`
    result in the compiled program). `checkpointed=False` builds the same
    program with `jax.checkpoint` the identity: nothing rematerialized. T is
    no other size of the toy model, so a product whose result ends in
    `[T, T]` is one over the scores. (The expert layer's blocking is the
    file's `toy_blocking`, the same under every test, so the cache is sound.)"""
    E, T, V = 6, 12, 64
    seq = dataclasses.replace(config_mod.PRESETS[TINY].config.seq,
                              num_hidden_layers=3, compute_dtype=compute_dtype)
    params = sp.init_params(jax.random.key(0), seq, V)
    keys = jax.random.split(jax.random.key(1), 5)
    obs = jnp.stack([jax.random.randint(keys[0], (E, T), 0, V),
                     jnp.broadcast_to(jnp.arange(T), (E, T)),
                     jnp.zeros((E, T), jnp.int32)], axis=-1)
    actions = jax.random.randint(keys[1], (E, T), 0, V)
    w_logp, w_entropy, w_value = (jax.random.normal(k, (E, T)) for k in keys[2:])

    def loss(p):
        log_prob, entropy, value, _ = sp.unroll(p, obs, actions, seq)
        return jnp.sum(log_prob * w_logp + entropy * w_entropy + value * w_value)

    identity = lambda fn, *args, **kwargs: fn  # noqa: E731
    with mock.patch.object(sp, "ATTN_ROWS", 2), mock.patch.object(
            jax, "checkpoint", jax.checkpoint if checkpointed else identity):
        compiled = jax.jit(jax.grad(loss)).lower(params).compile()
    products = re.findall(rf"= \w+\[[\d,]*{T},{T}\]\S* dot\(", compiled.as_text())
    return compiled(params), len(products)


@pytest.mark.parametrize("compute_dtype", ["bfloat16", "float32"])
def test_the_rematerialized_gradient_is_the_plain_one(compute_dtype):
    """Cutting the pass into checkpoints changes when a value is computed,
    never which: at float32 the gradient equals, leaf for leaf and bit for
    bit, that of the program with no `jax.checkpoint` at all; at bfloat16
    (where the plain program may fuse, and so round, differently) within the
    rounding."""
    got, _ = _unroll_gradient(compute_dtype, True)
    want, _ = _unroll_gradient(compute_dtype, False)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    norm = lambda x: float(jnp.linalg.norm(x))  # noqa: E731
    assert sum(norm(g) ** 2 for _, g in flat) ** 0.5 > 1.0
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if compute_dtype == "float32":
            assert bool(jnp.array_equal(g, w)), name
        else:
            assert norm(g - w) <= 1e-2 * norm(w), name


@pytest.mark.parametrize("compute_dtype", ["bfloat16", "float32"])
def test_the_update_computes_the_scores_twice_a_layer(compute_dtype):
    """The compiled gradient holds three products with a `[.., T, T]` result
    a layer: the forward pass's scores, the scores of a trip's
    rematerialization, and the softmax's cotangent. A fourth is a layer's
    rematerialization running attention forward again only to rebuild the
    FFN's input (one checkpoint round a whole layer does that); with nothing
    rematerialized there are two."""
    _, products = _unroll_gradient(compute_dtype, True)
    _, plain = _unroll_gradient(compute_dtype, False)
    assert (products, plain) == (3 * 3, 2 * 3)


# -- the decode step's attention kernel ---------------------------------------

BLOCK = mla_decode.BLOCK_POSITIONS
KERNEL_T = 2 * BLOCK


def _decode_inputs(E, dtype, rank=128, layers=2, heads=4, rope=16):
    """Queries and a full stacked cache at the smallest shapes that tile."""
    keys = jax.random.split(jax.random.key(0), 4)
    normal = lambda k, *shape: jax.random.normal(k, shape)  # noqa: E731
    return (normal(keys[0], E, heads, rank), normal(keys[1], E, heads, rope),
            normal(keys[2], layers, E, KERNEL_T, rank).astype(dtype),
            normal(keys[3], layers, E, KERNEL_T, rope).astype(dtype))


@pytest.mark.parametrize("slot", [0, BLOCK - 1, BLOCK, KERNEL_T - 1])
@pytest.mark.parametrize("dtype, tol", [("float32", 5e-6), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("E", [mla_decode.BLOCK_ROWS, 3 * mla_decode.BLOCK_ROWS])
def test_the_decode_kernel_equals_the_einsums(E, dtype, tol, slot):
    """The kernel through the Pallas interpreter against the einsum path, one
    row block and several, at a slot in the first block, at its last position,
    at the first of the next and at the row's end. At bfloat16 the two round
    the probabilities at different scales (before and after the division)."""
    q_lat, q_rope, c_kv, k_r = _decode_inputs(E, jnp.dtype(dtype))
    run = jax.jit(lambda fn, s: fn(q_lat, q_rope, c_kv, k_r, 1, s, 0.125),
                  static_argnums=0)
    got = run(mla_decode.mla_decode, jnp.int32(slot))
    want = run(mla_decode.reference, jnp.int32(slot))
    assert got.shape == want.shape and got.dtype == want.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < tol * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("slot", [0, BLOCK - 1, BLOCK, KERNEL_T - 2])
def test_the_decode_kernel_reads_nothing_past_the_slot(slot):
    """NaN in every cache position past `slot`, in the slot's own block and
    in the blocks behind it, reaches no output: the kernel's result is, bit
    for bit, its result on a cache that holds zeros there. (The einsums
    give those positions weight zero and still multiply: 0 x NaN.)"""
    q_lat, q_rope, c_kv, k_r = _decode_inputs(mla_decode.BLOCK_ROWS, jnp.bfloat16)
    past = (jnp.arange(KERNEL_T) > slot)[None, None, :, None]
    run = jax.jit(lambda c, r: mla_decode.mla_decode(
        q_lat, q_rope, c, r, 0, jnp.int32(slot), 0.125))
    want = run(jnp.where(past, 0, c_kv), jnp.where(past, 0, k_r))
    got = run(jnp.where(past, jnp.nan, c_kv), jnp.where(past, jnp.nan, k_r))
    assert bool(jnp.all(jnp.isfinite(got))) and bool(jnp.array_equal(got, want))
    poisoned = mla_decode.reference(
        q_lat, q_rope, jnp.where(past, jnp.nan, c_kv), k_r, 0, slot, 0.125)
    assert not bool(jnp.all(jnp.isfinite(poisoned)))


@pytest.mark.parametrize("on_tpu, rank, kernel", [
    (True, 128, True), (True, 16, False), (False, 128, False)])
def test_the_decode_takes_the_kernel_where_the_cache_tiles_on_a_tpu(
        on_tpu, rank, kernel, monkeypatch):
    """Which path runs is read from the input: the kernel on a TPU where the
    shapes tile, the einsums elsewhere (the tiny preset's rank of 16, every
    CPU run), and no error either way."""
    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: on_tpu)
    args = _decode_inputs(mla_decode.BLOCK_ROWS, jnp.float32, rank=rank)
    jaxpr = jax.make_jaxpr(lambda s: mla_decode.mla_decode_auto(
        *args, 0, s, 0.125))(jnp.int32(3))
    assert ("pallas_call" in str(jaxpr)) is kernel
    assert not mla_decode.tiles(mla_decode.BLOCK_ROWS + 1, KERNEL_T, 128)
    assert not mla_decode.tiles(mla_decode.BLOCK_ROWS, KERNEL_T + 1, 128)
    with pytest.raises(ValueError, match="not whole blocks"):
        mla_decode.mla_decode(*_decode_inputs(4, jnp.float32), 0, 3, 0.125)


def test_the_rollout_compiled_for_a_v5e_leaves_the_cache_in_hbm(v5e, monkeypatch):
    """`rollout_scan` at the shipped preset's widths and two layers, compiled
    for the described chip. In the scan's body the carried cache pair is at
    home in HBM (no `S(1)`, the compiler's mark for VMEM, on it), nothing
    cache-sized is copied or sliced asynchronously (a pair a layer lived in
    VMEM, and every step evicted each to HBM and fetched it back: PERF.md,
    PR 32), and attention is one kernel a layer."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: True)
    monkeypatch.setattr(sp, "MOE_DENSE_TOKENS", 128)   # as shipped
    layers = 2
    preset = config_mod.resolve(
        "impala_joyai_flash", None, None, {"seq.num_hidden_layers": str(layers)})
    cfg = preset.config
    env, _ = train.build_env(preset.env, preset.algo, cfg, 0,
                             env_kwargs=preset.env_kwargs)
    policy = impala.make_policy(env, cfg)
    key = jax.random.key(0)
    on_chip = lambda make: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
        jax.eval_shape(make))
    rollout = jax.jit(lambda p, r, k: common.rollout_scan(
        env, policy, p, r, k, cfg.rollout_steps))
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = rollout.lower(
            on_chip(lambda: impala.init_params(env, cfg, key)),
            on_chip(lambda: common.init_rollout(env, key, cfg.num_envs)),
            on_chip(lambda: key)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    kernel = 'custom_call_target="tpu_custom_call"'
    bodies = [c for c in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text)
              if kernel in c]
    assert len(bodies) == 1, "the decode kernels are in one computation: the scan's body"
    body = bodies[0].splitlines()
    # One attention kernel a layer and nothing else: at 64 rows a step (2
    # assignments an expert) the experts run as batched matmuls.
    assert sum(kernel in line for line in body) == layers
    assert all("mla_decode" in line for line in body if kernel in line)
    # The experts' weights reach them as the `compute_dtype` copies made once
    # outside the scan: no float32 expert matrix is read a step.
    held, H, W = (cfg.seq.experts_held, cfg.seq.hidden_size,
                  cfg.seq.moe_intermediate_size)
    wide = re.compile(rf"f32\[{held},(?:{H},{W}|{W},{H})\]")
    assert [line.strip()[:200] for line in body if wide.search(line)] == []
    dtype = {"bfloat16": "bf16", "float32": "f32"}[cfg.seq.compute_dtype]
    T, widths = cfg.rollout_steps, f"{cfg.seq.kv_lora_rank}|{cfg.seq.qk_rope_head_dim}"
    cache_sized = re.compile(rf"{dtype}\[(?:\d+,)*{T},(?:{widths})\]\{{[^}}]*\}}")
    moved = [line.strip()[:200] for line in body
             if re.search(r" (?:copy|slice)-start\(", line)
             and cache_sized.search(re.split(r" (?:copy|slice)-start\(", line)[0])]
    assert moved == []
    root, = (line for line in body if line.lstrip().startswith("ROOT"))
    carried = [shape for shape in cache_sized.findall(root)
               if shape.startswith(f"{dtype}[{layers},{cfg.num_envs},")]
    assert len(carried) == 2 and not any("S(1)" in shape for shape in carried), carried


def test_the_long_context_rollout_compiled_for_a_v5e_reads_its_experts_by_the_kernel(
        v5e, monkeypatch):
    """`rollout_scan` at the `impala_mellum2` preset as shipped, compiled for
    the described chip. The scan's body calls the experts' kernel once a
    layer and holds no matmul over the held experts' stacked weights, which
    reach the kernel as `compute_dtype` copies made outside the scan; and
    the compiler moves neither kind of cache asynchronously (with no cost
    estimate on the kernel it staged the full layer's cache through VMEM
    every step, and the step waited on it: PERF.md, Findings, PR 35)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.undo()  # the shipped blocking, not `toy_blocking`'s
    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: True)
    preset = config_mod.resolve("impala_mellum2", None, None, {})
    cfg = preset.config
    env, _ = train.build_env(preset.env, preset.algo, cfg, 0,
                             env_kwargs=preset.env_kwargs)
    policy = impala.make_policy(env, cfg)
    key = jax.random.key(0)
    on_chip = lambda make: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
        jax.eval_shape(make))
    rollout = jax.jit(lambda p, r, k: common.rollout_scan(
        env, policy, p, r, k, cfg.rollout_steps))
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = rollout.lower(
            on_chip(lambda: impala.init_params(env, cfg, key)),
            on_chip(lambda: common.init_rollout(env, key, cfg.num_envs)),
            on_chip(lambda: key)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    kernel = 'custom_call_target="tpu_custom_call"'
    # (The prefill's grouped matmuls are the compiler's own kernels, in the
    # loops of its trips.)
    bodies = [c for c in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text)
              if kernel in c and "moe_decode" in c]
    assert len(bodies) == 1, "the decode's kernels are in one computation: the scan's body"
    body = bodies[0].splitlines()
    calls = [line for line in body if kernel in line]
    seq = cfg.seq
    assert len(calls) == seq.num_hidden_layers and all("moe_decode" in c for c in calls)
    held, H, W = seq.experts_held, seq.hidden_size, seq.moe_intermediate_size
    stacked = re.compile(rf"(bf16|f32)\[{held},(?:{H},{W}|{W},{H})\]")
    carries = re.compile(r" (?:parameter|get-tuple-element|tuple)\(")
    users = [line.strip()[:160] for line in body[1:] if stacked.search(line)
             and kernel not in line and not carries.search(line)]
    assert users == [], "only the kernel reads the held experts' stacked weights"
    assert not any(m.group(1) == "f32" for line in body
                   for m in stacked.finditer(line))
    E, kv, d = cfg.num_envs, seq.num_key_value_heads, seq.head_dim
    caches = re.compile(rf"bf16\[\d+,{E},{kv},(?:{seq.sliding_window}|{cfg.rollout_steps}),{d}\]")
    moved = [line.strip()[:200] for line in body
             if re.search(r" (?:copy|slice)-start\(", line) and caches.search(line)]
    assert moved == []


@pytest.mark.parametrize("kernel", [True, False])
def test_the_sparse_pass_compiled_for_a_v5e_never_holds_the_scores(v5e, kernel, monkeypatch):
    """One sparse layer of `impala_keye_vl2` as shipped over a row of 8,192,
    forward and backward, compiled for the described chip. With the kernels
    no buffer holds a block's float32 attention scores `[.., heads, 512,
    8192]`: the indexer's one score a pair, `[.., 512, 8192]`, is all. Four
    kernels: attention's forward, dq and dkv, and the selection
    (`ops/topk_select.py`) once, in the forward's loop over the blocks: the
    row's rematerialization keeps the kernel's residuals and the row's
    `keep`, so neither the attention forward nor the selection runs again
    in the backward pass. The dense masked blocks with the radix select,
    the same program off a TPU, hold the scores: the test can tell."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: kernel)
    seq = config_mod.PRESETS["impala_keye_vl2"].config.seq
    T, H = 8192, seq.hidden_size
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e)  # noqa: E731
    p = jax.tree.map(on_chip, jax.eval_shape(
        lambda: sp.init_params(jax.random.key(0), seq, 8))["params"]["layer_0"]["attn"])
    h = on_chip(jax.ShapeDtypeStruct((1, T, H), jnp.float32))
    positions = jnp.arange(T)[None]
    grad = jax.jit(jax.grad(lambda p, h: jnp.sum(sp.dsa_unroll(p, h, positions, seq)[0]),
                            argnums=(0, 1)))
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = grad.lower(p, h).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    calls = text.count('custom_call_target="tpu_custom_call"')
    lead = [np.prod([int(n) for n in m.split(",") if n])
            for m in re.findall(r"f32\[((?:\d+,)*)512,8192\]", text)]
    assert 1 in lead  # the indexer's scores
    selections = [line for line in text.splitlines()
                  if 'custom_call_target="tpu_custom_call"' in line and "topk_select" in line]
    if kernel:  # forward, dq, dkv and the selection: nothing of the forward runs again
        assert calls == 4 and len(selections) == 1 and set(lead) == {1}
    else:
        assert calls == 0 and max(lead) == seq.num_attention_heads


# -- the expert layer and the chip's share ----------------------------------

def _expert_layer(seq, key=0):
    params = sp.init_params(jax.random.key(key), seq, 32)
    return params["params"]["layer_1"]["moe"]


def _whole_layer_reference(full, h, seq):
    """The uncut layer by the plain reference: every expert held."""
    net = {**_network(seq), "expert_offset": 0}
    return _reference()._moe(full, h, net)


@pytest.mark.parametrize("dense_tokens", [0, 4096])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_reference(
        dense_tokens, monkeypatch):
    """Through the grouped matmuls (0) and through every held expert on every
    token (4096: a decode step's path)."""
    monkeypatch.setattr(sp, "MOE_DENSE_TOKENS", dense_tokens)
    seq = config_mod.PRESETS[TINY].config.seq
    whole = dataclasses.replace(seq, experts_held=seq.n_routed_experts)
    full = _expert_layer(whole)
    h = jax.random.normal(jax.random.key(5), (48, seq.hidden_size))
    want = _whole_layer_reference(full, h, whole)
    shared = sp._swiglu(full["shared"], h, jnp.float32)
    shares = seq.n_routed_experts // seq.experts_held
    total, landed = shared, 0.0
    for i in range(shares):
        cut = dataclasses.replace(seq, expert_offset=i * seq.experts_held)
        lo, hi = cut.expert_offset, cut.expert_offset + cut.experts_held
        part = {**full, "experts": jax.tree.map(lambda a: a[lo:hi], full["experts"])}
        y, stats, _ = sp.moe(part, h, cut)
        # Each share computes the shared expert whole; count it once.
        total = total + (y - shared)
        landed += float(stats["routed_here_frac"])
        # The share agrees with the reference given the same share.
        ref_part = _reference()._moe(part, h, _network(cut))
        assert float(jnp.max(jnp.abs(y - ref_part))) < 1e-5
    assert landed == pytest.approx(1.0)
    assert float(jnp.max(jnp.abs(total - want))) < 2e-5


@pytest.mark.parametrize("moe_rows, dense_tokens", [
    (4, 0), (16, 0), (4096, 0), (4096, 4096)])
def test_every_token_to_one_held_expert_drops_nothing(
        moe_rows, dense_tokens, monkeypatch):
    """The worst routing for a grouped matmul: every token's top choices
    include the same held expert. Trips of `moe_rows` assignments take them
    all, whatever the trip size."""
    monkeypatch.setattr(sp, "MOE_ROWS", moe_rows)
    monkeypatch.setattr(sp, "MOE_DENSE_TOKENS", dense_tokens)
    seq = config_mod.PRESETS[TINY].config.seq
    layer = _expert_layer(seq)
    # Expert 2 (held) and expert 9 (absent) win every token.
    bias = jnp.zeros_like(layer["bias"]).at[jnp.array([2, 9])].set(10.0)
    layer = {**layer, "bias": bias}
    h = jax.random.normal(jax.random.key(6), (40, seq.hidden_size))
    y, stats, _ = jax.jit(lambda p, h: sp.moe(p, h, seq))(layer, h)
    assert float(stats["moe_dropped"]) == 0.0
    assert float(stats["routed_here_frac"]) == pytest.approx(0.5)
    assert float(stats["expert_load_max_over_mean"]) == pytest.approx(seq.experts_held)
    want = _reference()._moe(layer, h, _network(seq))
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5
    # Gradients through the trips too (the loop's reverse rule is written out).
    g = jax.grad(lambda p, h: jnp.sum(sp.moe(p, h, seq)[0] ** 2), argnums=(0, 1))(layer, h)
    w = jax.grad(lambda p, h: jnp.sum(_reference()._moe(p, h, _network(seq)) ** 2),
                 argnums=(0, 1))(layer, h)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * max(float(jnp.max(jnp.abs(b))), 1.0)


def _ragged_dot_that_leaves_garbage(real):
    """`lax.ragged_dot` as a grouped kernel on the chip may behave: the rows
    of no group hold whatever the memory held, here NaN, in the product and
    in the product of its reverse rule (the CPU's writes zeros there)."""
    def outside(sizes, n):
        return (jnp.arange(n) >= jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def dot(lhs, rhs, sizes):
        out = real(lhs, rhs, sizes, preferred_element_type=jnp.float32)
        return jnp.where(outside(sizes, lhs.shape[0]), jnp.nan, out)

    def forward(lhs, rhs, sizes):
        return dot(lhs, rhs, sizes), (lhs, rhs, sizes)

    def backward(kept, g):
        lhs, rhs, sizes = kept
        _, pull = jax.vjp(lambda a, b: real(
            a, b, sizes, preferred_element_type=jnp.float32), lhs, rhs)
        d_lhs, d_rhs = pull(g)
        return jnp.where(outside(sizes, lhs.shape[0]), jnp.nan, d_lhs), d_rhs, None

    dot.defvjp(forward, backward)
    return lambda lhs, rhs, sizes, **_: dot(lhs, rhs, sizes)


@pytest.mark.parametrize("moe_rows", [16, 4096])
def test_rows_of_no_group_reach_no_gradient(moe_rows, monkeypatch):
    """A trip's rows past the last landed assignment belong to no group.
    Whatever a grouped matmul leaves there, NaN included, the layer's output
    and every gradient are what they are with zeros there."""
    monkeypatch.setattr(sp, "MOE_ROWS", moe_rows)
    monkeypatch.setattr(sp, "MOE_DENSE_TOKENS", 0)
    seq = config_mod.PRESETS[TINY].config.seq
    layer = _expert_layer(seq)
    h = jax.random.normal(jax.random.key(8), (40, seq.hidden_size))

    def grads():
        return jax.value_and_grad(
            lambda p, h: jnp.sum(sp.moe(p, h, seq)[0] ** 2), argnums=(0, 1))(layer, h)

    want_value, want = grads()
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _ragged_dot_that_leaves_garbage(jax.lax.ragged_dot))
    value, got = grads()
    assert float(sp.moe(layer, h, seq)[1]["routed_here_frac"]) < 1.0  # such rows exist
    assert float(value) == float(want_value)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        assert bool(jnp.array_equal(g, w)), jax.tree_util.keystr(path)


def test_the_bias_changes_which_experts_are_chosen_and_not_their_weights():
    seq = config_mod.PRESETS[TINY].config.seq
    layer = _expert_layer(seq)
    h = jax.random.normal(jax.random.key(7), (64, seq.hidden_size))
    idx0, w0 = sp.route({**layer, "bias": jnp.zeros_like(layer["bias"])}, h, seq)
    tilt = jnp.zeros_like(layer["bias"]).at[3].set(10.0)
    idx1, w1 = sp.route({**layer, "bias": tilt}, h, seq)
    assert bool(jnp.all(jnp.any(idx1 == 3, axis=-1))) and not bool(
        jnp.all(jnp.any(idx0 == 3, axis=-1)))
    # Weights are the sigmoid scores of the chosen, normalised over them and
    # scaled: the bias enters nowhere.
    s = jax.nn.sigmoid(h @ layer["router"])
    chosen = jnp.take_along_axis(s, idx1, axis=-1)
    want = chosen / chosen.sum(-1, keepdims=True) * seq.routed_scaling_factor
    assert float(jnp.max(jnp.abs(w1 - want))) < 1e-5
    assert float(jnp.max(jnp.abs(w1.sum(-1) - seq.routed_scaling_factor))) < 1e-5


# -- grouped-query layers: two kinds of cache, the prefill, the band ---------

GQA_SEQ = config_mod.PRESETS[GQA_TINY].config.seq
WINDOW = GQA_SEQ.sliding_window


def test_token_task_prefill_is_what_stepping_through_the_prompt_gives():
    env = make_token_task(vocab_size=32, horizon=12, prompt_min=4, prompt_max=6,
                          prefill_len=4)
    assert env.spec.prefill_len == 4
    state, obs = env.reset(jax.random.key(2))
    stepped, seen = state, [obs]
    for _ in range(3):  # the actions are ignored while the prompt lasts
        out = env.step(stepped, jnp.asarray(9))
        stepped = out.state
        seen.append(out.obs)
    moved, first = env.prefill(state)
    assert np.array_equal(np.asarray(first), np.stack(seen))
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, moved._replace(key=None))),
        jax.tree.leaves(jax.tree.map(np.asarray, stepped._replace(key=None)))))
    with pytest.raises(ValueError, match="at most prompt_min"):
        make_token_task(vocab_size=32, horizon=12, prompt_min=4, prompt_max=6,
                        prefill_len=5)
    assert make_token_task(vocab_size=32, horizon=12, prompt_min=4,
                           prompt_max=6).prefill is None


@pytest.mark.parametrize("P", [0, WINDOW - 1, WINDOW, WINDOW + 3])
def test_prefill_then_decode_equals_decode_from_zero_equals_reference(P):
    """The prompt's first P positions in one causal pass that fills both kinds
    of cache (of a window layer the last `WINDOW` of them), then decoding:
    the same trajectory as decoding from position 0 (the same keys sample the
    same tokens), and the reference's full causal pass over those tokens gives
    the behaviour log-probabilities the rollout recorded."""
    sets = {"num_envs": "4"}
    envs = {"prompt_min": 8, "prompt_max": 11}
    env, cfg, policy = _setup(sets, {**envs, "prefill_len": P}, preset=GQA_TINY)
    env0, _, policy0 = _setup(sets, {**envs, "prefill_len": 0}, preset=GQA_TINY)
    assert env.spec.prefill_len == P and policy.prefill is not None
    behaviour = impala.init_params(env, cfg, jax.random.key(1))
    rstate, traj = _rollout(env, cfg, policy, behaviour)
    rstate0, traj0 = _rollout(env0, cfg, policy0, behaviour)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, rstate.obs)),
        jax.tree.leaves(jax.tree.map(np.asarray, rstate0.obs))))
    for name in ("obs", "reward", "done", "terminated", "final_obs"):
        assert np.array_equal(np.asarray(getattr(traj, name)),
                              np.asarray(getattr(traj0, name))), name
    assert traj.reward.shape == (cfg.rollout_steps, 4)
    live = np.asarray(traj.obs[..., 2]) == 0
    assert live[:max(P - 1, 0)].sum() == 0 and live.sum() > 0
    assert np.array_equal(np.asarray(traj.action)[live], np.asarray(traj0.action)[live])
    assert np.abs(np.asarray(traj.log_prob - traj0.log_prob))[live].max() < 2e-5
    assert np.abs(np.asarray(traj.value - traj0.value)).max() < 2e-5
    want_logits, want_values = _reference(cfg.seq).forward(
        behaviour, traj.obs, _network(cfg.seq))
    want = jnp.take_along_axis(
        jax.nn.log_softmax(want_logits), traj.action[..., None], axis=-1)[..., 0]
    assert np.abs(np.asarray(traj.log_prob - want))[live].max() < 2e-5
    assert np.abs(np.asarray(traj.value - want_values)).max() < 2e-5


def test_a_policy_that_cannot_prefill_is_stepped_through_the_prompt():
    env, cfg, policy = _setup({"num_envs": "4"}, {"prompt_min": 4, "prefill_len": 3,
                                                  "prompt_max": 6})
    assert policy.prefill is None and env.spec.prefill_len == 3
    _, traj = _rollout(env, cfg, policy, impala.init_params(env, cfg, jax.random.key(1)))
    assert np.asarray(traj.log_prob)[:3].min() < 0.0  # sampled, not stood in for


def _gqa_layer(kind, key=0):
    params = sp.init_params(jax.random.key(key), GQA_SEQ, 32)
    index = GQA_SEQ.layer_types.index(kind)
    return params["params"][f"layer_{index}"]["attn"]


@pytest.mark.parametrize("kind", sp.GQA_KINDS)
def test_a_key_outside_the_band_changes_no_output_of_the_causal_pass(kind):
    """Position `j`'s hidden state changed beyond recognition: the queries
    before `j`, and in a window layer those `WINDOW` or more after it, give
    the same output bit for bit. (Finite garbage: inside a block's slice a
    masked key's probability is exactly zero, and zero times a finite value
    is zero.)"""
    E, T, j = 2, 16, 5
    p = _gqa_layer(kind)
    h = jax.random.normal(jax.random.key(3), (E, T, GQA_SEQ.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(T), (E, T))
    out, _ = sp.gqa_unroll(p, h, positions, GQA_SEQ, kind)
    moved, _ = sp.gqa_unroll(p, h.at[:, j].set(1e4), positions, GQA_SEQ, kind)
    same = np.all(np.asarray(out) == np.asarray(moved), axis=(0, 2))
    sees = [t >= j and (kind == "full_attention" or t - j < WINDOW) for t in range(T)]
    assert same.tolist() == [not s for s in sees]


@pytest.mark.parametrize("position", [0, WINDOW - 2, WINDOW, 3 * WINDOW + 1])
@pytest.mark.parametrize("kind", sp.GQA_KINDS)
def test_the_decode_reads_no_slot_that_is_not_filled(kind, position):
    """NaN keys and huge values in every slot past the fill (a ring that has
    wrapped has none): the step's output is finite and the same. A masked
    score is selected away, so a NaN key never reaches the softmax; a masked
    slot's probability is exactly zero, and what a cache holds there is zeros
    or an older position's values, never NaN."""
    E = 3
    p = _gqa_layer(kind)
    slots = sp.window_of(GQA_SEQ, kind, 16)
    shape = (2, E, GQA_SEQ.num_key_value_heads, slots, GQA_SEQ.head_dim)
    k, v = (jax.random.normal(jax.random.key(i), shape) for i in (4, 5))
    unfilled = (jnp.arange(slots) > position)[:, None]
    dirty = (jnp.where(unfilled, jnp.nan, k), jnp.where(unfilled, 1e30, v))
    h = jax.random.normal(jax.random.key(6), (E, GQA_SEQ.hidden_size))
    positions = jnp.full((E,), position)
    want, (k1, _) = sp.gqa_step(p, h, positions, (k, v), 1, position, GQA_SEQ, kind)
    got, _ = sp.gqa_step(p, h, positions, dirty, 1, position, GQA_SEQ, kind)
    assert np.isfinite(np.asarray(got)).all()
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # The token's key went to slot `position mod slots` of layer 1 alone.
    changed = np.any(np.asarray(k1 != k), axis=(1, 2, 4))
    assert changed[0].sum() == 0 and changed[1].tolist() == [
        s == position % slots for s in range(slots)]


def test_each_kind_of_layer_has_its_cache_at_its_own_size():
    cache = sp.init_cache(GQA_SEQ, 5, 16)
    lead = (5, GQA_SEQ.num_key_value_heads)
    assert [a.shape for a in cache["sliding_attention"]] == [
        (3, *lead, WINDOW, GQA_SEQ.head_dim)] * 2
    assert [a.shape for a in cache["full_attention"]] == [
        (1, *lead, 16, GQA_SEQ.head_dim)] * 2
    # 3 x 4 + 16 of the 4 x 16 slots a row that a uniform cache would keep.
    slots = sum(a.shape[0] * a.shape[3] for a, _ in cache.values())
    assert slots == 28


def test_yarn_frequencies_of_the_published_keys_against_a_hand_count():
    """`rope_parameters.full_attention` of the source: theta 5e5, factor 16,
    original context 8,192, beta_fast 32, beta_slow 1, head size 128. The pair
    that turns n times over 8,192 positions is 128 ln(8192 / (2 pi n)) / (2 ln
    5e5): 18.08 for n = 32, 34.98 for n = 1, so pairs up to 18 keep their
    frequency, pairs from 35 on take a sixteenth, and pair 19 is 1/17 of the
    way between."""
    seq = config_mod.PRESETS["impala_mellum2"].config.seq
    inv, factor = sp.rope_frequencies(seq, "full_attention")
    plain, one = sp.rope_frequencies(seq, "sliding_attention")
    base = 5e5 ** (-np.arange(64) / 64.0)
    assert one == 1.0 and np.allclose(plain, base, rtol=1e-6)
    assert factor == 1.2772588722239782 == pytest.approx(0.1 * np.log(16.0) + 1.0)
    assert np.allclose(inv[:19], base[:19], rtol=1e-6)
    assert np.allclose(inv[35:], base[35:] / 16.0, rtol=1e-6)
    assert inv[19] == pytest.approx(base[19] * (16 / 17) + base[19] / 16 / 17, rel=1e-6)
    assert inv[26] == pytest.approx(base[26] * (9 / 17) + base[26] / 16 * (8 / 17), rel=1e-6)
    # The reference writes the same arithmetic out for itself.
    ref_inv, ref_factor = _reference(seq).yarn_frequencies(
        128, _network(seq)["rope_parameters"]["full_attention"])
    assert np.array_equal(ref_inv, inv) and ref_factor == factor


def test_the_softmax_router_is_the_references_ties_included():
    """Softmax over all the logits, top-k by probability, renormalised over
    the chosen, no bias, no scaling; two experts with the same router column
    tie at every token, and the lower index wins on both sides."""
    seq = dataclasses.replace(GQA_SEQ, n_routed_experts=64, experts_held=16,
                              num_experts_per_tok=8)
    router = jax.random.normal(jax.random.key(7), (seq.hidden_size, 64))
    router = router.at[:, 9].set(router[:, 3]).at[:, 40].set(router[:, 41])
    h = jax.random.normal(jax.random.key(8), (256, seq.hidden_size))
    idx, w = sp.route({"router": router}, h, seq)
    want_idx, want_w = _reference(seq).route(router, h, 8)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    assert np.abs(np.asarray(w - want_w)).max() < 1e-6
    assert np.abs(np.asarray(w).sum(-1) - 1.0).max() < 1e-6
    both = (np.asarray(idx) == 3).any(-1) & (np.asarray(idx) == 9).any(-1)
    first = np.argmax(np.asarray(idx) == 3, -1) < np.argmax(np.asarray(idx) == 9, -1)
    assert both.sum() > 10 and first[both].all()
    prob = jax.nn.softmax(h @ router)
    assert np.allclose(np.asarray(w), np.asarray(
        jnp.take_along_axis(prob, idx, -1) / jnp.take_along_axis(prob, idx, -1).sum(
            -1, keepdims=True)), atol=1e-6)


@pytest.mark.parametrize("dense_tokens", [0, 4096])
def test_the_four_shares_of_a_softmax_routed_layer_add_up_to_the_uncut_reference(
        dense_tokens, monkeypatch):
    """64 experts, top-8, 16 held at offsets 0, 16, 32, 48, no shared expert:
    the four chips' parts sum to the reference's layer with every expert."""
    monkeypatch.setattr(sp, "MOE_DENSE_TOKENS", dense_tokens)
    seq = dataclasses.replace(GQA_SEQ, n_routed_experts=64, experts_held=16,
                              num_experts_per_tok=8)
    whole = dataclasses.replace(seq, experts_held=64)
    full = sp.init_params(jax.random.key(0), whole, 32)["params"]["layer_1"]["moe"]
    assert set(full) == {"router", "experts"}
    h = jax.random.normal(jax.random.key(5), (48, seq.hidden_size))
    ref = _reference(seq)
    want = ref.moe(full, h, {**_network(whole), "expert_offset": 0})
    total, landed = 0.0, 0.0
    for offset in (0, 16, 32, 48):
        cut = dataclasses.replace(seq, expert_offset=offset)
        part = {**full, "experts": jax.tree.map(
            lambda a: a[offset:offset + 16], full["experts"])}
        y, stats, _ = sp.moe(part, h, cut)
        assert float(jnp.max(jnp.abs(y - ref.moe(part, h, _network(cut))))) < 1e-5
        total, landed = total + y, landed + float(stats["routed_here_frac"])
    assert landed == pytest.approx(1.0)
    assert float(jnp.max(jnp.abs(total - want))) < 2e-5


def test_the_grouped_query_preset_is_the_configuration_the_benchmark_states():
    with open(os.path.join(ROOT, "benchmark/configs/impala_mellum2.json")) as fh:
        cfg = json.load(fh)
    preset = config_mod.PRESETS["impala_mellum2"]
    seq, net = preset.config.seq, cfg["network"]
    for key, value in net.items():
        if hasattr(seq, key):
            got = getattr(seq, key)
            assert (list(got) if isinstance(got, tuple) else got) == value, key
    assert net["layer_types"] == cfg["layer_types"][:4] == list(seq.layer_types)
    yarn = cfg["rope_parameters"]["full_attention"]
    assert net["rope_parameters"] == cfg["rope_parameters"]
    assert (yarn["factor"], yarn["original_max_position_embeddings"], yarn["beta_fast"],
            yarn["beta_slow"], yarn["attention_factor"], yarn["rope_theta"]) == (
        seq.rope_factor, seq.rope_original_max_position_embeddings, seq.rope_beta_fast,
        seq.rope_beta_slow, seq.rope_attention_factor, seq.rope_theta)
    assert cfg["rope_parameters"]["sliding_attention"]["rope_theta"] == seq.rope_theta
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                "sliding_window", "moe_intermediate_size", "num_experts_per_tok",
                "num_hidden_layers", "rms_norm_eps"):
        assert cfg[key] == net[key] == getattr(seq, key), key
    assert cfg["num_experts"] == seq.experts_held == 16
    assert cfg["published"]["num_experts"] == seq.n_routed_experts == 64
    assert (seq.scoring_func, seq.n_shared_experts, seq.first_k_dense_replace,
            seq.routed_scaling_factor) == ("softmax", 0, 0, 1.0)
    assert cfg["norm_topk_prob"] is True and set(cfg["mlp_layer_types"]) == {"sparse"}
    assert net["vocab_size"] == preset.env_kwargs["vocab_size"] == cfg["vocab_size"]
    algo = cfg["algorithm"]
    for key in ("num_envs", "rollout_steps", "gamma", "lam", "rho_bar", "c_bar",
                "value_coef", "entropy_coef", "lr", "actor_refresh_every"):
        assert algo[key] == getattr(preset.config, key), key
    assert preset.env_kwargs["horizon"] == preset.config.rollout_steps == 4096
    assert preset.env_kwargs["prefill_len"] == preset.env_kwargs["prompt_min"] == 3072
    shapes = jax.eval_shape(
        lambda: sp.init_params(jax.random.key(0), seq, net["vocab_size"]))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 595_155_457 and "595,155,457" in cfg["deployment"]["parameters_here"]


# -- sparse layers: QK-norm, the indexer, key selection, a third cache ------

SPARSE_SEQ = config_mod.PRESETS[SPARSE_TINY].config.seq
TOPK = SPARSE_SEQ.sa_config.topk


def _as_top_k(scores, valid, k):
    """The set `lax.top_k` picks of the valid scores, as a mask."""
    masked = jnp.where(valid, scores, -jnp.inf)
    _, idx = jax.lax.top_k(masked, min(k, scores.shape[-1]))
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, idx].set(True) & valid


@pytest.mark.parametrize("S, k", [(7, 1), (16, 5), (33, 5), (100, 20), (300, 64), (12, 12)])
@pytest.mark.parametrize("ties", [False, True])
def test_the_selection_is_lax_top_ks_ties_included(S, k, ties):
    """`top_k_mask` finds the k-th largest score digit by digit and keeps the
    first of the keys equal to it: the set `lax.top_k` picks, ties to the
    lower position, over keys some of which are not valid, with whole rows
    of one value, fewer valid keys than k, and negative scores."""
    key = jax.random.key(S * 7 + k)
    scores = jax.random.normal(key, (8, S)) * 3
    if ties:  # a handful of values: every row has ties at its threshold
        scores = jnp.round(scores) / 2
        scores = scores.at[0].set(0.0).at[1].set(-1.5)
    valid = jax.random.uniform(jax.random.fold_in(key, 1), (8, S)) < 0.6
    valid = valid.at[2].set(True).at[3].set(jnp.arange(S) < 2)
    got = sp.top_k_mask(scores, valid, k)
    assert np.array_equal(np.asarray(got), np.asarray(_as_top_k(scores, valid, k)))
    assert np.array_equal(np.asarray(got.sum(-1)),
                          np.minimum(np.asarray(valid.sum(-1)), k))


def _sparse_layer(key=0, seq=SPARSE_SEQ):
    """A sparse layer's attention with norms and bias that are not the
    identity, so that leaving one out shows."""
    p = sp.init_params(jax.random.key(key), seq, 32)["params"]["layer_1"]["attn"]
    noisy = lambda x, i: x * (1.0 + 0.5 * jax.random.normal(  # noqa: E731
        jax.random.key(100 + i), x.shape))
    ip = p["indexer"]
    return {**p, "q_norm": noisy(p["q_norm"], 1), "k_norm": noisy(p["k_norm"], 2),
            "indexer": {**ip, "k_norm": noisy(ip["k_norm"], 3),
                        "k_bias": 0.3 * jax.random.normal(jax.random.key(4), ip["k_bias"].shape)}}


def _sparse_params(key=1):
    params = sp.init_params(jax.random.key(key), SPARSE_SEQ, 64)
    layers = {name: {**layer, "attn": _sparse_layer(i)}
              for i, (name, layer) in enumerate(params["params"].items())
              if name.startswith("layer_")}
    return {"params": {**params["params"], **layers}}


def _tokens(E=3, T=16, key=2, V=64):
    tokens = jax.random.randint(jax.random.key(key), (E, T), 0, V)
    positions = jnp.broadcast_to(jnp.arange(T), (E, T))
    return jnp.stack([tokens, positions, jnp.zeros_like(tokens)], -1)


def test_the_sparse_causal_pass_is_the_references_and_drops_keys():
    """At a `topk` of 5 in rows of 16 most queries drop keys; the program's
    causal pass (blocks of `q_chunk_size`, through one `lax.map`) gives the
    plain reference's logits and values (`lax.top_k` over float32 scores,
    mRoPE written out, per-head norms and the indexer key's LayerNorm)."""
    params, obs = _sparse_params(), _tokens()
    logits, values = sp.logits_and_values(params, obs, SPARSE_SEQ)
    want, want_values = _reference(SPARSE_SEQ).forward(
        params, jnp.swapaxes(obs, 0, 1), _network(SPARSE_SEQ))
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(jnp.swapaxes(logits, 0, 1) - want))) < 2e-5 * scale
    assert float(jnp.max(jnp.abs(values.T - want_values))) < 2e-5
    # The same without the selection is another model: the indexer decides.
    dense = dataclasses.replace(SPARSE_SEQ, sa_config=dataclasses.replace(
        SPARSE_SEQ.sa_config, topk=16))
    all_keys, _ = sp.logits_and_values(params, obs, dense)
    assert float(jnp.max(jnp.abs(all_keys - logits))) > 100 * 2e-5 * scale


def test_a_selection_off_by_one_key_fails_the_comparison(monkeypatch):
    """The planted fault: every query that drops keys swaps its last
    selected key for the first one it dropped. The logits move by far more
    than the CPU's limit on `logits_attn` (1e-4 of their scale)."""
    params, obs = _sparse_params(), _tokens()
    want, _ = _reference(SPARSE_SEQ).forward(
        params, jnp.swapaxes(obs, 0, 1), _network(SPARSE_SEQ))
    right = sp.top_k_mask

    def off_by_one(scores, valid, k):
        keep = right(scores, valid, k)
        dropped = valid & ~keep
        last = jnp.arange(keep.shape[-1]) == jnp.max(
            jnp.where(keep, jnp.arange(keep.shape[-1]), -1), axis=-1, keepdims=True)
        first = jnp.arange(keep.shape[-1]) == jnp.argmax(dropped, axis=-1)[..., None]
        swap = jnp.any(dropped, axis=-1, keepdims=True)
        return jnp.where(swap, (keep & ~last) | first, keep)

    monkeypatch.setattr(sp, "top_k_mask", off_by_one)
    logits, _ = sp.logits_and_values(params, obs, SPARSE_SEQ)
    err = float(jnp.max(jnp.abs(jnp.swapaxes(logits, 0, 1) - want)) / jnp.max(jnp.abs(want)))
    assert err > 1e-2, err


def test_mrope_with_text_positions_is_rope():
    """A text token's (t, h, w) are one position, so the source's sections
    (16 / 24 / 24 of 64 frequencies) turn every frequency by it: the
    reference's mRoPE, its plain RoPE and the program's rotation agree."""
    ref = _reference(SPARSE_SEQ)
    seq = config_mod.PRESETS["impala_keye_vl2"].config.seq
    x = jax.random.normal(jax.random.key(0), (40, 4, 128))
    pos = jnp.arange(40) * 97
    plain = ref.rope(x, pos, 1e7)
    assert np.abs(np.asarray(ref.mrope(x, pos, [16, 24, 24], 1e7) - plain)).max() < 1e-6
    ours = sp._rope_halves(x, pos[:, None], sp.rope_frequencies(seq, sp.SPARSE))
    assert np.abs(np.asarray(ours - plain)).max() < 1e-4
    # Positions that differ by component would not: the sections are real.
    with pytest.raises(ValueError, match="does not split"):
        ref.mrope(x, pos, [16, 24, 16], 1e7)


def test_the_per_head_norms_come_before_rope():
    """q and k of a sparse layer are RMS-normed per head with their learned
    scales and then rotated; a grouped-query layer of the other kinds has no
    norm and is as before."""
    p = _sparse_layer()
    h = jax.random.normal(jax.random.key(5), (6, SPARSE_SEQ.hidden_size))
    pos = jnp.arange(6) + 3
    freq = sp.rope_frequencies(SPARSE_SEQ, sp.SPARSE)
    for which in "qk":
        y = (h @ p[f"w_{which}"]).reshape(6, -1, SPARSE_SEQ.head_dim)
        normed = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6) * p[f"{which}_norm"]
        want = sp._rope_halves(normed, pos[:, None], freq)
        got = sp._gqa_project(p, h, pos, SPARSE_SEQ, sp.SPARSE, which)
        assert np.abs(np.asarray(got - want)).max() < 1e-5
        rotated = sp._rope_halves(y, pos[:, None], freq)
        after = rotated / jnp.sqrt(jnp.mean(rotated ** 2, -1, keepdims=True) + 1e-6) * p[
            f"{which}_norm"]
        assert np.abs(np.asarray(got - after)).max() > 1e-2  # the order shows
    plain = {k: p[k] for k in ("w_q", "w_k", "w_v", "w_o")}
    y = sp._gqa_project(plain, h, pos, GQA_SEQ, "full_attention", "q")
    want = sp._rope_halves((h @ p["w_q"]).reshape(6, -1, GQA_SEQ.head_dim), pos[:, None],
                           sp.rope_frequencies(GQA_SEQ, "full_attention"))
    assert np.abs(np.asarray(y - want)).max() < 1e-5


@pytest.mark.parametrize("P", [0, TOPK - 1, TOPK, TOPK + 4])
def test_sparse_prefill_then_decode_equals_the_references_full_forward(P):
    """The prompt's first P positions in one causal pass that fills keys,
    values and indexer keys, then decoding, each step's indexer scoring
    every filled slot: the behaviour log-probabilities and values the
    rollout recorded are the reference's full causal pass over its tokens,
    and the trajectory is the one decoding from position 0 gives."""
    sets = {"num_envs": "4"}
    envs = {"prompt_min": 9, "prompt_max": 11}
    env, cfg, policy = _setup(sets, {**envs, "prefill_len": P}, preset=SPARSE_TINY)
    env0, _, policy0 = _setup(sets, {**envs, "prefill_len": 0}, preset=SPARSE_TINY)
    assert policy.prefill is not None and env.spec.prefill_len == P
    behaviour = impala.init_params(env, cfg, jax.random.key(1))
    _, traj = _rollout(env, cfg, policy, behaviour)
    _, traj0 = _rollout(env0, cfg, policy0, behaviour)
    live = np.asarray(traj.obs[..., 2]) == 0
    assert live.sum() > 0 and np.array_equal(np.asarray(traj.obs), np.asarray(traj0.obs))
    assert np.array_equal(np.asarray(traj.action)[live], np.asarray(traj0.action)[live])
    want_logits, want_values = _reference(cfg.seq).forward(
        behaviour, traj.obs, _network(cfg.seq))
    want = jnp.take_along_axis(
        jax.nn.log_softmax(want_logits), traj.action[..., None], axis=-1)[..., 0]
    assert np.abs(np.asarray(traj.log_prob - want))[live].max() < 2e-5
    assert np.abs(np.asarray(traj.value - want_values)).max() < 2e-5


@pytest.mark.parametrize("position", [0, TOPK - 2, TOPK + 1, 15])
def test_the_sparse_decode_reads_no_slot_that_is_not_filled(position):
    """NaN keys and indexer keys, huge values in every slot past the fill:
    the step's output is finite and the same; the token's three go to slot
    `position` of layer 1 alone."""
    E, T = 3, 16
    p = _sparse_layer()
    shape = (2, E, SPARSE_SEQ.num_key_value_heads, T, SPARSE_SEQ.head_dim)
    k, v = (jax.random.normal(jax.random.key(i), shape) for i in (4, 5))
    keys = jax.random.normal(jax.random.key(6), (2, E, T, SPARSE_SEQ.sa_config.indexer_head_dim))
    unfilled = jnp.arange(T) > position
    dirty = (jnp.where(unfilled[:, None], jnp.nan, k), jnp.where(unfilled[:, None], 1e30, v),
             jnp.where(unfilled[:, None], jnp.nan, keys))
    h = jax.random.normal(jax.random.key(7), (E, SPARSE_SEQ.hidden_size))
    positions = jnp.full((E,), position)
    want, (k1, _, keys1) = sp.dsa_step(p, h, positions, (k, v, keys), 1, position, SPARSE_SEQ)
    got, _ = sp.dsa_step(p, h, positions, dirty, 1, position, SPARSE_SEQ)
    assert np.isfinite(np.asarray(got)).all()
    assert np.array_equal(np.asarray(got), np.asarray(want))
    changed = np.any(np.asarray(k1 != k), axis=(1, 2, 4))
    assert changed[0].sum() == 0 and changed[1].tolist() == [s == position for s in range(T)]
    changed = np.any(np.asarray(keys1 != keys), axis=(1, 3))
    assert changed[0].sum() == 0 and changed[1].tolist() == [s == position for s in range(T)]


def test_a_sparse_layer_keeps_three_caches_and_its_prefill_fills_them():
    cache = sp.init_cache(SPARSE_SEQ, 5, 16)
    L, lead = SPARSE_SEQ.num_hidden_layers, (5, SPARSE_SEQ.num_key_value_heads)
    assert list(cache) == [sp.SPARSE]
    assert [a.shape for a in cache[sp.SPARSE]] == [
        (L, *lead, 16, SPARSE_SEQ.head_dim)] * 2 + [
        (L, 5, 16, SPARSE_SEQ.sa_config.indexer_head_dim)]
    assert all(a.dtype == jnp.float32 for a in cache[sp.SPARSE])  # the tiny preset's
    obs = _tokens(E=5, T=7)
    _, _, filled = sp.trunk(_sparse_params(), obs, SPARSE_SEQ, cache)
    for a in filled[sp.SPARSE]:
        a = np.asarray(a)
        written = np.any(a != 0, axis=tuple(i for i in range(a.ndim) if i not in (0, a.ndim - 2)))
        assert written[:, :7].all() and not written[:, 7:].any()


def test_the_indexer_gets_no_gradient_and_everything_else_does():
    """The selection is discrete and the indexer's scores reach no output,
    so the policy's loss gives the indexer exactly zero gradient (no
    alignment loss is built); the per-head norms and the projections learn."""
    env, cfg, policy = _setup({"num_envs": "4"}, {"prompt_min": 3, "prompt_max": 6},
                              preset=SPARSE_TINY)
    params = impala.init_params(env, cfg, jax.random.key(0))
    rstate, traj = _rollout(env, cfg, policy, impala.init_params(env, cfg, jax.random.key(1)))
    grads = jax.grad(lambda p: impala.impala_loss(
        p, policy, traj, rstate.obs, cfg, False)[0])(params)
    attn = grads["params"]["layer_0"]["attn"]
    assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in jax.tree.leaves(attn["indexer"]))
    for name in ("w_q", "w_k", "w_v", "w_o", "q_norm", "k_norm"):
        assert float(jnp.max(jnp.abs(attn[name]))) > 0.0, name


@pytest.mark.parametrize("dense_tokens", [0, 4096])
def test_the_eight_shares_of_a_128_expert_layer_add_up_to_the_uncut_reference(
        dense_tokens, monkeypatch):
    """128 experts, top-8, 16 held at offsets 0, 16, ..., 112 (the sparse
    configuration's router and share at toy widths): the eight chips' parts
    sum to the reference's layer with every expert."""
    monkeypatch.setattr(sp, "MOE_DENSE_TOKENS", dense_tokens)
    seq = dataclasses.replace(SPARSE_SEQ, n_routed_experts=128, experts_held=16,
                              num_experts_per_tok=8)
    whole = dataclasses.replace(seq, experts_held=128)
    full = sp.init_params(jax.random.key(0), whole, 32)["params"]["layer_1"]["moe"]
    assert set(full) == {"router", "experts"}
    h = jax.random.normal(jax.random.key(5), (48, seq.hidden_size))
    ref = _reference(seq)
    want = ref.moe(full, h, {**_network(whole), "expert_offset": 0})
    total, landed = 0.0, 0.0
    for offset in range(0, 128, 16):
        cut = dataclasses.replace(seq, expert_offset=offset)
        part = {**full, "experts": jax.tree.map(
            lambda a: a[offset:offset + 16], full["experts"])}
        y, stats, _ = sp.moe(part, h, cut)
        assert float(jnp.max(jnp.abs(y - ref.moe(part, h, _network(cut))))) < 1e-5
        total, landed = total + y, landed + float(stats["routed_here_frac"])
    assert landed == pytest.approx(1.0)
    assert float(jnp.max(jnp.abs(total - want))) < 2e-5


def test_the_sparse_preset_is_the_configuration_the_benchmark_states():
    with open(os.path.join(ROOT, "benchmark/configs/impala_keye_vl2.json")) as fh:
        cfg = json.load(fh)
    preset = config_mod.PRESETS["impala_keye_vl2"]
    seq, net = preset.config.seq, cfg["network"]
    for key, value in net.items():
        if hasattr(seq, key) and key != "sa_config":
            assert getattr(seq, key) == value, key
    assert net["sa_config"] == cfg["sa_config"]
    assert dataclasses.asdict(seq.sa_config) == {
        k: v for k, v in net["sa_config"].items() if k != "indexer_num_kv_heads"}
    # One indexer key a position, as the program builds it.
    assert net["sa_config"]["indexer_num_kv_heads"] == 1
    assert seq.kinds == (sp.SPARSE,) * 4 and seq.layer_types == ()
    assert net["rope_scaling"] == cfg["rope_scaling"] and seq.rope_theta == cfg["rope_theta"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                "moe_intermediate_size", "num_experts_per_tok", "num_hidden_layers",
                "rms_norm_eps"):
        assert cfg[key] == net[key] == getattr(seq, key), key
    assert cfg["num_experts"] == cfg["num_local_experts"] == seq.experts_held == 16
    assert cfg["published"]["num_experts"] == seq.n_routed_experts == 128
    assert (seq.scoring_func, seq.n_shared_experts, seq.first_k_dense_replace,
            seq.routed_scaling_factor) == ("softmax", 0, 0, 1.0)
    assert cfg["norm_topk_prob"] is True and cfg["mlp_only_layers"] == []
    assert net["vocab_size"] == preset.env_kwargs["vocab_size"] == cfg["vocab_size"]
    algo = cfg["algorithm"]
    for key in ("num_envs", "rollout_steps", "gamma", "lam", "rho_bar", "c_bar",
                "value_coef", "entropy_coef", "lr", "actor_refresh_every"):
        assert algo[key] == getattr(preset.config, key), key
    assert preset.env_kwargs["horizon"] == preset.config.rollout_steps == 8192
    assert preset.env_kwargs["prefill_len"] == preset.env_kwargs["prompt_min"] == 6144
    shapes = jax.eval_shape(
        lambda: sp.init_params(jax.random.key(0), seq, net["vocab_size"]))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 465_393_153 and "465,393,153" in cfg["deployment"]["parameters_here"]


# sha256 of `jit(train_step).lower(abstract state).as_text()` on the CPU, at
# the commit before grouped-query layers and the prefill came (PERF.md,
# Findings): what the pixel cells run has not changed by an operation since.
# The token cell's is PR 35's: its step gained one output, the constant 1.0
# of `decode_experts_read_frac` (its decode runs the batched matmuls over
# every held expert: `MOE_KERNEL_ASSIGNMENTS`), and is otherwise the
# parent's `4cf61beb...` operation for operation; nothing else may move it.
PARENT_STEPS = {
    ("impala_pong", 64): "219e8237219554c914b2017673191b49b87a929f91be90f3cabf9529cb73105f",
    ("impala_pong", 4096): "4d1d084ed5f57706826f78d4e87b8cc5435bcc197d141e7606556de295f1ee23",
    ("impala_joyai_flash", 64): "03b2be6673c3e12ed4f3525fd129330562ad5b8a609be6ca3572768660d3ebdb",
    # The window-and-full cell's as shipped, at PR 35's commit: the sparse
    # kind shares its projection, cache and prefill code and moves none of it.
    ("impala_mellum2", 8): "7f0b0181a524a070e85f157d888c7001b19d305532bf072cd41917579cebf130",
    # The sparse cell's as shipped. Off a TPU its causal pass takes the
    # dense masked blocks, so its text is PR 36's `83a905c3...` but for one
    # output, `sparse_tiles_frac` (a block's tiles that hold a selected pair,
    # counted after its attention, summed over blocks, meaned over rows and
    # layers): no operation of the parent's is gone or changed.
    ("impala_keye_vl2", 4): "a7175f5f2336219ddad961f914f43b75d607eb04983d9dbcf01a96683cc74463",
}


@pytest.mark.parametrize("name, num_envs", list(PARENT_STEPS))
def test_the_standing_cells_step_lowers_to_the_parents_text(
        name, num_envs, monkeypatch):
    import hashlib

    monkeypatch.undo()  # the shipped blocking, not `toy_blocking`'s
    preset = config_mod.resolve(name, None, None, {"num_envs": str(num_envs)})
    cfg = preset.config
    env, _ = train.build_env(preset.env, preset.algo, cfg, 0,
                             env_kwargs=preset.env_kwargs)
    state = jax.eval_shape(lambda: impala.init_state(env, cfg, jax.random.key(0)))
    text = jax.jit(impala.make_train_step(env, cfg), donate_argnums=0).lower(
        state).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STEPS[name, num_envs]


# -- the preset, the seam, the eval ------------------------------------------

def test_the_preset_refuses_an_episode_that_is_not_one_unroll():
    preset = config_mod.resolve(TINY, None, None, {"rollout_steps": "8"})
    env, _ = train.build_env(preset.env, preset.algo, preset.config, 0,
                             env_kwargs=preset.env_kwargs)
    with pytest.raises(ValueError, match="exactly one unroll.*--env-set horizon=8"):
        impala.make_train_step(env, preset.config)
    with pytest.raises(ValueError, match="reads .token id, position, is_prompt."):
        from actor_critic_tpu.envs import make_cartpole
        impala.make_policy(make_cartpole(), preset.config)


def test_dotted_overrides_reach_the_policys_group():
    cfg = config_mod.resolve(TINY, None, None, {
        "seq.hidden_size": "48", "lr": "1e-3", "seq.compute_dtype": "bfloat16"}).config
    assert (cfg.seq.hidden_size, cfg.lr, cfg.seq.compute_dtype) == (48, 1e-3, "bfloat16")
    with pytest.raises(KeyError, match="no field 'nope'"):
        config_mod.resolve(TINY, None, None, {"seq.nope": "1"})
    with pytest.raises(KeyError, match="is not set in this preset"):
        config_mod.resolve("impala_pong", None, None, {"seq.hidden_size": "48"})


def test_the_shipped_preset_is_the_configuration_the_benchmark_states():
    """Every width of `benchmark/configs/impala_joyai_flash.json` is the
    preset's, and the catalog's keys at the file's top level agree with its
    `network` group."""
    with open(os.path.join(ROOT, "benchmark/configs/impala_joyai_flash.json")) as fh:
        cfg = json.load(fh)
    preset = config_mod.PRESETS["impala_joyai_flash"]
    seq, net = preset.config.seq, cfg["network"]
    for key, value in net.items():
        if hasattr(seq, key):
            assert getattr(seq, key) == value, key
    assert net["vocab_size"] == preset.env_kwargs["vocab_size"] == cfg["vocab_size"]
    assert cfg["n_routed_experts"] == seq.experts_held == 16
    assert cfg["published"]["n_routed_experts"] == seq.n_routed_experts == 256
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor", "first_k_dense_replace",
                "num_hidden_layers", "rms_norm_eps"):
        assert cfg[key] == net[key] == getattr(seq, key), key
    assert cfg["rope_theta"] == seq.rope_theta
    algo = cfg["algorithm"]
    for key in ("num_envs", "rollout_steps", "gamma", "lam", "rho_bar", "c_bar",
                "value_coef", "entropy_coef", "lr", "actor_refresh_every"):
        assert algo[key] == getattr(preset.config, key), key
    assert preset.env_kwargs["horizon"] == preset.config.rollout_steps == 512
    # 565M parameters, as the file says.
    shapes = jax.eval_shape(
        lambda: sp.init_params(jax.random.key(0), seq, net["vocab_size"]))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 564.5e6 < count < 565.5e6


def test_greedy_eval_threads_the_cache():
    """`--eval-every`'s program: greedy decode through the policy's carry,
    equal to decoding by hand."""
    env, cfg, policy = _setup({"num_envs": "4"})
    state = impala.init_state(env, cfg, jax.random.key(0))
    # Make the greedy policy a copier of the current token, so the return is known.
    eval_fn = jax.jit(impala.make_eval_fn(env, cfg), static_argnums=(2, 3))
    got = float(eval_fn(state, jax.random.key(9), 8, cfg.rollout_steps + 8))

    keys = jax.random.split(jax.random.key(9), 8)
    env_state, obs = jax.vmap(env.reset)(keys)
    carry, ret = policy.init_carry(8), np.zeros(8)
    alive = np.ones(8)
    for _ in range(cfg.rollout_steps):
        dist, _, carry = policy.step(state.params, obs, carry)
        out = jax.vmap(env.step)(env_state, dist.mode())
        env_state, obs = out.state, out.obs
        ret += np.asarray(out.reward) * alive
        alive *= 1.0 - np.asarray(out.done)
    assert got == pytest.approx(float(ret.mean()), abs=1e-6)


@BOTH
def test_the_tiny_presets_return_rises_on_the_copy_task(preset):
    env, cfg, _ = _setup(preset=preset)
    state = impala.init_state(env, cfg, jax.random.key(0))
    step = jax.jit(impala.make_train_step(env, cfg), donate_argnums=0)
    returns = []
    for _ in range(250):
        state, m = step(state)
        returns.append(m["mean_finished_return"])
    first, last = float(np.mean(returns[:10])), float(np.mean(returns[-10:]))
    assert float(m["moe_dropped"]) == 0.0 and np.isfinite(float(m["loss"]))
    # Chance is 1 / 7 a token; a policy that learnt to repeat does far better.
    assert last > 0.35 and last > 2 * first, (first, last)
