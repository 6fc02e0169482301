"""The linear-attention kind of layer (`models/gated_delta.py`) and the
hybrid block it sits in (`impala_qwen3_next_tiny`: Gated DeltaNet and gated
full attention 3:1, a gated shared expert), at toy widths on the CPU, against
the plain reference (`benchmark/reference/impala_qwen3_next.py`): the chunked
pass is the per-token recurrence across chunk boundaries, at a strong and a
weak decay; step through the caches = one causal pass = the reference;
prefill then decode leaves the reference's state and conv tail; partial
RoPE, the output gate, the zero-centred norms and the shared expert's gate
are applied; the experts' shares add up to the uncut layer."""

import dataclasses
import os
import sys

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
from actor_critic_tpu.models import gated_delta as gd  # noqa: E402
from actor_critic_tpu.models import seq_policy as sp  # noqa: E402
from benchmark import harness  # noqa: E402

TINY = "impala_qwen3_next_tiny"
SEQ = config_mod.PRESETS[TINY].config.seq


def _reference():
    return harness.load_module("reference", "impala_qwen3_next")


def _network(seq):
    return dataclasses.asdict(seq)


@pytest.fixture(autouse=True)
def toy_blocking(monkeypatch):
    """`seq_policy`'s blocking at the toy widths' scale: a decode step takes
    the batched matmuls, and the linear layers' causal pass runs a row a
    trip."""
    monkeypatch.setattr(sp, "MOE_ROWS", 256)
    monkeypatch.setattr(sp, "MOE_DENSE_TOKENS", 32)
    monkeypatch.setattr(sp, "ATTN_QUERIES", 6)


def _inputs(T, decay, seed=0, E=2, nv=4, dk=8, dv=8):
    """Seeded q, k (unit rows, q scaled), v, g, beta as the layer makes them;
    `decay` "strong" gives g of -2 to -30 a position (a chunk's sum down to
    hundreds), "weak" of -1e-3 to -1e-2; "repeated" is the weak decay with
    one key at every position and beta near 1, as a row padded with one
    token makes them (`k_i . k_j = 1`)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (E, T, nv, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (E, 1 if decay == "repeated" else T, nv, dk)))
    k = jnp.broadcast_to(k, (E, T, nv, dk))
    v = jax.random.normal(ks[2], (E, T, nv, dv))
    lo, hi = (2.0, 30.0) if decay == "strong" else (1e-3, 1e-2)
    g = -jax.random.uniform(ks[3], (E, T, nv), minval=lo, maxval=hi)
    beta = jax.random.uniform(ks[4], (E, T, nv), minval=0.9 if decay == "repeated" else 0.0)
    return q, k, v, g, beta


def _per_token(q, k, v, g, beta):
    """The reference's recurrence a row at a time: (o, final state)."""
    ref = _reference()
    with jax.default_matmul_precision("highest"):
        out = [ref.delta_rule(q[e], k[e], v[e], g[e], beta[e]) for e in range(q.shape[0])]
    return jnp.stack([o for o, _ in out]), jnp.stack([s for _, s in out])


@pytest.mark.parametrize("chunk, T", [(4, 19), (4, 150), (64, 150), (64, 19)])
@pytest.mark.parametrize("decay", ["strong", "weak", "repeated"])
def test_the_chunked_pass_is_the_per_token_recurrence(chunk, T, decay):
    """Across chunk boundaries and a last short chunk; the final state too.
    The strong decay's chunk sums reach hundreds below zero, where a factored
    `exp(-G_j)` would overflow; with one key repeated, the series `sum (-A)^n`
    for the chunk's inverse cancels terms of 1e17 and more (NaN in float32,
    as a row padded with EOS gave on the chip): no value is non-finite."""
    args = _inputs(T, decay)
    with jax.default_matmul_precision("highest"):
        o, S, lowest = gd.chunked(*args, jnp.float32, chunk)
    want_o, want_S = _per_token(*args)
    assert np.all(np.isfinite(np.asarray(o)))
    assert float(jnp.max(jnp.abs(o - want_o))) < 2e-5 * float(jnp.max(jnp.abs(want_o)))
    assert float(jnp.max(jnp.abs(S - want_S))) < 2e-5 * float(jnp.max(jnp.abs(want_S)))
    G = jnp.cumsum(jnp.pad(args[3], [(0, 0), (0, -T % chunk), (0, 0)]).reshape(
        2, -1, chunk, 4), axis=2)[:, :, -1]
    assert np.allclose(np.asarray(lowest), np.asarray(G.min(axis=(1, 2))), rtol=1e-5)
    if decay == "strong" and chunk == 64:
        assert float(lowest.min()) < -300


def test_a_state_reset_at_each_chunk_boundary_fails_the_comparison():
    """The control: the chunked pass with its state dropped between chunks
    (each chunk from S = 0) is far from the recurrence, at a weak decay where
    the state carries across; the limit above holds the real pass to 2e-5."""
    q, k, v, g, beta = _inputs(40, "weak")
    with jax.default_matmul_precision("highest"):
        parts = [gd.chunked(*(x[:, i:i + 8] for x in (q, k, v, g, beta)), jnp.float32, 8)[0]
                 for i in range(0, 40, 8)]
    want, _ = _per_token(q, k, v, g, beta)
    err = float(jnp.max(jnp.abs(jnp.concatenate(parts, axis=1) - want)))
    assert err > 0.1 * float(jnp.max(jnp.abs(want)))


def test_the_delta_term_is_part_of_the_pass():
    """The other control: plain gated linear attention (no delta term, `S_t =
    exp(g_t) S + beta_t k_t v_t^T`) differs from the reference's recurrence
    by far more than the pass does."""
    q, k, v, g, beta = _inputs(40, "weak")

    def plain(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, None, None] * S + (beta[:, None] * k)[:, :, None] * v[:, None, :]
        return S, jnp.einsum("hdv,hd->hv", S, q)

    _, o = jax.lax.scan(plain, jnp.zeros((4, 8, 8)), tuple(x[0] for x in (q, k, v, g, beta)))
    want, _ = _per_token(q, k, v, g, beta)
    assert float(jnp.max(jnp.abs(o - want[0]))) > 0.1 * float(jnp.max(jnp.abs(want[0])))


def test_the_decode_step_is_one_position_of_the_recurrence():
    q, k, v, g, beta = _inputs(7, "strong", E=1)
    S = jnp.zeros((1, 4, 8, 8))
    outs = []
    for t in range(7):
        o, S = gd.recurrent_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S)
        outs.append(o)
    want_o, want_S = _per_token(q, k, v, g, beta)
    assert float(jnp.max(jnp.abs(jnp.stack(outs, 1) - want_o))) < 1e-5
    assert float(jnp.max(jnp.abs(S - want_S))) < 1e-5


def test_the_convolution_is_causal_and_its_tail_continues_it():
    x = jax.random.normal(jax.random.key(0), (2, 9, 6))
    w = jax.random.normal(jax.random.key(1), (4, 6))
    whole = gd.conv(x, w)
    # Output t reads inputs t-3..t only: a change at position 5 moves 5.. only.
    moved = gd.conv(x.at[:, 5].add(1.0), w)
    assert np.allclose(np.asarray(whole[:, :5]), np.asarray(moved[:, :5]))
    assert not np.allclose(np.asarray(whole[:, 5:]), np.asarray(moved[:, 5:]))
    # The last 3 inputs of a prefix continue it position by position.
    rest = gd.conv(x[:, 6:], w, x[:, 3:6])
    assert np.allclose(np.asarray(rest), np.asarray(whole[:, 6:]), atol=1e-6)


# -- the hybrid block ------------------------------------------------------

def _setup(sets=None, env_sets=None, seed=0):
    env_sets = {"vocab_size": 64, "prompt_min": 5, "prompt_max": 9, **(env_sets or {})}
    env_sets.setdefault("prefill_len", 5)
    preset = config_mod.resolve(TINY, None, None, {"num_envs": "4", **(sets or {})},
                                env_overrides=env_sets)
    cfg = preset.config
    env, _ = train.build_env(preset.env, preset.algo, cfg, seed, env_kwargs=preset.env_kwargs)
    return env, cfg, impala.make_policy(env, cfg)


def _noisy(params, key=7):
    """Every norm weight and the linear layers' gates moved off their
    initial values, so that `1 + w`, the gated norm's weight and `dt_bias`
    show."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.key(key), len(leaves))
    out = []
    for (path, x), k in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if "norm" in name or "dt_bias" in name:
            x = x + 0.3 * jax.random.normal(k, x.shape)
        out.append(x)
    return jax.tree_util.tree_unflatten(tree, out)


def _rollout(env, cfg, policy, params, seed=3):
    rstate = common.init_rollout(env, jax.random.key(seed), cfg.num_envs)
    return jax.jit(lambda p, r, k: common.rollout_scan(
        env, policy, p, r, k, cfg.rollout_steps))(params, rstate, jax.random.key(seed + 1))


def test_the_tiny_preset_keeps_the_published_ratios():
    assert SEQ.kinds == (gd.LINEAR,) * 3 + ("full_attention",)
    assert (SEQ.linear_num_value_heads // SEQ.linear_num_key_heads,
            SEQ.num_attention_heads // SEQ.num_key_value_heads) == (2, 8)
    assert SEQ.rotary_dim * 4 == SEQ.head_dim
    assert config_mod.PRESETS[TINY].config.rollout_steps % gd.CHUNK != 0


@pytest.mark.parametrize("compute_dtype, tol", [("float32", 2e-5), ("bfloat16", 6e-2)])
def test_step_through_the_caches_equals_unroll_equals_reference(compute_dtype, tol):
    """The rollout prefills 5 positions, then decodes through the linear
    layers' state and conv tail and the full layer's keys and values; the
    causal pass over the same rows, the linear layers in chunks, gives the
    same log-probabilities and values, and so does the reference's per-token
    recurrence (norm weights off their initial values)."""
    env, cfg, policy = _setup({"seq.compute_dtype": compute_dtype})
    params = _noisy(impala.init_params(env, cfg, jax.random.key(1)))
    _, traj = _rollout(env, cfg, policy, params)
    out = policy.unroll(params, traj)
    live = np.asarray(out.mask) > 0
    assert live.sum() > 0
    scale = max(1.0, float(jnp.max(jnp.abs(traj.value))))
    assert np.abs(np.asarray(out.log_prob - traj.log_prob))[live].max() < tol
    assert np.abs(np.asarray(out.value - traj.value)).max() < tol * scale
    if compute_dtype != "float32":
        return
    logits, values = sp.logits_and_values(params, jnp.swapaxes(traj.obs, 0, 1), cfg.seq)
    want_logits, want_values = _reference().forward(params, traj.obs, _network(cfg.seq))
    top = float(jnp.max(jnp.abs(want_logits)))
    assert float(jnp.max(jnp.abs(jnp.swapaxes(logits, 0, 1) - want_logits))) < tol * top
    assert float(jnp.max(jnp.abs(values.T - want_values))) < tol * scale


@pytest.mark.parametrize("P", [0, 2, 5])
def test_prefill_then_decode_equals_the_references_full_forward(P):
    """The prompt's first P positions in one causal pass that fills both
    kinds of cache: the state and conv tail it leaves are the reference's
    after those positions, and the rollout's behaviour log-probabilities
    and values are the reference's full causal pass over its tokens."""
    env, cfg, policy = _setup(env_sets={"prefill_len": P})
    params = _noisy(impala.init_params(env, cfg, jax.random.key(1)))
    _, traj = _rollout(env, cfg, policy, params)
    ref, net = _reference(), _network(cfg.seq)
    live = np.asarray(traj.obs[..., 2]) == 0
    want_logits, want_values = ref.forward(params, traj.obs, net)
    want = jnp.take_along_axis(
        jax.nn.log_softmax(want_logits), traj.action[..., None], axis=-1)[..., 0]
    scale = max(1.0, float(jnp.max(jnp.abs(want_values))))
    assert np.abs(np.asarray(traj.log_prob - want))[live].max() < 2e-5
    assert np.abs(np.asarray(traj.value - want_values)).max() < 2e-5 * scale
    if P == 0:
        return
    # The prefill's caches against the reference's layer over the prefix.
    _, _, carry = policy.prefill(params, traj.obs[:P], policy.init_carry(cfg.num_envs))
    states, tails = carry[0][gd.LINEAR]
    p = params["params"]
    x = p["embed"][traj.obs[:P, 0, 0]]
    with jax.default_matmul_precision("highest"):
        h = ref.norm0(x, p["layer_0"]["attn_norm"], net["rms_norm_eps"])
        _, (state, tail) = ref.linear_attention(p["layer_0"]["linear"], h, net)
    assert float(jnp.max(jnp.abs(states[0, 0] - state))) < 2e-5
    assert float(jnp.max(jnp.abs(tails[0, 0] - tail))) < 2e-6
    k, _ = carry[0]["full_attention"]
    assert float(jnp.max(jnp.abs(k[0, :, :, P:]))) == 0.0  # nothing past the prefix


def test_each_kind_keeps_its_own_cache():
    seq = SEQ
    cache = sp.init_cache(seq, 3, 19)
    state, tail = cache[gd.LINEAR]
    k, v = cache["full_attention"]
    assert state.shape == (3, 3, 4, 8, 8) and state.dtype == jnp.float32
    assert tail.shape == (3, 3, 3, gd.conv_channels(seq))
    assert k.shape == v.shape == (1, 3, 1, 19, 16)


def test_partial_rope_turns_the_first_quarter_only():
    p = sp.init_params(jax.random.key(0), SEQ, 32)["params"]["layer_3"]["attn"]
    h = jax.random.normal(jax.random.key(1), (5, SEQ.hidden_size))
    at = lambda pos: sp._gqa_project(  # noqa: E731
        p, h, jnp.full((5,), pos), SEQ, "full_attention", "q")
    rot = SEQ.rotary_dim
    assert rot == 4
    assert np.array_equal(np.asarray(at(0)[..., rot:]), np.asarray(at(9)[..., rot:]))
    assert not np.allclose(np.asarray(at(0)[..., :rot]), np.asarray(at(9)[..., :rot]))


def test_the_output_gate_and_the_shared_experts_gate_are_applied():
    """A gate weight of zero halves what it gates (`sigmoid(0)`); the
    program's full layer and expert layer follow it as the reference does."""
    env, cfg, policy = _setup()
    params = impala.init_params(env, cfg, jax.random.key(1))
    p = params["params"]["layer_3"]
    h = jax.random.normal(jax.random.key(2), (2, 7, cfg.seq.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(7), (2, 7))
    ref, net = _reference(), _network(cfg.seq)
    zero = {**p["attn"], "w_gate": jnp.zeros_like(p["attn"]["w_gate"])}
    half, _ = sp.gqa_unroll(zero, h, pos, cfg.seq, "full_attention")
    ungated, _ = sp.gqa_unroll({k: v for k, v in zero.items() if k != "w_gate"}, h, pos,
                               cfg.seq, "full_attention")
    assert np.allclose(np.asarray(half), 0.5 * np.asarray(ungated), atol=1e-6)
    out, _ = sp.gqa_unroll(p["attn"], h, pos, cfg.seq, "full_attention")
    with jax.default_matmul_precision("highest"):
        want = ref.full_attention(p["attn"], h[0], pos[0], net)
    assert float(jnp.max(jnp.abs(out[0] - want))) < 1e-5
    x = h.reshape(-1, cfg.seq.hidden_size)
    moe = {**p["moe"], "shared_gate": jnp.zeros_like(p["moe"]["shared_gate"])}
    routed = {k: v for k, v in moe.items() if k not in ("shared", "shared_gate")}
    y, _, _ = sp.moe(moe, x, cfg.seq)
    y_routed, _, _ = sp.moe(routed, x, cfg.seq)
    shared = sp._swiglu(p["moe"]["shared"], x, jnp.float32)
    assert np.allclose(np.asarray(y - y_routed), 0.5 * np.asarray(shared), atol=1e-5)
    y, _, _ = sp.moe(p["moe"], x, cfg.seq)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(p["moe"], x, net)
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5


@pytest.mark.parametrize("dense_tokens", [0, 4096])
def test_the_shares_of_a_512_expert_layer_add_up_to_the_uncut_reference(
        dense_tokens, monkeypatch):
    """512 experts, top-10, 16 held at offsets 0, 16, ..., 496 (the
    configuration's router and share at toy widths): the thirty-two chips'
    routed parts, with the gated shared expert counted once, sum to the
    reference's layer with every expert."""
    monkeypatch.setattr(sp, "MOE_DENSE_TOKENS", dense_tokens)
    seq = dataclasses.replace(SEQ, n_routed_experts=512, experts_held=16,
                              num_experts_per_tok=10)
    whole = dataclasses.replace(seq, experts_held=512)
    full = sp.init_params(jax.random.key(0), whole, 32)["params"]["layer_1"]["moe"]
    assert set(full) == {"router", "experts", "shared", "shared_gate"}
    h = jax.random.normal(jax.random.key(5), (48, seq.hidden_size))
    ref = _reference()
    with jax.default_matmul_precision("highest"):
        want = ref.moe(full, h, {**_network(whole), "expert_offset": 0})
    routed_only = {k: full[k] for k in ("router", "experts")}
    total, landed = 0.0, 0.0
    for offset in range(0, 512, 16):
        cut = dataclasses.replace(seq, expert_offset=offset)
        part = {**routed_only, "experts": jax.tree.map(
            lambda a: a[offset:offset + 16], full["experts"])}
        y, stats, _ = sp.moe(part, h, cut)
        total, landed = total + y, landed + float(stats["routed_here_frac"])
    shared, _, _ = sp.moe({**full, "experts": jax.tree.map(
        lambda a: a[:16], full["experts"])}, h, seq)
    first, _, _ = sp.moe({**routed_only, "experts": jax.tree.map(
        lambda a: a[:16], full["experts"])}, h, seq)
    total = total + (shared - first)   # the shared expert, once
    assert landed == pytest.approx(1.0)
    assert float(jnp.max(jnp.abs(total - want))) < 2e-5


@pytest.mark.parametrize("decay", ["strong", "weak", "repeated"])
def test_the_chunked_passs_gradient_is_the_recurrences(decay):
    """The gradient of the chunked pass (the series inverse, the blocks of
    chunks, each rematerialized) with respect to every input is the
    per-token recurrence's."""
    args = _inputs(23, decay)
    w = jax.random.normal(jax.random.key(9), (2, 23, 4, 8))
    ref = _reference()

    def mine(*args):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(gd.chunked(*args, jnp.float32, 4)[0] * w)

    def theirs(*args):
        with jax.default_matmul_precision("highest"):
            return sum(jnp.sum(ref.delta_rule(*(x[e] for x in args))[0] * w[e])
                       for e in range(2))

    got = jax.grad(mine, argnums=range(5))(*args)
    want = jax.grad(theirs, argnums=range(5))(*args)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * max(float(jnp.max(jnp.abs(b))), 1.0)


def test_the_update_reports_the_least_chunk_decay():
    env, cfg, policy = _setup()
    params = impala.init_params(env, cfg, jax.random.key(1))
    _, traj = _rollout(env, cfg, policy, params)
    lowest = float(policy.unroll(params, traj).metrics["gdn_chunk_logdecay_min"])
    p = params["params"]
    obs = jnp.swapaxes(traj.obs, 0, 1)
    want = np.inf
    x = p["embed"][obs[..., 0]]
    for i in range(3):  # the linear layers' own g over the row (19 < one chunk)
        layer = p[f"layer_{i}"]
        h = sp._norm(cfg.seq, x, layer["attn_norm"])
        _, _, _, g = gd._project(layer["linear"], h, cfg.seq)
        want = min(want, float(jnp.min(jnp.sum(g, axis=1))))
        x = x + gd.unroll(layer["linear"], h, cfg.seq)[0]
        x = x + sp.moe(layer["moe"], sp._norm(cfg.seq, x, layer["ffn_norm"]).reshape(
            -1, x.shape[-1]), cfg.seq)[0].reshape(x.shape)
    assert lowest == pytest.approx(want, rel=1e-5)
