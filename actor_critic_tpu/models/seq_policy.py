"""A decoder policy over tokens: latent attention (MLA) and sigmoid-routed
experts of which this chip holds a share, with a cache through the rollout.

The block, as the configuration's source publishes it (RMSNorm eps 1e-6;
`x += MLA(norm(x))`; `x += FFN(norm(x))`; final norm; untied `lm_head`):

- MLA: `c_q = norm(x W_qa)`; `q = c_q W_qb` -> heads x (nope + rope);
  `[c_kv, k_r] = x W_kva`; `c_kv = norm(c_kv)`; `[k_nope, v] = c_kv W_kvb` ->
  heads x (nope + v); RoPE (interleaved pairs, no scaling) on `q_rope` and on
  the one `k_r` all heads share; scores `q.k / sqrt(nope + rope)`, causal;
  `out = concat_h(softmax . v) W_o`. What decoding keeps per token and layer
  is `c_kv` and `k_r` (`kv_lora_rank + qk_rope_head_dim` values).
- Expert layer: `s = sigmoid(x W_g)` (float32, all `n_routed_experts`); the
  top `num_experts_per_tok` by `s + b`; weights `s[idx] / sum(s[idx]) x
  routed_scaling_factor`; `y = sum_i w_i E_i(x) + E_shared(x)`, each expert
  `down(silu(gate x) * up x)`. The first `first_k_dense_replace` layers are
  that MLP at `intermediate_size`, with no router.
- The chip's share: the layer is told `experts_held` and `expert_offset`. It
  routes over all experts at the published router width and adds `w_i E_i(x)`
  only for the chosen experts it holds (weights normalised over all chosen
  ones, as published); the shared expert is computed whole. What absent
  experts would add is left out and the partial result goes on. Nothing
  stands in for absent chips or their exchange.
- No token is dropped under any routing: the token-expert assignments that
  land here are sorted by expert and run through grouped matmuls
  (`lax.ragged_dot`) `MOE_ROWS` assignments a trip, in a loop whose trip count
  is read from the routing: balanced routing pays for one trip, the worst
  case (every token to held experts only) for all of them.
  `moe_dropped` counts what the trips missed. A pass of at most
  `MOE_DENSE_TOKENS` tokens (a decode step: 64 tokens, 2 an expert) instead
  runs every held expert on every token as one batched matmul and weights the
  results: the same sum, the held experts' weights read once a step, and a
  time that does not move with the routing.
- Heads: logits over the vocabulary slice the env draws its ids from, and a
  scalar value on the final norm's output.

Precision: float32 parameters, norms, softmax, router scores and log-softmax;
every other matmul takes `compute_dtype` operands (bfloat16 as shipped) and
accumulates in float32.

The model is plain functions over a parameter tree shaped like a flax one
(`{"params": {...}}`): `step` (one token through the latent cache) and
`unroll` (one causal pass over `[E, T]`) share the weights under
`lax.map` / `jax.checkpoint`, which linen's lifted transforms would only wrap.
`make_policy` gives the trainers' `common.Policy`.

What the causal pass keeps for its backward pass is two arrays a layer, the
layer's input and the residual stream after attention (`[E, T, H]` float32:
2 x 268 MB at the shipped shape): each half of a layer is one
`jax.checkpoint` (`trunk`), and inside the halves every `lax.map` trip
(`_map_rows`: `ATTN_ROWS` episodes of attention, `MLP_ROWS` / `HEAD_ROWS`
token rows) is one more, so the `[heads, T, T]` scores and the wide halves of
MLA are alive a trip at a time, in the backward pass too. The update therefore
runs a trip of attention forward twice (the forward pass, the trip's own
rematerialization) and the FFN likewise; the cut between the halves is there
so that rebuilding the FFN's input does not run attention a third time.

The cache is a carry of the rollout scan and starts fresh with it, so an
episode must be exactly one unroll and every row at the same position: the
token env guarantees both (`envs/token_task.py`) and `make_policy` refuses an
env whose `episode_horizon` is not the unroll length. The cache slot of a step
is row 0's position. It is one stacked pair for the whole model, which stays
in HBM through the scan; a decode step writes one slot a layer in place and
reads each layer's filled prefix once (`ops/mla_decode.py`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from actor_critic_tpu.models.distributions import Categorical
from actor_critic_tpu.ops.mla_decode import mla_decode_auto


@dataclasses.dataclass(frozen=True)
class SeqPolicyConfig:
    """Sizes under the source's own key names (published values as defaults),
    then what this chip holds."""

    hidden_size: int = 2048
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256       # the router's width, as published
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    first_k_dense_replace: int = 1
    num_hidden_layers: int = 40
    rms_norm_eps: float = 1e-6
    rope_theta: float = 32e6
    # The chip's share of each expert layer.
    experts_held: int = 256
    expert_offset: int = 0
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if not 0 <= self.expert_offset <= self.n_routed_experts - self.experts_held:
            raise ValueError(
                f"experts_held={self.experts_held} at expert_offset="
                f"{self.expert_offset} is no share of {self.n_routed_experts}"
            )
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (RoPE pairs)")

    @property
    def latent_dim(self) -> int:
        """Values the cache keeps per token and layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim


# The floor under the sum of the chosen scores, as the source's code has it.
_EPS = 1e-20
# `e_score_correction_bias`: a seeded, non-zero, untrained buffer, uniform in
# [-BIAS_SCALE, BIAS_SCALE].
BIAS_SCALE = 0.05
# Blocking of the passes (memory, never results), sized so that the shipped
# preset's step program fits one v5e (15.2 GB by `memory_analysis()`).
ATTN_ROWS = 8       # episodes a trip of the causal pass's attention
MLP_ROWS = 8192     # token rows a trip of the dense MLP and the shared expert
HEAD_ROWS = 4096    # token rows a trip of the lm_head
MOE_ROWS = 24576    # held assignments a trip of the grouped matmuls
# Up to this many tokens a pass (a decode step), every held expert runs on
# every token: the weights are read once whatever the routing.
MOE_DENSE_TOKENS = 128


# -- parameters -----------------------------------------------------------

def init_params(key: jax.Array, cfg: SeqPolicyConfig, vocab_size: int) -> dict:
    """Seeded float32 parameters. Matrices are `normal / sqrt(fan_in)`, the
    embedding unit normal, the policy head 0.01 of that (a near-uniform first
    policy, as the other networks' heads)."""
    H, nh = cfg.hidden_size, cfg.num_attention_heads
    keys = iter(jax.random.split(key, 16 * cfg.num_hidden_layers + 8))

    def mat(*shape, scale=1.0):
        fan_in = shape[-2]
        return jax.random.normal(next(keys), shape, jnp.float32) * (
            scale / fan_in ** 0.5
        )

    def mlp(*lead, width):
        return {"w_gate": mat(*lead, H, width), "w_up": mat(*lead, H, width),
                "w_down": mat(*lead, width, H)}

    p: dict[str, Any] = {
        "embed": jax.random.normal(next(keys), (vocab_size, H), jnp.float32)
    }
    for i in range(cfg.num_hidden_layers):
        layer = {
            "attn_norm": jnp.ones((H,), jnp.float32),
            "ffn_norm": jnp.ones((H,), jnp.float32),
            "mla": {
                "w_qa": mat(H, cfg.q_lora_rank),
                "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
                "w_qb": mat(cfg.q_lora_rank,
                            nh * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
                "w_kva": mat(H, cfg.latent_dim),
                "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
                "w_kvb": mat(cfg.kv_lora_rank,
                             nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "w_o": mat(nh * cfg.v_head_dim, H),
            },
        }
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = mlp(width=cfg.intermediate_size)
        else:
            layer["moe"] = {
                "router": mat(H, cfg.n_routed_experts),
                "bias": jax.random.uniform(
                    next(keys), (cfg.n_routed_experts,), jnp.float32,
                    -BIAS_SCALE, BIAS_SCALE),
                "experts": mlp(cfg.experts_held, width=cfg.moe_intermediate_size),
                "shared": mlp(width=cfg.moe_intermediate_size),
            }
        p[f"layer_{i}"] = layer
    p["final_norm"] = jnp.ones((H,), jnp.float32)
    p["lm_head"] = mat(H, vocab_size, scale=0.01)
    p["value_head"] = {"kernel": mat(H, 1), "bias": jnp.zeros((1,), jnp.float32)}
    return {"params": p}


# -- pieces ---------------------------------------------------------------

def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mm(x, w, cd, out=jnp.float32):
    """`x @ w` over the last axis: `compute_dtype` operands, float32 sums.
    `out=cd` hands the result on rounded, where it is a matmul operand only."""
    return jax.lax.dot_general(
        x.astype(cd), w.astype(cd), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(out)


def _einsum(spec, a, b, cd):
    return jnp.einsum(spec, a.astype(cd), b.astype(cd),
                      preferred_element_type=jnp.float32)


def _rope(x, positions, theta):
    """Rotate interleaved pairs `(x[2i], x[2i+1])` of the last axis by
    `position * theta ** (-2i / d)`. `positions` broadcasts against
    `x.shape[:-1]`."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def _map_rows(fn, rows: int, *xs):
    """`fn` over the leading axis of `xs`, `rows` at a time, each trip
    rematerialized in the backward pass (so a trip's intermediates are never
    all alive). One call where `rows` covers everything."""
    n = xs[0].shape[0]
    if rows >= n:
        return fn(*xs)
    while n % rows:  # the largest trip under `rows` that divides
        rows -= 1
    split = lambda x: x.reshape(n // rows, rows, *x.shape[1:])  # noqa: E731
    out = jax.lax.map(lambda c: jax.checkpoint(fn)(*c), tuple(map(split, xs)))
    return jax.tree.map(lambda y: y.reshape(n, *y.shape[2:]), out)


def _swiglu(p, x, cd):
    g, u = _mm(x, p["w_gate"], cd), _mm(x, p["w_up"], cd)
    return _mm(jax.nn.silu(g) * u, p["w_down"], cd)


def _mla_latents(p, h, positions, cfg, cd):
    """The narrow halves of the projections: (c_q [.., q_lora_rank], c_kv
    [.., kv_lora_rank], k_r [.., rope] with RoPE applied)."""
    c_q = _rms(_mm(h, p["w_qa"], cd), p["q_norm"], cfg.rms_norm_eps)
    kv = _mm(h, p["w_kva"], cd)
    c_kv = _rms(kv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.rms_norm_eps)
    k_r = _rope(kv[..., cfg.kv_lora_rank:], positions, cfg.rope_theta)
    return c_q, c_kv, k_r


def _mla_queries(p, c_q, positions, cfg, cd):
    """(q_nope, q_rope [.., heads, d]) from the query latent, RoPE applied."""
    nh, dn, dr = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = _mm(c_q, p["w_qb"], cd, out=cd).reshape(*c_q.shape[:-1], nh, dn + dr)
    return q[..., :dn], _rope(q[..., dn:], positions[..., None], cfg.rope_theta)


def mla_unroll(p, h, positions, cfg: SeqPolicyConfig):
    """Causal latent attention over `h [E, T, H]`. The latents are computed
    for all rows at once; the wide halves (queries, keys and values a head),
    the `[heads, T, T]` scores and the output projection run `ATTN_ROWS`
    episodes a trip, so that neither is ever whole in memory."""
    cd = jnp.dtype(cfg.compute_dtype)
    nh, dn, dv = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    T = h.shape[1]
    scale = (dn + cfg.qk_rope_head_dim) ** -0.5
    causal = jnp.tril(jnp.ones((T, T), jnp.bool_))

    def attend(c_q, c_kv, k_r, positions):
        q_nope, q_rope = _mla_queries(p, c_q, positions, cfg, cd)
        kv = _mm(c_kv, p["w_kvb"], cd, out=cd).reshape(*c_kv.shape[:-1], nh, dn + dv)
        s = _einsum("ethd,eshd->ehts", q_nope, kv[..., :dn], cd)
        s = s + _einsum("ethr,esr->ehts", q_rope, k_r, cd)
        s = jnp.where(causal, s * scale, -jnp.inf)
        out = _einsum("ehts,eshv->ethv", jax.nn.softmax(s, axis=-1), kv[..., dn:], cd)
        return _mm(out.reshape(*out.shape[:2], nh * dv), p["w_o"], cd)

    c_q, c_kv, k_r = _mla_latents(p, h, positions, cfg, cd)
    return _map_rows(attend, ATTN_ROWS, c_q, c_kv, k_r, positions)


def mla_step(p, h, positions, cache, layer: int, slot, cfg: SeqPolicyConfig):
    """One token a row through layer `layer` of the latent cache `(c_kv
    [layers, E, T, rank], k_r [layers, E, T, rope])`: the token's latent goes
    into `slot`, the queries are absorbed into the latent space (`q_nope
    W_kvb^k`), and attention reads the cached latents only, as far as they
    are filled (`ops/mla_decode.py`: one kernel on a TPU where the cache
    tiles, two einsums elsewhere). Returns (out [E, H], cache)."""
    cd = jnp.dtype(cfg.compute_dtype)
    nh, dn, dv = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    c_q, c_kv, k_r = _mla_latents(p, h, positions, cfg, cd)
    q_nope, q_rope = _mla_queries(p, c_q, positions, cfg, cd)
    put = lambda buf, new: jax.lax.dynamic_update_slice(  # noqa: E731
        buf, new.astype(buf.dtype)[None, :, None, :], (layer, 0, slot, 0))
    c_all, r_all = put(cache[0], c_kv), put(cache[1], k_r)
    w_kvb = p["w_kvb"].reshape(rank, nh, dn + dv)
    q_lat = _einsum("ehd,chd->ehc", q_nope, w_kvb[..., :dn], cd)
    o_lat = mla_decode_auto(q_lat, q_rope, c_all, r_all, layer, slot,
                            (dn + cfg.qk_rope_head_dim) ** -0.5)
    o = _einsum("ehc,chv->ehv", o_lat, w_kvb[..., dn:], cd)
    return _mm(o.reshape(h.shape[0], nh * dv), p["w_o"], cd), (c_all, r_all)


def route(p, h, cfg: SeqPolicyConfig):
    """The published router: (idx [N, k] over all experts, weights [N, k])."""
    s = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), p["router"], precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["bias"]),
                           cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + _EPS)
    return idx, weights * cfg.routed_scaling_factor


def _held_experts(cfg: SeqPolicyConfig, R: int):
    """The held experts over the assignments that landed here, `R` a trip.

    `run(experts, h, w_flat, route) -> (y [N, H], assignments done)` with
    `route = (order, ends, sizes, landed)`: the assignment ids sorted by held
    expert (absent ones last), each expert's end and size in that order, and
    how many landed. A loop runs `ceil(landed / R)` trips, read from the
    routing: balanced routing takes one, and whatever the routing no
    assignment is left out. The loop has no reverse rule, so the backward
    pass is written out: the same trips again, each rematerialized, with the
    routing as the only thing kept."""
    cd = jnp.dtype(cfg.compute_dtype)
    k = cfg.num_experts_per_tok

    def trip(j, experts, h, w_flat, route):
        """Assignments `[j R, (j + 1) R)`: (weighted outputs [R, H], (their
        tokens [R], assignments done)). Rows past the last landed assignment
        belong to no group, and what a grouped matmul leaves in such rows of
        its result is whatever the memory held (on the chip; zeros on the
        CPU): NaN there would reach the router's gradient as `0 * NaN`. So
        every grouped matmul has those rows selected to zero on its way in
        and on its way out, which holds its two cotangents to zero there
        too."""
        order, ends, sizes, landed = route
        lo = j * R
        with jax.named_scope("moe_route"):
            rows = jax.lax.dynamic_slice(order, (lo,), (R,))
            valid = (lo + jnp.arange(R) < landed)[:, None]
            token = rows // k
            x = jnp.take(h, token, axis=0)
            sizes_j = jnp.clip(ends - lo, 0, R) - jnp.clip(ends - sizes - lo, 0, R)
        with jax.named_scope("moe_experts"):
            def dot(a, w):
                out = jax.lax.ragged_dot(
                    jnp.where(valid, a, 0.0).astype(cd), w.astype(cd), sizes_j,
                    preferred_element_type=jnp.float32)
                return jnp.where(valid, out, 0.0)

            act = jax.nn.silu(dot(x, experts["w_gate"])) * dot(x, experts["w_up"])
            out = dot(act, experts["w_down"])
        with jax.named_scope("moe_route"):
            out = out * jnp.take(w_flat, rows)[:, None]
        return out, (token, jnp.sum(sizes_j))

    def trips_of(route):
        return (route[3] + R - 1) // R

    @jax.custom_vjp
    def run(experts, h, w_flat, route):
        def body(j, carry):
            y, done = carry
            out, (token, n) = trip(j, experts, h, w_flat, route)
            with jax.named_scope("moe_route"):
                return y.at[token].add(out), done + n

        init = (jnp.zeros(h.shape, jnp.float32), jnp.zeros((), jnp.int32))
        return jax.lax.fori_loop(0, trips_of(route), body, init)

    def forward(experts, h, w_flat, route):
        return run(experts, h, w_flat, route), (experts, h, w_flat, route)

    def backward(kept, cotangents):
        experts, h, w_flat, route = kept
        y_bar = cotangents[0]

        def body(j, grads):
            _, pull, (token, _) = jax.vjp(
                lambda *weights: trip(j, *weights, route),
                experts, h, w_flat, has_aux=True)
            with jax.named_scope("moe_route"):
                rows_bar = jnp.take(y_bar, token, axis=0)
            return jax.tree.map(jnp.add, grads, pull(rows_bar))

        zeros = jax.tree.map(jnp.zeros_like, (experts, h, w_flat))
        grads = jax.lax.fori_loop(0, trips_of(route), body, zeros)
        return (*grads, None)

    run.defvjp(forward, backward)
    return run


def _held_experts_dense(experts, h, weights_here, cd):
    """`sum_e weights_here[n, e] E_e(h[n])` with every held expert on every
    token: for the few tokens of a decode step."""
    with jax.named_scope("moe_experts"):
        act = jax.nn.silu(_einsum("nh,ehw->enw", h, experts["w_gate"], cd)) \
            * _einsum("nh,ehw->enw", h, experts["w_up"], cd)
        out = _einsum("enw,ewh->enh", act, experts["w_down"], cd)
    with jax.named_scope("moe_route"):
        return jnp.einsum("enh,ne->nh", out, weights_here)


def moe(p, h, cfg: SeqPolicyConfig):
    """The expert layer's part this chip computes for tokens `h [N, H]`:
    `sum_i w_i E_i(x)` over the chosen experts held here, plus the shared
    expert. Returns (y [N, H], stats)."""
    cd = jnp.dtype(cfg.compute_dtype)
    N, _ = h.shape
    held = cfg.experts_held
    A = N * cfg.num_experts_per_tok             # token-expert assignments
    R = min(A, MOE_ROWS)                        # of them a trip
    with jax.named_scope("moe_route"):
        idx, weights = route(p, h, cfg)
        local = idx.reshape(A) - cfg.expert_offset
        here = (local >= 0) & (local < held)
        group = jnp.where(here, local, held)    # absent experts sort last
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
        ends = jnp.cumsum(sizes)
        landed = ends[-1]
        order = jnp.pad(order, (0, -A % R))
    if N <= MOE_DENSE_TOKENS:
        with jax.named_scope("moe_route"):
            weights_here = jnp.zeros((N, held + 1), jnp.float32).at[
                jnp.arange(N)[:, None], group.reshape(idx.shape)].add(weights)
        y = _held_experts_dense(p["experts"], h, weights_here[:, :held], cd)
        done = landed
    else:
        y, done = _held_experts(cfg, R)(
            p["experts"], h, weights.reshape(A), (order, ends, sizes, landed))
    with jax.named_scope("moe_shared"):
        y = y + _map_rows(lambda x: _swiglu(p["shared"], x, cd), MLP_ROWS, h)
    mean_load = jnp.maximum(landed, 1).astype(jnp.float32) / held
    return y, {
        "routed_here_frac": landed.astype(jnp.float32) / A,
        "expert_load_max_over_mean": jnp.max(sizes).astype(jnp.float32) / mean_load,
        "moe_dropped": (landed - done).astype(jnp.float32),
    }


def _ffn(layer, h, cfg: SeqPolicyConfig):
    """The layer's FFN on `h [N, H]`: (y, the expert layer's stats or None)."""
    if "moe" in layer:
        return moe(layer["moe"], h, cfg)
    cd = jnp.dtype(cfg.compute_dtype)
    return _map_rows(lambda x: _swiglu(layer["mlp"], x, cd), MLP_ROWS, h), None


def _layers(params):
    p = params["params"]
    return p, [p[f"layer_{i}"] for i in range(sum(k.startswith("layer_") for k in p))]


# -- the two passes -------------------------------------------------------

def init_cache(cfg: SeqPolicyConfig, num_envs: int, horizon: int):
    """The latent cache, one pair for the whole model: `(c_kv [layers, E, T,
    kv_lora_rank], k_r [layers, E, T, qk_rope_head_dim])` in `compute_dtype`
    (its values are matmul operands only). Stacked, a rollout's carry is too
    large for the compiler to keep in VMEM between decode steps (a pair a
    layer it kept there, and evicted to HBM and fetched back every step):
    its home is HBM, where a step writes one slot in place."""
    cd = jnp.dtype(cfg.compute_dtype)
    lead = (cfg.num_hidden_layers, num_envs, horizon)
    return (jnp.zeros((*lead, cfg.kv_lora_rank), cd),
            jnp.zeros((*lead, cfg.qk_rope_head_dim), cd))


def step(params, obs, cache, cfg: SeqPolicyConfig):
    """Decode one token a row: `obs [E, 3]` -> (logits [E, V], value [E],
    cache)."""
    cd = jnp.dtype(cfg.compute_dtype)
    p, layers = _layers(params)
    tokens, positions = obs[:, 0], obs[:, 1]
    slot = positions[0]
    x = jnp.take(p["embed"], tokens, axis=0)
    for i, layer in enumerate(layers):
        with jax.named_scope("mla"):
            h = _rms(x, layer["attn_norm"], cfg.rms_norm_eps)
            a, cache = mla_step(layer["mla"], h, positions, cache, i, slot, cfg)
            x = x + a
        y, _ = _ffn(layer, _rms(x, layer["ffn_norm"], cfg.rms_norm_eps), cfg)
        x = x + y
    h = _rms(x, p["final_norm"], cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        logits = _mm(h, p["lm_head"], cd)
    return logits, _value(p, h), cache


def _value(p, h):
    v = jnp.matmul(h, p["value_head"]["kernel"], precision=jax.lax.Precision.HIGHEST)
    return v[..., 0] + p["value_head"]["bias"][0]


def trunk(params, obs, cfg: SeqPolicyConfig):
    """The causal pass over `obs [E, T, 3]`: (final-normed hidden [E, T, H],
    the expert layers' stats, layers-mean).

    Each half of a layer is rematerialized on its own, so the backward pass
    keeps the layer's input and the residual stream after attention. With
    one checkpoint round the whole layer only the input is kept, and the
    FFN's backward, which starts from that residual stream, has every trip
    of `attend` run forward again just to rebuild it, before the trips'
    own rematerialization runs them a third time. Cut here, the attention
    half's rematerialization recomputes the latents (the trips' inputs) and
    nothing of `attend`, whose output feeds only the linear `x + .`."""
    p, layers = _layers(params)
    tokens, positions = obs[..., 0], obs[..., 1]
    E, T = tokens.shape
    x = jnp.take(p["embed"], tokens, axis=0)

    def attn(layer, x):
        with jax.named_scope("mla"):
            h = _rms(x, layer["attn_norm"], cfg.rms_norm_eps)
            return x + mla_unroll(layer["mla"], h, positions, cfg)

    def ffn(layer, x):
        h = _rms(x, layer["ffn_norm"], cfg.rms_norm_eps).reshape(E * T, -1)
        y, stats = _ffn(layer, h, cfg)
        return x + y.reshape(x.shape), stats

    stats = []
    for layer in layers:
        x = jax.checkpoint(attn)(layer, x)
        x, s = jax.checkpoint(ffn)(layer, x)
        if s is not None:
            stats.append(s)
    mean = {k: jnp.mean(jnp.stack([s[k] for s in stats])) for k in stats[0]} \
        if stats else {}
    return _rms(x, p["final_norm"], cfg.rms_norm_eps), mean


def logits_and_values(params, obs, cfg: SeqPolicyConfig):
    """(logits [E, T, V], values [E, T]) of the causal pass, logits whole:
    for tests and the benchmark's check at a few rows, not for the loss."""
    h, _ = trunk(params, obs, cfg)
    with jax.named_scope("lm_head"):
        logits = _mm(h, params["params"]["lm_head"], jnp.dtype(cfg.compute_dtype))
    return logits, _value(params["params"], h)


def unroll(params, obs, actions, cfg: SeqPolicyConfig):
    """What the loss needs of the causal pass over `obs [E, T, 3]`:
    (log-probability of `actions`, entropy, value, each [E, T], and the
    expert layers' stats). The `[E*T, V]` log-probabilities are never whole
    in memory: the head runs `HEAD_ROWS` token rows a trip."""
    cd = jnp.dtype(cfg.compute_dtype)
    h, stats = trunk(params, obs, cfg)
    E, T, H = h.shape
    w = params["params"]["lm_head"]

    def head(h, a):
        logp = jax.nn.log_softmax(_mm(h, w, cd), axis=-1)
        chosen = jnp.take_along_axis(logp, a[:, None], axis=-1)[:, 0]
        return chosen, -jnp.sum(jnp.exp(logp) * logp, axis=-1)

    with jax.named_scope("lm_head"):
        log_prob, entropy = _map_rows(
            head, HEAD_ROWS, h.reshape(E * T, H),
            actions.reshape(E * T).astype(jnp.int32))
    return (log_prob.reshape(E, T), entropy.reshape(E, T),
            _value(params["params"], h), stats)


def make_policy(spec, cfg: SeqPolicyConfig, horizon: int):
    """The trainers' `common.Policy` over this model for an env whose
    observations are `(token id, position, is_prompt)` and whose episode is
    exactly one unroll of `horizon` steps."""
    from actor_critic_tpu.algos.common import Policy, Unrolled

    if tuple(spec.obs_shape) != (3,) or not spec.discrete:
        raise ValueError(
            "a sequence policy reads (token id, position, is_prompt) "
            f"observations and emits token ids; got obs_shape={spec.obs_shape}"
        )
    if spec.can_truncate or spec.episode_horizon != horizon:
        raise ValueError(
            f"the policy's cache lives inside one unroll and starts fresh with "
            f"it, so the env's episode must be exactly one unroll: "
            f"episode_horizon={spec.episode_horizon} (can_truncate="
            f"{spec.can_truncate}) against rollout_steps={horizon}; set "
            f"--env-set horizon={horizon} (a carry across unrolls is not built)"
        )

    def policy_step(params, obs, cache):
        logits, value, cache = step(params, obs, cache, cfg)
        return Categorical(logits), value, cache

    def policy_unroll(params, traj):
        obs = jnp.swapaxes(traj.obs, 0, 1)
        log_prob, entropy, value, stats = unroll(
            params, obs, jnp.swapaxes(traj.action, 0, 1), cfg)
        mask = 1.0 - traj.obs[..., 2].astype(jnp.float32)
        stats = {**stats, "response_frac": jnp.mean(mask)}
        return Unrolled(log_prob.T, entropy.T, value.T, mask, stats)

    return Policy(
        init_carry=lambda num_envs: init_cache(cfg, num_envs, horizon),
        step=policy_step,
        unroll=policy_unroll,
        # Every episode terminates at the unroll's last step, so V-trace
        # multiplies the bootstrap by zero: nothing to evaluate.
        bootstrap=lambda params, obs: jnp.zeros((obs.shape[0],), jnp.float32),
    )
