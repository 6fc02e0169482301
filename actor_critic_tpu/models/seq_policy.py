"""A decoder policy over tokens: latent attention (MLA) or grouped-query
attention in window and full layers, and routed experts of which this chip
holds a share, with a cache through the rollout.

The block, as the configuration's source publishes it (RMSNorm eps 1e-6;
`x += Attention(norm(x))`; `x += FFN(norm(x))`; final norm; untied
`lm_head`). The configuration says, under the source's own key names, which
attention each layer has (`layer_types`; none: MLA everywhere), how the router
scores (`scoring_func`), whether a layer has a shared expert
(`n_shared_experts`) and how many leading layers are dense
(`first_k_dense_replace`); from the residual stream after attention on, every
configuration runs the same code.

- MLA: `c_q = norm(x W_qa)`; `q = c_q W_qb` -> heads x (nope + rope);
  `[c_kv, k_r] = x W_kva`; `c_kv = norm(c_kv)`; `[k_nope, v] = c_kv W_kvb` ->
  heads x (nope + v); RoPE (interleaved pairs, no scaling) on `q_rope` and on
  the one `k_r` all heads share; scores `q.k / sqrt(nope + rope)`, causal;
  `out = concat_h(softmax . v) W_o`. What decoding keeps per token and layer
  is `c_kv` and `k_r` (`kv_lora_rank + qk_rope_head_dim` values).
- Grouped-query attention (`layer_types[l]`): `q = x W_q` -> heads x
  `head_dim`, `k = x W_k`, `v = x W_v` -> `num_key_value_heads` x `head_dim`,
  no bias; RoPE on the whole of `q` and `k`, pairs `(j, j + head_dim / 2)`;
  query head `i` reads key/value head `i // (heads / kv heads)`; scores `q.k /
  sqrt(head_dim)`. `"sliding_attention"`: the query at `t` sees keys `s` with
  `0 <= t - s < sliding_window`, frequencies `theta ** (-2j / d)`.
  `"full_attention"`: causal, frequencies scaled as YaRN does
  (`rope_frequencies`), cos and sin times `rope_attention_factor`. What
  decoding keeps per token and layer is the key after RoPE and the value; a
  window layer keeps the last `sliding_window` of them, in a ring.
- Sparse attention (`sa_config`, every layer): grouped-query attention over
  the causal prefix with plain RoPE, `q` and `k` RMS-normed per head
  (`q_norm`, `k_norm`, before RoPE), and DeepSeek Sparse Attention's
  lightning indexer: `q^I = x W_qI` -> `indexer_num_heads` x
  `indexer_head_dim`, one key a position `k^I = LayerNorm(x W_kI)`, both
  with RoPE on all their dimensions, head weights `w = x W_w / sqrt(heads x
  dim)`, scores `I[t, s] = sum_j w_j relu(q^I_j[t] . k^I[s])`; the query at
  `t` attends over the `topk` causal keys of the largest `I[t, .]` only
  (ties to the lower position, as `lax.top_k`). What decoding keeps per
  token and layer is the key, the value and `k^I`. The selection is
  discrete, so the indexer gets no gradient from the loss.
- Hybrid layers (`full_attention_interval`): layer `i` is full grouped-query
  attention where `(i + 1) % interval == 0`, Gated DeltaNet otherwise
  (`LINEAR`: `models/gated_delta.py`, a delta-rule recurrence over a matrix
  state, chunked in the causal pass). Here the full layers may gate their
  output (`gated_attention`: `sigmoid(x W_gate)` on attention's output,
  per-head norms on q and k), turn only `partial_rotary_factor` of each
  head by RoPE, and every norm may be zero-centred (`1 + w`). What decoding
  keeps of a linear layer is its state and the last inputs of its conv.
- Expert layer: `s = sigmoid(x W_g)` (float32, all `n_routed_experts`); the
  top `num_experts_per_tok` by `s + b`; weights `s[idx] / sum(s[idx]) x
  routed_scaling_factor`; or `s = softmax(x W_g)` over all the experts, the
  top by `s`, no `b`; `y = sum_i w_i E_i(x) + E_shared(x)` (the shared expert
  where the layer has one, times `sigmoid(x w_sg)` where the configuration
  gives `shared_expert_intermediate_size`), each expert `down(silu(gate x) *
  up x)`. The first
  `first_k_dense_replace` layers are that MLP at `intermediate_size`, with no
  router.
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
  `MOE_DENSE_TOKENS` tokens (a decode step) instead runs held experts on
  every token and weights the results: the same sum. Where it expects fewer
  than `MOE_KERNEL_ASSIGNMENTS` assignments an expert (8 tokens x 8 of 64: 1
  an expert) it runs only the experts some token chose (`ops/moe_decode.py`:
  one kernel on a TPU where the experts tile): the chosen experts' weights
  read once a step, so the time follows how many were chosen, and
  `decode_experts_read_frac` in the rows says how many. Elsewhere (64 tokens
  x 8 of 256: 2 an expert; off a TPU) it runs every held expert as batched
  matmuls: every held expert's weights read once a step, a time that does
  not move with the routing, `decode_experts_read_frac` 1.
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
so that rebuilding the FFN's input does not run attention a third time. A
grouped-query layer's trip is `GQA_ROWS` episodes. On a TPU where the row
tiles, its causal pass is one kernel a row under the layer's band
(`gqa_unroll`, `ops/sparse_attention.py`), which never writes the scores and
keeps its output and log-sum-exp for the backward pass. Elsewhere the trip is
not rematerialized as a whole: inside it every block of `ATTN_QUERIES`
queries is, and a block takes the keys of its band only, so a row of 4,096
fits and a window layer never multiplies what it would mask.

The cache is a carry of the rollout scan and starts fresh with it, so an
episode must be exactly one unroll and every row at the same position: the
token env guarantees both (`envs/token_task.py`) and `make_policy` refuses an
env whose `episode_horizon` is not the unroll length. The cache slot of a step
is row 0's position. It is one stacked pair for the whole model, which stays
in HBM through the scan; a decode step writes one slot a layer in place and
reads each layer's filled prefix once (`ops/mla_decode.py`). Grouped-query
layers have one stacked pair a KIND, each at its own size (`init_cache`), and
their policy can fill them from a prompt in one causal pass
(`Policy.prefill`, `trunk(..., cache)`) before decoding starts. Sparse layers
keep their indexer keys beside keys and values, a third array of their kind's
cache.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
import numpy as np

from actor_critic_tpu.models import gated_delta
from actor_critic_tpu.models.distributions import Categorical
from actor_critic_tpu.models.gated_delta import LINEAR
from actor_critic_tpu.models.matmul import einsum as _einsum, mm as _mm
from actor_critic_tpu.ops.mla_decode import mla_decode_auto
from actor_critic_tpu.ops import moe_decode, sparse_attention, topk_select


# The kinds of grouped-query layer, as the source's `layer_types` names them.
GQA_KINDS = ("sliding_attention", "full_attention")
# Full causal grouped-query attention with per-head norms on q and k and a
# learned key selection (`SparseAttention`) in every layer.
SPARSE = "sparse_attention"


@dataclasses.dataclass(frozen=True)
class SparseAttention:
    """DeepSeek Sparse Attention's lightning indexer over a grouped-query
    layer, under the source's `sa_config` key names. Each query attends only
    to the `topk` causal keys of the highest indexer score `I[t, s] = sum_j
    w[t, j] relu(q_j[t] . k[s])`: `indexer_num_heads` query heads of
    `indexer_head_dim`, one key a position (the source's
    `indexer_num_kv_heads` is 1). `q_chunk_size` x `kv_chunk_size` tile the
    causal pass's square: its selection runs `q_chunk_size` queries a block,
    and `sparse_tiles_frac` counts the tiles that hold a selected pair; no
    result depends on either."""

    indexer_head_dim: int = 64
    indexer_num_heads: int = 16
    q_chunk_size: int = 512
    kv_chunk_size: int = 512
    topk: int = 2048

    def __post_init__(self):
        if self.indexer_head_dim % 2:
            raise ValueError(f"indexer_head_dim={self.indexer_head_dim} is odd (RoPE pairs)")


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
    scoring_func: str = "sigmoid"     # or "softmax": no bias, no scaling
    n_shared_experts: int = 1         # 0: the expert layer has no shared expert
    # The attention of each layer: () is latent attention (MLA, the keys
    # above) in every layer; else the kind of each of the first
    # `num_hidden_layers` layers, "sliding_attention" or "full_attention":
    # grouped-query attention over `num_key_value_heads` heads of
    # `head_dim`, a causal window of `sliding_window` keys (the query's own
    # counted) with plain RoPE, or full causal attention with RoPE scaled as
    # `rope_parameters.full_attention` says (YaRN; a factor of 1 is plain).
    layer_types: tuple[str, ...] = ()
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_factor: float = 1.0
    rope_original_max_position_embeddings: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.0
    # Set: every layer is grouped-query attention over the causal prefix
    # (`SPARSE`, plain RoPE), its q and k heads RMS-normed before RoPE, each
    # query reading the keys an indexer selects; `layer_types` is then ().
    sa_config: SparseAttention | None = None
    # Set: layer `i` is full grouped-query attention where `(i + 1) %
    # full_attention_interval == 0` and Gated DeltaNet (`LINEAR`,
    # `models/gated_delta.py`, the `linear_*` keys below) otherwise, as
    # `qwen3_next` lays its layers out; `layer_types` is then ().
    full_attention_interval: int = 0
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_value_head_dim: int = 128
    # RoPE on the first `partial_rotary_factor x head_dim` dimensions of q
    # and k only (pairs `(j, j + rotary / 2)`); the rest pass through.
    partial_rotary_factor: float = 1.0
    # `qwen3_next`'s full layers: `q_proj` doubles its width for an output
    # gate (`out = (softmax(s) v * sigmoid(h W_gate)) W_o`, its half stored as
    # `w_gate`), and q and k are RMS-normed per head before RoPE.
    gated_attention: bool = False
    # Every norm of the block (the layer norms, the final one, q and k)
    # keeps its weight about zero and scales by `1 + w`, as `qwen3_next`'s
    # RMSNorm does; the weights start at 0.
    zero_centered_norm: bool = False
    # Set: the shared expert has this width and its output is scaled by
    # `sigmoid(h w_sg)` (the Qwen convention whose key this is), in place
    # of `n_shared_experts x moe_intermediate_size` ungated.
    shared_expert_intermediate_size: int = 0
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
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func={self.scoring_func!r}: sigmoid or softmax")
        if self.layer_types and self.sa_config is not None:
            raise ValueError("sa_config makes every layer sparse: no layer_types")
        if self.full_attention_interval and (self.layer_types or self.sa_config):
            raise ValueError("full_attention_interval lays the layers out: no "
                             "layer_types, no sa_config")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"{self.linear_num_value_heads} value heads do not group over "
                f"{self.linear_num_key_heads} key heads")
        if self.layer_types:
            kinds = self.layer_types[:self.num_hidden_layers]
            if len(kinds) < self.num_hidden_layers or set(kinds) - set(GQA_KINDS):
                raise ValueError(
                    f"layer_types must name {self.num_hidden_layers} layers, "
                    f"each one of {GQA_KINDS}; got {self.layer_types}")
        if self.grouped:
            if self.num_attention_heads % self.num_key_value_heads or self.head_dim % 2:
                raise ValueError(
                    f"{self.num_attention_heads} query heads do not group over "
                    f"{self.num_key_value_heads} key/value heads, or head_dim="
                    f"{self.head_dim} is odd")

    def attention(self, layer: int) -> str:
        """The kind of layer `layer`'s attention: "mla", one of `GQA_KINDS`,
        `SPARSE` or `LINEAR`."""
        if self.sa_config is not None:
            return SPARSE
        if self.full_attention_interval:
            return "full_attention" if (layer + 1) % self.full_attention_interval == 0 \
                else LINEAR
        return self.layer_types[layer] if self.layer_types else "mla"

    @property
    def kinds(self) -> tuple[str, ...]:
        return tuple(map(self.attention, range(self.num_hidden_layers)))

    @property
    def grouped(self) -> bool:
        """Grouped-query layers (a cache of keys and values a kind, a
        prefill), not latent attention."""
        return bool(self.layer_types or self.full_attention_interval) \
            or self.sa_config is not None

    def cache_index(self, layer: int) -> int:
        """Which layer of its kind's stacked cache a grouped-query layer is."""
        return self.kinds[:layer].count(self.kinds[layer])

    @property
    def rotary_dim(self) -> int:
        """Dimensions of a grouped-query head that RoPE turns."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def latent_dim(self) -> int:
        """Values the cache keeps per token and layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim


# The scope round the attention half of a layer of each kind.
SCOPE_OF = {"mla": "mla", "sliding_attention": "attn_window",
            "full_attention": "attn_full", SPARSE: "attn_sparse",
            LINEAR: "attn_linear"}
# The floor under the sum of the chosen scores, as the source's code has it.
_EPS = 1e-20
# `e_score_correction_bias`: a seeded, non-zero, untrained buffer, uniform in
# [-BIAS_SCALE, BIAS_SCALE].
BIAS_SCALE = 0.05
# Blocking of the passes (memory, never results), sized so that the shipped
# preset's step program fits one v5e (15.2 GB by `memory_analysis()`).
ATTN_ROWS = 8       # episodes a trip of the causal pass's attention
# The grouped-query layers' causal pass also blocks over queries, so that a
# row of 4,096 positions fits: a trip holds the `[heads, ATTN_QUERIES, keys]`
# scores of `GQA_ROWS` episodes, and a window layer's block meets only the
# keys of its band. The kernel that takes the pass on a TPU tiles the row's
# square by `ATTN_QUERIES` queries and keys.
GQA_ROWS = 1        # episodes a trip
ATTN_QUERIES = 512  # queries a block; the kernel's tile
LINEAR_ROWS = 1     # episodes a trip of the linear layers' causal pass
MLP_ROWS = 8192     # token rows a trip of the dense MLP and the shared expert
HEAD_ROWS = 4096    # token rows a trip of the lm_head
MOE_ROWS = 24576    # held assignments a trip of the grouped matmuls
# Up to this many tokens a pass (a decode step), each held expert runs on
# every token and its weights are read once.
MOE_DENSE_TOKENS = 128
# Of those passes, the ones that expect fewer than this many assignments an
# expert (`N x num_experts_per_tok / n_routed_experts`: 1.0 at Mellum's 8
# rows, 2.0 at JoyAI's 64) read only the experts some token chose
# (`ops/moe_decode.py`); the others run the batched matmuls over every held
# expert. What decided it (my chip runs, PR 35, six seeds a side): at 1.0 a
# third of the held experts is read by no step and the cell gained 17.6%
# (9,790 -> 11,514 steps/s, spread 0.38%); at 2.0 four fifths are read, the
# kernel gave +0.6% (15,857 -> 15,956) and a time that follows the seed's
# routing (`decode_experts_read_frac` 0.775-0.827): a six-seed spread of
# 0.58% where the batched matmuls have 0.11%, over half the benchmark's bound.
MOE_KERNEL_ASSIGNMENTS = 2.0


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

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    norm = jnp.zeros if cfg.zero_centered_norm else jnp.ones

    p: dict[str, Any] = {
        "embed": jax.random.normal(next(keys), (vocab_size, H), jnp.float32)
    }
    for i in range(cfg.num_hidden_layers):
        layer = {
            "attn_norm": norm((H,), jnp.float32),
            "ffn_norm": norm((H,), jnp.float32),
        }
        if cfg.attention(i) == LINEAR:
            layer["linear"] = gated_delta.init_params(mat, uniform, cfg)
        elif cfg.attention(i) != "mla":
            kv = cfg.num_key_value_heads * cfg.head_dim
            layer["attn"] = {
                "w_q": mat(H, nh * cfg.head_dim), "w_k": mat(H, kv),
                "w_v": mat(H, kv), "w_o": mat(nh * cfg.head_dim, H),
            }
            if cfg.gated_attention:
                layer["attn"].update({
                    "w_gate": mat(H, nh * cfg.head_dim),
                    "q_norm": norm((cfg.head_dim,), jnp.float32),
                    "k_norm": norm((cfg.head_dim,), jnp.float32),
                })
            if cfg.attention(i) == SPARSE:
                sa = cfg.sa_config
                layer["attn"].update({
                    "q_norm": jnp.ones((cfg.head_dim,), jnp.float32),
                    "k_norm": jnp.ones((cfg.head_dim,), jnp.float32),
                    "indexer": {
                        "w_q": mat(H, sa.indexer_num_heads * sa.indexer_head_dim),
                        "w_k": mat(H, sa.indexer_head_dim),
                        "k_norm": jnp.ones((sa.indexer_head_dim,), jnp.float32),
                        "k_bias": jnp.zeros((sa.indexer_head_dim,), jnp.float32),
                        "w_weights": mat(H, sa.indexer_num_heads),
                    },
                })
        else:
            layer["mla"] = {
                "w_qa": mat(H, cfg.q_lora_rank),
                "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
                "w_qb": mat(cfg.q_lora_rank,
                            nh * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
                "w_kva": mat(H, cfg.latent_dim),
                "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
                "w_kvb": mat(cfg.kv_lora_rank,
                             nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "w_o": mat(nh * cfg.v_head_dim, H),
            }
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = mlp(width=cfg.intermediate_size)
        else:
            layer["moe"] = {"router": mat(H, cfg.n_routed_experts)}
            if cfg.scoring_func == "sigmoid":
                layer["moe"]["bias"] = jax.random.uniform(
                    next(keys), (cfg.n_routed_experts,), jnp.float32,
                    -BIAS_SCALE, BIAS_SCALE)
            layer["moe"]["experts"] = mlp(
                cfg.experts_held, width=cfg.moe_intermediate_size)
            if cfg.shared_expert_intermediate_size:
                layer["moe"]["shared"] = mlp(width=cfg.shared_expert_intermediate_size)
                layer["moe"]["shared_gate"] = mat(H, 1)
            elif cfg.n_shared_experts:
                layer["moe"]["shared"] = mlp(
                    width=cfg.n_shared_experts * cfg.moe_intermediate_size)
        p[f"layer_{i}"] = layer
    p["final_norm"] = norm((H,), jnp.float32)
    p["lm_head"] = mat(H, vocab_size, scale=0.01)
    p["value_head"] = {"kernel": mat(H, 1), "bias": jnp.zeros((1,), jnp.float32)}
    return {"params": p}


# -- pieces ---------------------------------------------------------------

def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _norm(cfg: SeqPolicyConfig, x, w):
    """The block's RMSNorm of `x` by the weight `w` (`1 + w` where the
    configuration's norms are zero-centred)."""
    return _rms(x, 1.0 + w if cfg.zero_centered_norm else w, cfg.rms_norm_eps)


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


def _map_rows(fn, rows: int, *xs, remat: bool = True):
    """`fn` over the leading axis of `xs`, `rows` at a time, each trip
    rematerialized in the backward pass (so a trip's intermediates are never
    all alive; `remat=False` where `fn` rematerializes smaller pieces itself).
    One call where `rows` covers everything."""
    n = xs[0].shape[0]
    if rows >= n:
        return fn(*xs)
    while n % rows:  # the largest trip under `rows` that divides
        rows -= 1
    split = lambda x: x.reshape(n // rows, rows, *x.shape[1:])  # noqa: E731
    out = jax.lax.map(lambda c: (jax.checkpoint(fn) if remat else fn)(*c),
                      tuple(map(split, xs)))
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

# -- grouped-query attention, windowed and full ----------------------------

def rope_frequencies(cfg: SeqPolicyConfig, kind: str):
    """(inv_freq [head_dim / 2] float32, the factor on cos and sin) of a
    grouped-query layer of `kind`. A window layer: `theta ** (-2j / d)`.
    A full layer: YaRN, as `rope_parameters.full_attention` gives it. Pair
    `j` turns `original_max_position_embeddings * inv_freq_j / 2 pi` times
    over the original context; the pairs that turn `beta_fast` times or more
    keep their frequency, those that turn `beta_slow` times or fewer take
    `inv_freq_j / factor`, a linear ramp over the pair index between the two
    blends the rest, at every position; cos and sin are multiplied by
    `attention_factor`."""
    d = cfg.rotary_dim
    inv = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if kind != "full_attention" or cfg.rope_factor == 1.0:
        return inv.astype(np.float32), 1.0

    def pair_that_turns(times):
        # jaxlint: disable=nonfinite-hazard (host arithmetic on the
        # configuration's positive constants, at trace time; no array)
        return d * math.log(cfg.rope_original_max_position_embeddings
                            / (times * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(pair_that_turns(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(cfg.rope_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = inv / cfg.rope_factor * ramp + inv * (1.0 - ramp)
    return inv.astype(np.float32), cfg.rope_attention_factor


def _rope_halves(x, positions, freq):
    """Rotate the pairs `(x[j], x[j + d/2])` of the last axis (the
    `rotate_half` convention) by `position * inv_freq_j`; `positions`
    broadcasts against `x.shape[:-1]`, `freq` is `rope_frequencies`'."""
    inv, factor = freq
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x = x.astype(jnp.float32)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _gqa_project(p, h, positions, cfg, kind, which: str, scale: float = 1.0):
    """`h W_<which>` as `[.., heads, head_dim]`, RoPE applied to q and k (to
    their first `rotary_dim` dimensions), after the per-head RMSNorm where
    the layer has one (`q_norm`, `k_norm`); q or k times `scale` in float32
    before the one cast to `compute_dtype`."""
    cd = jnp.dtype(cfg.compute_dtype)
    y = _mm(h, p[f"w_{which}"], cd, out=cd)
    y = y.reshape(*h.shape[:-1], -1, cfg.head_dim)
    if which == "v":
        return y
    if f"{which}_norm" in p:
        y = _norm(cfg, y, p[f"{which}_norm"])
    freq = rope_frequencies(cfg, kind)
    rot = cfg.rotary_dim
    if rot == cfg.head_dim:
        y = _rope_halves(y, positions[..., None], freq)
    else:
        turned = _rope_halves(y[..., :rot], positions[..., None], freq)
        y = jnp.concatenate([turned, y[..., rot:].astype(jnp.float32)], axis=-1)
    return (y if scale == 1.0 else y * scale).astype(cd)


def _gate(p, h, out, cd):
    """`out [.., heads x head_dim]` times the output gate `sigmoid(h W_gate)`
    where the layer has one."""
    if "w_gate" not in p:
        return out
    return out * jax.nn.sigmoid(_mm(h, p["w_gate"], cd))


def window_of(cfg: SeqPolicyConfig, kind: str, horizon: int) -> int:
    """How many keys a query of a layer of `kind` sees at most, its own
    counted, and so the slots a row of that layer's cache has."""
    return min(cfg.sliding_window, horizon) if kind == "sliding_attention" else horizon


def gqa_unroll(p, h, positions, cfg: SeqPolicyConfig, kind: str):
    """Causal grouped-query attention over `h [E, T, H]`: (out [E, T, H],
    (k, v) each `[E, T, kv heads, head_dim]` in `compute_dtype`, the keys
    after RoPE: what a cache keeps). Keys and values are computed for all
    rows at once; the queries, the scores and the output projection run
    `GQA_ROWS` episodes a trip. A query `t` attends the keys `[t - window +
    1, t]` and no others.

    Where `sparse_attention.takes_kernel` (a TPU, rows of whole
    `ATTN_QUERIES` tiles, heads of whole lanes), a trip's queries are
    projected whole and ONE kernel a row attends under the band, a static
    mask (`sparse_attention.attend_band`): a tile outside the band is
    neither computed nor fetched, no score reaches HBM. The trip is
    rematerialized in the backward pass but for the kernel's residuals.
    Elsewhere (the CPU, the tiny presets) a trip runs `ATTN_QUERIES` queries
    a block, each block rematerialized in the backward pass; a block of
    queries `[q0, q1)` takes the keys `[q0 - window + 1, q1)` only, so a
    window layer never multiplies what lies outside its band."""
    cd = jnp.dtype(cfg.compute_dtype)
    nkv, d = cfg.num_key_value_heads, cfg.head_dim
    T = h.shape[1]
    window = window_of(cfg, kind, T)
    k = _gqa_project(p, h, positions, cfg, kind, "k")
    v = _gqa_project(p, h, positions, cfg, kind, "v")

    def block(q0, q1, h, k, v, positions):
        lo = max(0, q0 - window + 1)
        q = _gqa_project(p, h[:, q0:q1], positions[:, q0:q1], cfg, kind, "q")
        q = q.reshape(*q.shape[:2], nkv, -1, d)
        s = _einsum("eqkgd,eskd->ekgqs", q, k[:, lo:q1], cd) * d ** -0.5
        ahead = jnp.arange(q0, q1)[:, None] - jnp.arange(lo, q1)[None, :]
        s = jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)
        out = _einsum("ekgqs,eskd->eqkgd", jax.nn.softmax(s, axis=-1), v[:, lo:q1], cd)
        out = _gate(p, h[:, q0:q1], out.reshape(*out.shape[:2], -1), cd)
        return _mm(out, p["w_o"], cd)

    def blocks(h, k, v, positions):
        return jnp.concatenate([
            jax.checkpoint(functools.partial(block, q0, min(q0 + ATTN_QUERIES, T)))(
                h, k, v, positions)
            for q0 in range(0, T, ATTN_QUERIES)], axis=1)

    def kernel(h, k, v, positions):
        q = _gqa_project(p, h, positions, cfg, kind, "q", scale=d ** -0.5)
        with jax.named_scope("attn_causal"):
            out = sparse_attention.attend_band(
                q.reshape(*q.shape[:2], nkv, -1, d), k, v, window=window,
                block_q=ATTN_QUERIES, block_kv=ATTN_QUERIES)
        return _mm(_gate(p, h, out.reshape(*h.shape[:2], -1), cd), p["w_o"], cd)

    if sparse_attention.takes_kernel(T, d, ATTN_QUERIES, ATTN_QUERIES):
        attend = jax.checkpoint(kernel, policy=jax.checkpoint_policies.save_only_these_names(
            sparse_attention.RESIDUALS))
    else:
        attend = blocks
    return _map_rows(attend, GQA_ROWS, h, k, v, positions, remat=False), (k, v)


def gqa_step(p, h, positions, cache, index: int, slot, cfg: SeqPolicyConfig, kind: str):
    """One token a row through a grouped-query layer whose keys and values
    are layer `index` of `cache = (k, v)`, each `[layers, E, kv heads, slots,
    head_dim]`: the token's key (after RoPE) and value go into slot `slot
    mod slots`, so a window layer's slots are a ring that keeps the last
    `slots` positions (a key carries its own rotation, so the order of the
    slots does not matter to the scores), and attention reads the slots
    filled so far. Returns (out [E, H], cache)."""
    cd = jnp.dtype(cfg.compute_dtype)
    nkv, d = cfg.num_key_value_heads, cfg.head_dim
    slots = cache[0].shape[3]
    q = _gqa_project(p, h, positions, cfg, kind, "q").reshape(h.shape[0], nkv, -1, d)
    put = lambda buf, new: jax.lax.dynamic_update_slice(  # noqa: E731
        buf, new.astype(buf.dtype)[None, :, :, None, :], (index, 0, 0, slot % slots, 0))
    k_all = put(cache[0], _gqa_project(p, h, positions, cfg, kind, "k"))
    v_all = put(cache[1], _gqa_project(p, h, positions, cfg, kind, "v"))
    s = _einsum("ekgd,eksd->ekgs", q, k_all[index], cd) * d ** -0.5
    s = jnp.where(jnp.arange(slots) <= slot, s, -jnp.inf)
    o = _einsum("ekgs,eksd->ekgd", jax.nn.softmax(s, axis=-1), v_all[index], cd)
    return _mm(_gate(p, h, o.reshape(h.shape[0], -1), cd), p["w_o"], cd), (k_all, v_all)


def gqa_fill(cache, index: int, k, v, keys=None):
    """Layer `index` of `cache` as a causal pass over positions `[0, P)`
    leaves it: `k`, `v` `[E, P, kv heads, head_dim]` at slot `position mod
    slots`, the last `slots` positions where the pass was longer; a sparse
    layer's indexer keys `keys [E, P, d]` at slot `position`."""
    slots = cache[0].shape[3]
    P = k.shape[1]

    def put(buf, new):
        new = jnp.swapaxes(new, 1, 2)[:, :, max(0, P - slots):]
        if P > slots:  # position j of the kept ones sits at slot j mod slots
            new = jnp.roll(new, (P - slots) % slots, axis=2)
        return jax.lax.dynamic_update_slice(
            buf, new.astype(buf.dtype)[None], (index, 0, 0, 0, 0))

    filled = put(cache[0], k), put(cache[1], v)
    if keys is None:
        return filled
    return (*filled, jax.lax.dynamic_update_slice(
        cache[2], keys.astype(cache[2].dtype)[None], (index, 0, 0, 0)))


# -- sparse layers: grouped-query attention over the keys an indexer picks ---

def _index_frequencies(cfg: SeqPolicyConfig):
    """RoPE over all `indexer_head_dim` dimensions of the indexer's queries
    and key, pairs `(j, j + d/2)`, frequencies `theta ** (-2j / d)`."""
    d = cfg.sa_config.indexer_head_dim
    return (cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)).astype(
        np.float32), 1.0


def index_keys(ip, h, positions, cfg: SeqPolicyConfig):
    """The indexer's key of each position, `LayerNorm(h W_k)` with RoPE:
    `[.., indexer_head_dim]` in `compute_dtype`, what the cache keeps."""
    k = _mm(h, ip["w_k"], jnp.dtype(cfg.compute_dtype))
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    k = k * ip["k_norm"] + ip["k_bias"]
    return _rope_halves(k, positions, _index_frequencies(cfg)).astype(cfg.compute_dtype)


def index_scores(ip, h, positions, keys, cfg: SeqPolicyConfig):
    """`I [e, Q, S] = sum_j w_j relu(q_j . k)` of the queries `h [e, Q, H]`
    at `positions [e, Q]` over the indexer keys `keys [e, S, d]`; `w = h
    W_weights / sqrt(heads x d)`. Float32 sums over `compute_dtype`
    operands; -0.0 is written +0.0, so that equal scores are equal bits."""
    cd = jnp.dtype(cfg.compute_dtype)
    sa = cfg.sa_config
    q = _mm(h, ip["w_q"], cd, out=cd).reshape(
        *h.shape[:-1], sa.indexer_num_heads, sa.indexer_head_dim)
    q = _rope_halves(q, positions[..., None], _index_frequencies(cfg)).astype(cd)
    w = _mm(h, ip["w_weights"], cd) * (sa.indexer_num_heads * sa.indexer_head_dim) ** -0.5
    scores = jnp.sum(jax.nn.relu(_einsum("eqjd,esd->eqjs", q, keys, cd)) * w[..., None],
                     axis=-2)
    return jnp.where(scores == 0.0, 0.0, scores)


RADIX_BITS = 4  # bits of the k-th largest score found a pass of `top_k_mask`


def top_k_mask(scores, valid, k: int):
    """The keys `lax.top_k` picks of `scores [.., S]` where `valid`, as a
    mask: the `min(k, valid)` largest, ties to the lower position. No sort:
    the k-th largest score is found `RADIX_BITS` bits a pass on the scores'
    bits read as integers in the order of the floats; every key above it is
    kept, and of the keys equal to it the first ones. The reference of
    `ops/topk_select.py`'s kernel, which the sparse layers take instead on a
    TPU (`topk_select.engages`), and their path everywhere else."""
    if k >= scores.shape[-1]:
        return jnp.broadcast_to(valid, scores.shape)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(valid, scores, -jnp.inf).astype(jnp.float32), jnp.uint32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    kth = jnp.zeros((*key.shape[:-1], 1), jnp.uint32)
    digits = jnp.arange(1, 1 << RADIX_BITS, dtype=jnp.uint32)
    for shift in range(32 - RADIX_BITS, -1, -RADIX_BITS):
        # How many keys lie at or above each next digit: it falls with the
        # digit, so the digits it keeps at k or more are the digit itself.
        at_least = kth | (digits << shift)
        count = jnp.sum(key[..., None, :] >= at_least[..., None], axis=-1)
        kth = kth | (jnp.sum(count >= k, axis=-1, keepdims=True).astype(
            jnp.uint32) << shift)
    above, tie = key > kth, key == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return valid & (above | (tie & (jnp.cumsum(tie, axis=-1) <= room)))


def _select_prefix(scores, lengths, k: int):
    """`top_k_mask` of `scores [.., S]` where each row's valid keys are the
    prefix `[0, lengths)` (`lengths` broadcast to the rows), as the kernel
    of `ops/topk_select.py`: the same mask, bool."""
    S = scores.shape[-1]
    lengths = jnp.broadcast_to(lengths, scores.shape[:-1]).reshape(-1)
    keep = topk_select.select(jax.lax.stop_gradient(scores).reshape(-1, S), lengths, k)
    return keep.reshape(scores.shape) != 0


def _chunk(size: int, T: int) -> int:
    """The largest block of at most `size` that divides `T`."""
    size = min(size, T)
    while T % size:
        size -= 1
    return size


# The name of a row's selection, which the kernel's backward pass keeps.
KEEP = "sparse_keep"


def dsa_unroll(p, h, positions, cfg: SeqPolicyConfig):
    """Causal sparse attention over `h [E, T, H]`: (out [E, T, H], (k, v,
    index keys): what the cache keeps, `sparse_tiles_frac`). Keys, values
    and the indexer's keys are computed for all rows at once; the queries
    run `GQA_ROWS` episodes a trip, and the indexer selects their keys
    `q_chunk_size` queries a block, the blocks one `lax.map` (a loop the
    compiler meets once). A block's selection is the kernel of
    `ops/topk_select.py` where `topk_select.engages` (a TPU, rows of whole
    tiles), `top_k_mask` elsewhere: the same mask.

    Where `sparse_attention.takes_kernel` (a TPU, shapes that tile), the
    blocks' selections are stacked into the row's `keep [T, T]` and one
    kernel attends over the `q_chunk_size` x `kv_chunk_size` tiles that hold
    a selected pair; no score reaches HBM. The row is rematerialized in the
    backward pass but for `keep` and the kernel's residuals, so the mask's
    tiled copies are never alive across the rows. Elsewhere each block
    scores its queries against every key of the row, masked to its
    selection, and is rematerialized in the backward pass
    (`sparse_attention.reference`: the tiny preset, the CPU).

    `sparse_tiles_frac` is the share of those tiles of the square that hold
    at least one selected pair, over the rows, read off the selection the
    pass built."""
    cd = jnp.dtype(cfg.compute_dtype)
    nkv, d = cfg.num_key_value_heads, cfg.head_dim
    T = h.shape[1]
    Q = _chunk(cfg.sa_config.q_chunk_size, T)
    K = _chunk(cfg.sa_config.kv_chunk_size, T)
    k = _gqa_project(p, h, positions, cfg, SPARSE, "k")
    v = _gqa_project(p, h, positions, cfg, SPARSE, "v")
    with jax.named_scope("dsa_index"):
        keys = index_keys(p["indexer"], h, positions, cfg)

    def select(h, keys, positions, q0):
        hq = jax.lax.dynamic_slice_in_dim(h, q0, Q, axis=1)
        pq = jax.lax.dynamic_slice_in_dim(positions, q0, Q, axis=1)
        with jax.named_scope("dsa_index"):
            scores = index_scores(p["indexer"], hq, pq, keys, cfg)
            with jax.named_scope("dsa_select"):
                if topk_select.engages(T, cfg.sa_config.topk):
                    keep = _select_prefix(scores, q0 + jnp.arange(Q) + 1, cfg.sa_config.topk)
                else:
                    causal = (q0 + jnp.arange(Q))[:, None] >= jnp.arange(T)[None, :]
                    keep = top_k_mask(jax.lax.stop_gradient(scores), causal,
                                      cfg.sa_config.topk)
        return hq, pq, keep

    def tiles_held(keep):  # [e, Q, T] -> [e]
        return jnp.sum(jnp.any(keep.reshape(keep.shape[0], Q, T // K, K), axis=(1, 3)),
                       axis=-1)

    def kernel(h, k, v, keys, positions):
        def block(q0):
            keep = select(h, keys, positions, q0)[2]
            return keep, tiles_held(keep)

        keep, held = jax.lax.map(block, jnp.arange(0, T, Q))
        keep = checkpoint_name(jnp.swapaxes(keep, 0, 1).reshape(h.shape[0], T, T), KEEP)
        # The projection of `_gqa_project`, the scale folded into q in
        # float32 before its one cast.
        q = _mm(h, p["w_q"], cd, out=cd).reshape(*h.shape[:2], nkv, -1, d)
        if "q_norm" in p:
            q = _norm(cfg, q, p["q_norm"])
        q = _rope_halves(q, positions[..., None, None], rope_frequencies(cfg, SPARSE))
        out = sparse_attention.attend((q * d ** -0.5).astype(cd), k, v, keep,
                                      block_q=Q, block_kv=K)
        return _mm(out.reshape(*h.shape[:2], -1), p["w_o"], cd), jnp.sum(held, 0)

    def blocks(h, k, v, keys, positions):
        def block(q0):
            hq, pq, keep = select(h, keys, positions, q0)
            q = _gqa_project(p, hq, pq, cfg, SPARSE, "q")
            q = q.reshape(*q.shape[:2], nkv, -1, d)
            out = sparse_attention.reference(q, k, v, keep, d ** -0.5)
            return _mm(out.reshape(*out.shape[:2], -1), p["w_o"], cd), tiles_held(keep)

        out, held = jax.lax.map(jax.checkpoint(block), jnp.arange(0, T, Q))
        return jnp.swapaxes(out, 0, 1).reshape(h.shape), jnp.sum(held, 0)

    if sparse_attention.takes_kernel(T, d, Q, K):
        attend = jax.checkpoint(kernel, policy=jax.checkpoint_policies.save_only_these_names(
            KEEP, sparse_attention.RESIDUALS))
    else:
        attend = blocks
    out, held = _map_rows(attend, GQA_ROWS, h, k, v, keys, positions, remat=False)
    return out, (k, v, keys), jnp.mean(held) / ((T // Q) * (T // K))


def dsa_step(p, h, positions, cache, index: int, slot, cfg: SeqPolicyConfig):
    """One token a row through a sparse layer whose keys, values and
    indexer keys are layer `index` of `cache = (k, v, index keys)`, `[layers,
    E, kv heads, T, head_dim]` twice and `[layers, E, T, indexer_head_dim]`:
    the token's three go into slot `slot`, the indexer scores every filled
    slot, and attention reads the `topk` it selects (a masked read of the
    whole cache; the selection as in `dsa_unroll`). Returns (out [E, H],
    cache)."""
    cd = jnp.dtype(cfg.compute_dtype)
    nkv, d = cfg.num_key_value_heads, cfg.head_dim
    k_all, v_all, keys_all = cache
    slots = k_all.shape[3]
    q = _gqa_project(p, h, positions, cfg, SPARSE, "q").reshape(h.shape[0], nkv, -1, d)
    put = lambda buf, new: jax.lax.dynamic_update_slice(  # noqa: E731
        buf, new.astype(buf.dtype)[None, :, :, None, :], (index, 0, 0, slot, 0))
    k_all = put(k_all, _gqa_project(p, h, positions, cfg, SPARSE, "k"))
    v_all = put(v_all, _gqa_project(p, h, positions, cfg, SPARSE, "v"))
    with jax.named_scope("dsa_index"):
        keys_all = jax.lax.dynamic_update_slice(
            keys_all, index_keys(p["indexer"], h, positions, cfg)[None, :, None],
            (index, 0, slot, 0))
        scores = index_scores(p["indexer"], h[:, None], positions[:, None],
                              keys_all[index], cfg)[:, 0]
        with jax.named_scope("dsa_select"):
            if topk_select.engages(slots, cfg.sa_config.topk):
                keep = _select_prefix(scores, slot + 1, cfg.sa_config.topk)
            else:
                keep = top_k_mask(scores, jnp.arange(slots) <= slot, cfg.sa_config.topk)
    s = _einsum("ekgd,eksd->ekgs", q, k_all[index], cd) * d ** -0.5
    s = jnp.where(keep[:, None, None], s, -jnp.inf)
    o = _einsum("ekgs,eksd->ekgd", jax.nn.softmax(s, axis=-1), v_all[index], cd)
    return _mm(o.reshape(h.shape[0], -1), p["w_o"], cd), (k_all, v_all, keys_all)


def route(p, h, cfg: SeqPolicyConfig):
    """The published router: (idx [N, k] over all experts, weights [N, k]).
    Sigmoid scores are picked by score + bias; softmax scores (over all the
    experts) by themselves, with no bias."""
    logits = jnp.matmul(
        h.astype(jnp.float32), p["router"], precision=jax.lax.Precision.HIGHEST)
    if cfg.scoring_func == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(s, cfg.num_experts_per_tok)
    else:
        s = jax.nn.sigmoid(logits)
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


def reads_chosen_only(cfg: SeqPolicyConfig, N: int) -> bool:
    """Whether a pass of `N` tokens fetches only the held experts some token
    chose: few enough tokens, few enough assignments an expert, and the
    kernel engages (a TPU, experts that tile). Static: read from shapes."""
    expected = N * cfg.num_experts_per_tok / cfg.n_routed_experts
    return (N <= MOE_DENSE_TOKENS and expected < MOE_KERNEL_ASSIGNMENTS
            and moe_decode.engages(cfg.hidden_size, cfg.moe_intermediate_size))


def moe(p, h, cfg: SeqPolicyConfig):
    """The expert layer's part this chip computes for tokens `h [N, H]`:
    `sum_i w_i E_i(x)` over the chosen experts held here, plus the shared
    expert where the layer has one. Returns (y [N, H], stats, the share of
    the held experts whose weights a pass that `reads_chosen_only` read: None
    for every other pass)."""
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
        weights_here = weights_here[:, :held]
        if reads_chosen_only(cfg, N):
            read = jnp.sum(sizes > 0).astype(jnp.float32) / held
            y = moe_decode.moe_decode(p["experts"], h, weights_here, sizes, cd)
        else:
            y, read = moe_decode.reference(p["experts"], h, weights_here, cd), None
        done = landed
    else:
        y, done = _held_experts(cfg, R)(
            p["experts"], h, weights.reshape(A), (order, ends, sizes, landed))
        read = None
    if "shared" in p:
        def shared(x):
            out = _swiglu(p["shared"], x, cd)
            if "shared_gate" in p:
                out = out * jax.nn.sigmoid(_mm(x, p["shared_gate"], cd))
            return out

        with jax.named_scope("moe_shared"):
            y = y + _map_rows(shared, MLP_ROWS, h)
    mean_load = jnp.maximum(landed, 1).astype(jnp.float32) / held
    return y, {
        "routed_here_frac": landed.astype(jnp.float32) / A,
        "expert_load_max_over_mean": jnp.max(sizes).astype(jnp.float32) / mean_load,
        "moe_dropped": (landed - done).astype(jnp.float32),
    }, read


def _ffn(layer, h, cfg: SeqPolicyConfig):
    """The layer's FFN on `h [N, H]`: `moe`'s three for an expert layer, (y,
    None, None) for a dense one."""
    if "moe" in layer:
        return moe(layer["moe"], h, cfg)
    cd = jnp.dtype(cfg.compute_dtype)
    return _map_rows(lambda x: _swiglu(layer["mlp"], x, cd), MLP_ROWS, h), None, None


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
    its home is HBM, where a step writes one slot in place.

    Grouped-query layers keep keys and values, each kind of layer at its own
    size: one stacked pair `[layers of the kind, E, kv heads, slots,
    head_dim]` a kind, `horizon` slots a row for the full layers and a ring
    of `sliding_window` for the window layers: `{kind: (k, v)}`. Sparse
    layers keep the indexer's keys beside them, `[layers, E, horizon,
    indexer_head_dim]`: `{SPARSE: (k, v, index keys)}`. Linear layers keep
    their state and conv tail (`models/gated_delta.py`), a size that does not
    grow with `horizon`, beside the full layers' keys and values: `{LINEAR:
    (state [layers, E, n_v, d_k, d_v] float32, tail [layers, E, K - 1,
    channels]), "full_attention": (k, v)}`."""
    cd = jnp.dtype(cfg.compute_dtype)
    if cfg.full_attention_interval:
        linear = cfg.kinds.count(LINEAR)
        state = (linear, num_envs, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
        tail = (linear, num_envs, cfg.linear_conv_kernel_dim - 1,
                gated_delta.conv_channels(cfg))
        kv = (cfg.kinds.count("full_attention"), num_envs, cfg.num_key_value_heads,
              horizon, cfg.head_dim)
        return {LINEAR: (jnp.zeros(state, jnp.float32), jnp.zeros(tail, cd)),
                "full_attention": (jnp.zeros(kv, cd), jnp.zeros(kv, cd))}
    if cfg.sa_config is not None:
        lead = (cfg.num_hidden_layers, num_envs)
        kv = (*lead, cfg.num_key_value_heads, horizon, cfg.head_dim)
        return {SPARSE: (jnp.zeros(kv, cd), jnp.zeros(kv, cd), jnp.zeros(
            (*lead, horizon, cfg.sa_config.indexer_head_dim), cd))}
    if cfg.layer_types:
        kinds = cfg.layer_types[:cfg.num_hidden_layers]
        return {kind: tuple(
            jnp.zeros((kinds.count(kind), num_envs, cfg.num_key_value_heads,
                       window_of(cfg, kind, horizon), cfg.head_dim), cd)
            for _ in "kv") for kind in GQA_KINDS}
    lead = (cfg.num_hidden_layers, num_envs, horizon)
    return (jnp.zeros((*lead, cfg.kv_lora_rank), cd),
            jnp.zeros((*lead, cfg.qk_rope_head_dim), cd))


def step(params, obs, cache, cfg: SeqPolicyConfig):
    """Decode one token a row: `obs [E, 3]` -> (logits [E, V], value [E],
    cache, the share of the held experts' weights this step read as a mean
    over the expert layers: None where it read them all, `reads_chosen_only`
    false)."""
    cd = jnp.dtype(cfg.compute_dtype)
    p, layers = _layers(params)
    tokens, positions = obs[:, 0], obs[:, 1]
    slot = positions[0]
    x = jnp.take(p["embed"], tokens, axis=0)
    reads = []
    for i, layer in enumerate(layers):
        kind = cfg.attention(i)
        with jax.named_scope(SCOPE_OF[kind]):
            h = _norm(cfg, x, layer["attn_norm"])
            if kind == "mla":
                a, cache = mla_step(layer["mla"], h, positions, cache, i, slot, cfg)
            elif kind == LINEAR:
                a, kv = gated_delta.step(layer["linear"], h, cache[kind],
                                         cfg.cache_index(i), cfg)
                cache = {**cache, kind: kv}
            elif kind == SPARSE:
                a, kv = dsa_step(layer["attn"], h, positions, cache[kind],
                                 cfg.cache_index(i), slot, cfg)
                cache = {**cache, kind: kv}
            else:
                a, kv = gqa_step(layer["attn"], h, positions, cache[kind],
                                 cfg.cache_index(i), slot, cfg, kind)
                cache = {**cache, kind: kv}
            x = x + a
        y, _, read = _ffn(layer, _norm(cfg, x, layer["ffn_norm"]), cfg)
        x = x + y
        if read is not None:
            reads.append(read)
    h = _norm(cfg, x, p["final_norm"])
    with jax.named_scope("lm_head"):
        logits = _mm(h, p["lm_head"], cd)
    read = jnp.mean(jnp.stack(reads)) if reads else None
    return logits, _value(p, h), cache, read


def _value(p, h):
    v = jnp.matmul(h, p["value_head"]["kernel"], precision=jax.lax.Precision.HIGHEST)
    return v[..., 0] + p["value_head"]["bias"][0]


def trunk(params, obs, cfg: SeqPolicyConfig, cache=None):
    """The causal pass over `obs [E, T, 3]`: (final-normed hidden [E, T, H],
    the expert layers' stats and the sparse layers' `sparse_tiles_frac`,
    layers-mean, and the linear layers' `gdn_chunk_logdecay_min`, the least
    sum of the decay's exponent over a chunk of any layer, head and row,
    `cache`). A cache of grouped-query or linear
    layers (`init_cache`'s) is returned as this pass over positions `[0, T)`
    leaves it (the prefill); None stays None.

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

    def attn(kind, layer, x):
        with jax.named_scope(SCOPE_OF[kind]):
            h = _norm(cfg, x, layer["attn_norm"])
            if kind == "mla":
                return x + mla_unroll(layer["mla"], h, positions, cfg), None, None
            if kind == LINEAR:
                a, state, lowest = _map_rows(
                    lambda h: gated_delta.unroll(layer["linear"], h, cfg), LINEAR_ROWS, h)
                return x + a, state, jnp.min(lowest)
            if kind == SPARSE:
                a, kv, held = dsa_unroll(layer["attn"], h, positions, cfg)
                return x + a, kv, held
            a, kv = gqa_unroll(layer["attn"], h, positions, cfg, kind)
            return x + a, kv, None

    def ffn(layer, x):
        h = _norm(cfg, x, layer["ffn_norm"]).reshape(E * T, -1)
        y, stats, _ = _ffn(layer, h, cfg)
        return x + y.reshape(x.shape), stats

    stats, held, lowest = [], [], []
    for i, layer in enumerate(layers):
        kind = cfg.attention(i)
        x, kv, counted = jax.checkpoint(attn, static_argnums=0)(kind, layer, x)
        if cache is not None:
            fill = gated_delta.fill if kind == LINEAR else gqa_fill
            cache = {**cache, kind: fill(cache[kind], cfg.cache_index(i), *kv)}
        if kind == LINEAR:
            lowest.append(counted)
        elif counted is not None:
            held.append(counted)
        x, s = jax.checkpoint(ffn)(layer, x)
        if s is not None:
            stats.append(s)
    mean = {k: jnp.mean(jnp.stack([s[k] for s in stats])) for k in stats[0]} \
        if stats else {}
    if held:
        mean["sparse_tiles_frac"] = jnp.mean(jnp.stack(held))
    if lowest:
        mean["gdn_chunk_logdecay_min"] = jnp.min(jnp.stack(lowest))
    return _norm(cfg, x, p["final_norm"]), mean, cache


def logits_and_values(params, obs, cfg: SeqPolicyConfig):
    """(logits [E, T, V], values [E, T]) of the causal pass, logits whole:
    for tests and the benchmark's check at a few rows, not for the loss."""
    h, _, _ = trunk(params, obs, cfg)
    with jax.named_scope("lm_head"):
        logits = _mm(h, params["params"]["lm_head"], jnp.dtype(cfg.compute_dtype))
    return logits, _value(params["params"], h)


def unroll(params, obs, actions, cfg: SeqPolicyConfig):
    """What the loss needs of the causal pass over `obs [E, T, 3]`:
    (log-probability of `actions`, entropy, value, each [E, T], and the
    expert layers' stats). The `[E*T, V]` log-probabilities are never whole
    in memory: the head runs `HEAD_ROWS` token rows a trip."""
    cd = jnp.dtype(cfg.compute_dtype)
    h, stats, _ = trunk(params, obs, cfg)
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

    # The carry: the cache and, where a decode step reads only the chosen
    # experts, what the steps read of the held experts' weights (the sum of
    # `step`'s shares, the steps counted); () where every step reads all.
    def init_carry(num_envs):
        counted = (jnp.float32(0.0), jnp.float32(0.0)) \
            if reads_chosen_only(cfg, num_envs) else ()
        return init_cache(cfg, num_envs, horizon), counted

    def policy_step(params, obs, carry):
        cache, counted = carry
        logits, value, cache, read = step(params, obs, cache, cfg)
        if counted:
            counted = (counted[0] + read, counted[1] + 1.0)
        return Categorical(logits), value, (cache, counted)

    def policy_unroll(params, traj):
        obs = jnp.swapaxes(traj.obs, 0, 1)
        log_prob, entropy, value, stats = unroll(
            params, obs, jnp.swapaxes(traj.action, 0, 1), cfg)
        mask = 1.0 - traj.obs[..., 2].astype(jnp.float32)
        stats = {**stats, "response_frac": jnp.mean(mask), **shape_stats}
        return Unrolled(log_prob.T, entropy.T, value.T, mask, stats)

    def policy_prefill(params, obs, carry):
        h, _, cache = trunk(params, jnp.swapaxes(obs, 0, 1), cfg, carry[0])
        with jax.named_scope("lm_head"):
            logits = _mm(h[:, -1], params["params"]["lm_head"],
                         jnp.dtype(cfg.compute_dtype))
        return Categorical(logits), _value(params["params"], h).T, (cache, carry[1])

    def rollout_metrics(carry):
        if not carry[1]:
            return {"decode_experts_read_frac": jnp.float32(1.0)}
        read, steps = carry[1]
        return {"decode_experts_read_frac": read / jnp.maximum(steps, 1.0)}

    # What the shape of the traffic alone decides, as counters of the rows:
    # the key positions the window (sparse) layers attend over what full
    # causal layers would, and the positions one prefill pass fills over all
    # positions.
    shape_stats = {}
    if cfg.sa_config is not None:
        shape_stats = {
            "sparse_kept_frac": jnp.float32(
                sum(min(t + 1, cfg.sa_config.topk) for t in range(horizon))
                / (horizon * (horizon + 1) / 2)),
            "prefill_frac": jnp.float32(spec.prefill_len / horizon),
        }
    elif cfg.full_attention_interval:
        shape_stats = {"prefill_frac": jnp.float32(spec.prefill_len / horizon)}
    elif cfg.layer_types:
        kept = [min(t + 1, window_of(cfg, kind, horizon))
                for kind in cfg.layer_types[:cfg.num_hidden_layers]
                for t in range(horizon)]
        shape_stats = {
            "window_kept_frac": jnp.float32(
                sum(kept) / (cfg.num_hidden_layers * horizon * (horizon + 1) / 2)),
            "prefill_frac": jnp.float32(spec.prefill_len / horizon),
        }
    return Policy(
        init_carry=init_carry,
        step=policy_step,
        unroll=policy_unroll,
        # Every episode terminates at the unroll's last step, so V-trace
        # multiplies the bootstrap by zero: nothing to evaluate.
        bootstrap=lambda params, obs: jnp.zeros((obs.shape[0],), jnp.float32),
        # The grouped-query layers' caches can be filled by one causal pass;
        # the latent cache is decoded into from position 0.
        prefill=policy_prefill if cfg.grouped else None,
        # The actor's own routing, counted as it decodes: exact, where the
        # update's re-evaluation of the trajectory routes under newer weights.
        rollout_metrics=rollout_metrics
        if cfg.num_hidden_layers > cfg.first_k_dense_replace else None,
    )
