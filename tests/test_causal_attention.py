"""The window and full layers' causal pass as one kernel a row
(`actor_critic_tpu/ops/sparse_attention.py::attend_band`, JAX's splash
attention under a static band or causal mask), through the Pallas
interpreter at rows of 512 and tiles of 128: against the dense masked
einsums, outputs and the gradients of q, k and v; `seq_policy.gqa_unroll`
with the kernel forced against its block path, a window layer and a gated
full layer with per-head norms and partial RoPE; and the tiny presets,
which keep the blocks on a TPU."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from actor_critic_tpu import config as config_mod  # noqa: E402
from actor_critic_tpu.models import seq_policy as sp  # noqa: E402
from actor_critic_tpu.ops import pallas_scan, sparse_attention as sa  # noqa: E402

T, TILE = 512, 128
# A band that is no whole number of tiles: partial tiles at both of its edges.
WINDOW = 100
# bfloat16 operands on both sides: a relative error of a few roundings
# (2^-8 each) of the largest entry.
TOL = 2.0 ** -6


def _band(window):
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    return ((ahead >= 0) & (ahead < window))[None]


def _err(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("window, nkv, group, d", [(T, 1, 4, 128), (WINDOW, 2, 2, 256)])
def test_the_band_kernel_is_the_dense_masked_pass_forward_and_backward(window, nkv, group, d):
    """Output and the gradients of q, k and v within bfloat16 rounding of
    the dense masked einsums, causal and banded, grouped-query heads of 128
    and 256; the band's control: the kernel under the causal mask is another
    pass than the band's."""
    ks = jax.random.split(jax.random.key(window), 4)
    q = jax.random.normal(ks[0], (1, T, nkv, group, d))
    k = jax.random.normal(ks[1], (1, T, nkv, d)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, T, nkv, d)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[3], (1, T, nkv, group, d))
    scale = d ** -0.5

    def kernel(q, k, v, window=window):
        return sa.attend_band((q * scale).astype(k.dtype), k, v, window=window,
                              block_q=TILE, block_kv=TILE).astype(jnp.float32)

    def dense(q, k, v):
        return sa.reference(q.astype(k.dtype), k, v, _band(window), scale)

    def run(fn):
        out = jax.jit(fn)(q, k, v)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2)))(q, k, v)
        return [out, *grads]

    got, want = run(kernel), run(dense)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        assert _err(a, b) < TOL, (name, _err(a, b))
    if window < T:
        causal = jax.jit(lambda q, k, v: kernel(q, k, v, window=T))(q, k, v)
        assert _err(causal, want[0]) > 10 * TOL


def _layer(kind):
    """A layer of `kind` at head size 128 whose rows tile: a window layer
    (plain RoPE), or a gated full layer with per-head norms and RoPE on a
    quarter of each head, as Qwen3-Next's."""
    common = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                  head_dim=128, num_hidden_layers=1, first_k_dense_replace=1,
                  intermediate_size=32)
    if kind == "sliding_attention":
        return sp.SeqPolicyConfig(layer_types=(kind,), sliding_window=WINDOW, **common)
    return sp.SeqPolicyConfig(full_attention_interval=1, gated_attention=True,
                              partial_rotary_factor=0.25, zero_centered_norm=True,
                              **common)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_pass_with_the_kernel_is_its_block_path(monkeypatch, kind):
    """`gqa_unroll` with the kernel forced (one kernel a row) against its
    blocks, two rows: outputs, the keys and values it returns for the cache,
    and the gradient of every weight of the layer; q reaches the kernel in
    `compute_dtype`."""
    cfg = _layer(kind)
    monkeypatch.setattr(sp, "ATTN_QUERIES", TILE)
    p = sp.init_params(jax.random.key(0), cfg, 32)["params"]["layer_0"]["attn"]
    # Norm weights off their zero start, so that their gradients are no
    # special case.
    p = {name: w + 0.1 if name.endswith("_norm") else w for name, w in p.items()}
    h = jax.random.normal(jax.random.key(1), (2, T, cfg.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(T), (2, T))
    w = jax.random.normal(jax.random.key(2), (2, T, cfg.hidden_size))

    def run():
        def loss(p):
            out, kv = sp.gqa_unroll(p, h, positions, cfg, kind)
            return jnp.sum(out * w), (out, kv)
        return jax.jit(jax.grad(loss, has_aux=True))(p)

    grads, (out, kv) = run()
    monkeypatch.setattr(sa, "takes_kernel", lambda T_, d, bq, bkv: (bq, bkv) == (TILE, TILE))
    band, seen = sa.attend_band, []
    monkeypatch.setattr(sa, "attend_band", lambda q, *a, **kw: seen.append(
        (q.dtype, kw["window"])) or band(q, *a, **kw))
    grads_k, (out_k, kv_k) = run()
    assert set(seen) == {(jnp.dtype(cfg.compute_dtype), sp.window_of(cfg, kind, T))}
    assert _err(out_k, out) < TOL
    for a, b in zip(kv_k, kv):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
    assert set(grads_k) == set(grads)
    for name in grads:
        assert _err(grads_k[name], grads[name]) < TOL, name


@pytest.mark.parametrize("preset", ["impala_mellum2_tiny", "impala_qwen3_next_tiny"])
def test_the_tiny_presets_keep_the_blocks_on_a_tpu(monkeypatch, preset):
    """Their heads of 8 and rows of 16 do not tile: no kernel in the causal
    pass of any grouped-query kind even where the program is on a TPU."""
    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: True)
    seq = config_mod.PRESETS[preset].config.seq
    params = sp.init_params(jax.random.key(0), seq, 32)["params"]
    h = jax.random.normal(jax.random.key(1), (2, 16, seq.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(16), (2, 16))
    kinds = {kind: i for i, kind in reversed(list(enumerate(seq.kinds)))
             if kind in sp.GQA_KINDS}
    assert kinds
    for kind, i in kinds.items():
        p = params[f"layer_{i}"]["attn"]
        text = str(jax.make_jaxpr(lambda h: sp.gqa_unroll(p, h, positions, seq, kind))(h))
        assert "pallas_call" not in text, kind
