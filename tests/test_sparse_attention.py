"""The sparse layers' causal pass as one kernel over the selected tiles
(`actor_critic_tpu/ops/sparse_attention.py`, JAX's splash attention with a
dynamic mask), through the Pallas interpreter, against the dense masked
einsums (`reference`): outputs and the gradients of q, k and v under a
selection that drops keys inside tiles, empties whole causal tiles and
keeps ties; which path a pass takes; and `seq_policy.dsa_unroll` with the
kernel forced against its reference path, `sparse_tiles_frac` included."""

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

T, D, TOPK, TILE = 1024, 128, 200, 256
SCALE = D ** -0.5
# bfloat16 operands on both sides: a relative error of a few roundings
# (2^-8 each) of the largest entry.
TOL = 2.0 ** -6


def _selection(seed=0):
    """`top_k_mask` over indexer-like scores of a few values (ties at every
    threshold), where the queries of the last two tiles score the keys of
    tile 1 at the bottom: those causal tiles hold no selected pair."""
    scores = jnp.round(jax.random.normal(jax.random.key(seed), (1, T, T)) * 2)
    far = (jnp.arange(T)[:, None] >= 2 * TILE) & (jnp.arange(T)[None, :] // TILE == 1)
    scores = jnp.where(far, -100.0, scores)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    return sp.top_k_mask(scores, causal, TOPK)


def _held(keep):
    n = T // TILE
    return np.asarray(jnp.any(keep.reshape(n, TILE, n, TILE), axis=(1, 3)))


def test_the_selection_drops_keys_in_tiles_empties_causal_tiles_and_keeps_one_key():
    keep = _selection()[0]
    kept = np.asarray(keep.sum(-1))
    assert kept.min() >= 1 and kept.max() == TOPK  # no empty row; rows drop keys
    held, causal = _held(keep), np.tril(np.ones((T // TILE,) * 2, bool))
    assert not (held & ~causal).any()
    assert (causal & ~held).sum() == 2  # tiles (2, 1) and (3, 1)
    full = np.asarray(jnp.all(keep.reshape(4, TILE, 4, TILE), axis=(1, 3)))
    assert (held & ~full).sum() > 4  # tiles with a mix of kept and dropped pairs


def _operands(nkv, group, seed=1):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (1, T, nkv, group, D))
    k = jax.random.normal(ks[1], (1, T, nkv, D)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, T, nkv, D)).astype(jnp.bfloat16)
    return q, k, v, jax.random.normal(ks[3], (1, T, nkv, group, D))


def _both(q, k, v, keep, w):
    """(output, gradients of q, k, v) of the kernel and of `reference`, q
    scaled in float32 and cast once on the kernel's side."""
    def kernel(q, k, v):
        return sa.attend((q * SCALE).astype(k.dtype), k, v, keep,
                         block_q=TILE, block_kv=TILE).astype(jnp.float32)

    def dense(q, k, v):
        return sa.reference(q.astype(k.dtype), k, v, keep, SCALE)

    def run(fn):
        out = jax.jit(fn)(q, k, v)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2)))(q, k, v)
        return [out, *grads]

    return run(kernel), run(dense)


def _err(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("nkv, group", [(1, 4), (1, 8), (2, 2)])
def test_the_kernel_is_the_dense_masked_pass_forward_and_backward(nkv, group):
    """Output and the gradients of q, k and v within bfloat16 rounding of
    the dense masked einsums, grouped-query heads laid out as the kernel's
    heads; under the whole causal mask instead the output is another."""
    keep = _selection()
    q, k, v, w = _operands(nkv, group)
    got, want = _both(q, k, v, keep, w)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        assert _err(a, b) < TOL, (name, _err(a, b))
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), keep.shape)
    other = sa.attend((q * SCALE).astype(k.dtype), k, v, causal, block_q=TILE, block_kv=TILE)
    assert _err(other, want[0]) > 10 * TOL


@pytest.mark.parametrize("T_, d, bq, bkv, tiles", [
    (T, D, TILE, TILE, True), (T, D, 512, 128, True), (T + 128, D, TILE, TILE, False),
    (T, 64, TILE, TILE, False), (T, D, 64, TILE, False), (T, D, TILE, 96, False)])
def test_the_kernel_is_taken_on_a_tpu_where_the_tiles_cover_the_row(monkeypatch, T_, d, bq, bkv, tiles):
    assert sa.tiles(T_, d, bq, bkv) is tiles
    assert not sa.takes_kernel(T_, d, bq, bkv)  # the CPU
    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: True)
    assert sa.takes_kernel(T_, d, bq, bkv) is tiles


def test_the_tiny_preset_keeps_the_reference_on_a_tpu(monkeypatch):
    """Head size 8 and rows of 16 do not tile: no kernel in its causal pass
    even where the program is on a TPU."""
    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: True)
    seq = config_mod.PRESETS["impala_keye_vl2_tiny"].config.seq
    p = sp.init_params(jax.random.key(0), seq, 32)["params"]["layer_0"]["attn"]
    h = jax.random.normal(jax.random.key(1), (2, 16, seq.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(16), (2, 16))
    text = str(jax.make_jaxpr(lambda h: sp.dsa_unroll(p, h, positions, seq))(h))
    assert "pallas_call" not in text


# A sparse layer at head size 128 and rows that tile (1,024 = 4 tiles of 256).
SEQ = sp.SeqPolicyConfig(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=1, head_dim=D,
    num_hidden_layers=1, first_k_dense_replace=1, intermediate_size=32,
    sa_config=sp.SparseAttention(indexer_head_dim=8, indexer_num_heads=2,
                                 q_chunk_size=TILE, kv_chunk_size=TILE, topk=TOPK))


def test_the_sparse_pass_with_the_kernel_is_its_reference_path(monkeypatch):
    """`dsa_unroll` with the kernel forced (the row's selection stacked,
    one kernel a row) against its block path, two rows: outputs,
    `sparse_tiles_frac` and the gradient of the layer's weights."""
    p = sp.init_params(jax.random.key(0), SEQ, 32)["params"]["layer_0"]["attn"]
    h = jax.random.normal(jax.random.key(1), (2, T, SEQ.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(T), (2, T))
    w = jax.random.normal(jax.random.key(2), (2, T, SEQ.hidden_size))

    def run():
        def loss(p):
            out, _, tiles = sp.dsa_unroll(p, h, positions, SEQ)
            return jnp.sum(out * w), (out, tiles)
        return jax.jit(jax.grad(loss, has_aux=True))(p)

    grads, (out, tiles) = run()
    monkeypatch.setattr(sa, "takes_kernel", lambda T, d, bq, bkv: (bq, bkv) == (TILE, TILE))
    text = str(jax.make_jaxpr(lambda h: sp.dsa_unroll(p, h, positions, SEQ))(h))
    assert "pallas_call" in text
    # q reaches the kernel in `compute_dtype`, scaled before its one cast.
    attend, seen = sa.attend, []
    monkeypatch.setattr(sa, "attend", lambda q, *a, **kw: seen.append(q.dtype) or attend(q, *a, **kw))
    grads_k, (out_k, tiles_k) = run()
    assert seen and set(seen) == {jnp.dtype(SEQ.compute_dtype)}
    assert _err(out_k, out) < TOL
    assert float(tiles_k) == float(tiles)
    # Rows of 1,024 at topk 200: every causal tile keeps a key, none past
    # the frontier does, so 10 of the 16 tiles.
    assert float(tiles) == pytest.approx(10 / 16)
    for name in ("w_q", "w_k", "w_v", "w_o", "q_norm", "k_norm"):
        assert _err(grads_k[name], grads[name]) < TOL, name
