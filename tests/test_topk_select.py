"""The sparse layers' selection kernel (`ops/topk_select.py`) under the Pallas
interpreter: the mask `seq_policy.top_k_mask` gives and the set `lax.top_k`
picks, bit for bit, over rows whose valid keys are a prefix; the program
takes it only on a TPU where a row is whole tiles of 128 lanes, and gives the
same bits through it; compiled for a described v5e it is one Mosaic call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_tpu import config as config_mod
from actor_critic_tpu.models import seq_policy as sp
from actor_critic_tpu.ops import pallas_scan, topk_select


def _as_top_k(scores, valid, k):
    """The set `lax.top_k` picks of the valid scores, as a mask."""
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, idx].set(True) & valid


def _scores(kind, rows, S, key):
    s = jax.random.normal(key, (rows, S)) * 3
    if kind == "ties":  # a handful of values: ties at every row's threshold
        s = jnp.round(s) / 2
    elif kind == "one_value":
        s = jnp.full((rows, S), 0.75).at[1].set(-2.0).at[2].set(0.0)
    elif kind == "signed_zeros":  # -0.0 sorts below +0.0, as the bits say
        s = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 7), 0.5, (rows, S)),
                      -0.0, 0.0).at[:, ::5].set(s[:, ::5])
    return s.astype(jnp.float32)


@pytest.mark.parametrize("kind", ["random", "ties", "one_value", "signed_zeros"])
@pytest.mark.parametrize("rows, S, k", [
    (4, 256, 32),        # a decode step's rows: not a multiple of 8
    (40, 384, 100),      # rows past one block, padded to the next
    (8, 128, 127),       # every key but one
    (512, 1024, 256),    # an update block's shape at a reduced row
])
def test_the_kernel_is_top_k_mask_and_lax_top_k(kind, rows, S, k):
    """Valid lengths under `k`, at `k`, one under it, at `S` and every
    other, over planted ties, rows of one value and signed zeros."""
    key = jax.random.key(rows * 1000 + S + k)
    scores = _scores(kind, rows, S, key)
    lengths = jax.random.randint(jax.random.fold_in(key, 1), (rows,), 0, S + 1)
    lengths = lengths.at[0].set(S).at[1].set(k).at[2].set(k - 1).at[3].set(1)
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    got = np.asarray(topk_select.select(scores, lengths, k)) != 0
    assert np.array_equal(got, np.asarray(sp.top_k_mask(scores, valid, k)))
    assert np.array_equal(got, np.asarray(_as_top_k(scores, valid, k)))
    assert np.array_equal(got.sum(-1), np.minimum(np.asarray(lengths), k))


def test_ties_at_the_threshold_keep_the_first_positions():
    """Ten keys equal to the k-th where four fit: the first four of them."""
    S, k = 256, 8
    s = jnp.zeros((8, S)).at[:, :4].set(5.0).at[:, 40:50].set(1.0)
    got = np.asarray(topk_select.select(s, jnp.full((8,), S), k)) != 0
    assert np.array_equal(np.flatnonzero(got[0]), [0, 1, 2, 3, 40, 41, 42, 43])
    assert (got == got[0]).all()


@pytest.mark.parametrize("S, k, tpu, takes", [
    (8192, 2048, True, True), (6144, 2048, True, True), (128, 127, True, True),
    (8192, 2048, False, False),     # off a TPU
    (16, 5, True, False),           # the tiny preset's rows: no whole tile
    (1000, 100, True, False),       # not a multiple of 128
    (256, 256, True, False),        # k keeps every key: nothing to select
])
def test_the_program_takes_the_kernel_only_on_a_tpu_where_a_row_tiles(
        S, k, tpu, takes, monkeypatch):
    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: tpu)
    assert topk_select.engages(S, k) is takes


def test_the_cpu_and_the_tiny_preset_keep_the_radix_select():
    assert not pallas_scan.on_tpu()
    seq = config_mod.PRESETS["impala_keye_vl2"].config.seq
    assert not topk_select.engages(8192, seq.sa_config.topk)
    tiny = config_mod.PRESETS["impala_keye_vl2_tiny"].config
    assert not any(topk_select.engages(S, tiny.seq.sa_config.topk)
                   for S in (tiny.rollout_steps, 128))


def _sparse(T):
    seq = config_mod.PRESETS["impala_keye_vl2_tiny"].config.seq
    p = sp.init_params(jax.random.key(3), seq, 32)["params"]["layer_0"]["attn"]
    h = jax.random.normal(jax.random.key(4), (2, T, seq.hidden_size))
    return seq, p, h


def _through(monkeypatch, kernel):
    """`topk_select.engages` as on a TPU (the kernel under the interpreter)
    or off one; every call of the kernel counted."""
    calls = []
    real = topk_select.select

    def counted(scores, lengths, k):
        calls.append(scores.shape)
        return real(scores, lengths, k)

    monkeypatch.setattr(topk_select, "engages", lambda S, k: kernel)
    monkeypatch.setattr(topk_select, "select", counted)
    return calls


def test_the_causal_pass_gives_the_same_bits_through_the_kernel(monkeypatch):
    """A sparse layer over rows of 256 positions: the causal pass's output
    and its cache through the kernel are the radix select's to the bit, and
    each block of queries goes through the kernel as `[queries, keys]`."""
    T = 256
    seq, p, h = _sparse(T)
    positions = jnp.broadcast_to(jnp.arange(T), (2, T))
    calls = _through(monkeypatch, True)
    got = sp.dsa_unroll(p, h, positions, seq)
    assert calls == [(sp._chunk(seq.sa_config.q_chunk_size, T), T)]
    _through(monkeypatch, False)
    want = sp.dsa_unroll(p, h, positions, seq)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


@pytest.mark.parametrize("slot", [3, 130, 255])
def test_the_decode_step_gives_the_same_bits_through_the_kernel(slot, monkeypatch):
    """A decode step over a cache of 256 slots filled up to `slot`: fewer
    filled slots than `topk` and more."""
    T = 256
    seq, p, h = _sparse(T)
    positions = jnp.broadcast_to(jnp.arange(T), (2, T))
    _, cache, _ = sp.dsa_unroll(p, h, positions, seq)
    cache = jax.tree.map(lambda x: x[None], cache)
    cache = (jnp.swapaxes(cache[0], 2, 3), jnp.swapaxes(cache[1], 2, 3), cache[2])
    token = h[:, slot]
    at = jnp.full((2,), slot)
    calls = _through(monkeypatch, True)
    got = sp.dsa_step(p, token, at, cache, 0, slot, seq)
    assert calls == [(2, T)]
    _through(monkeypatch, False)
    want = sp.dsa_step(p, token, at, cache, 0, slot, seq)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


@pytest.mark.parametrize("rows, S", [(512, 8192), (512, 6144), (4, 8192)],
                         ids=["update_block", "prefill_block", "decode_step"])
def test_compiled_for_a_v5e_the_selection_is_one_mosaic_call(rows, S, v5e, monkeypatch):
    """The three shapes the cell calls it at: Mosaic takes the kernel (the
    order keys, a block of rows and the mask in VMEM) as one call."""
    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: True)
    on_chip = lambda *a: jax.ShapeDtypeStruct(*a, sharding=v5e)  # noqa: E731
    text = jax.jit(lambda s, n: topk_select.select(s, n, 2048)).lower(
        on_chip((rows, S), jnp.float32), on_chip((rows,), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "topk_select" in text
