"""The decode's experts as one kernel over the chosen experts' weights
(`actor_critic_tpu/ops/moe_decode.py`), through the Pallas interpreter,
against the batched matmuls over every held expert (`reference`); what the
kernel's index maps name over the whole grid; which path a pass takes; the
policy's decode step with the kernel forced; and the counter the kernel
brings, `decode_experts_read_frac`, in the rows of a toy run."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import train  # noqa: E402
from actor_critic_tpu import config as config_mod  # noqa: E402
from actor_critic_tpu.algos import common, impala  # noqa: E402
from actor_critic_tpu.models import seq_policy as sp  # noqa: E402
from actor_critic_tpu.ops import moe_decode, pallas_scan  # noqa: E402
from actor_critic_tpu.utils import compile_cache  # noqa: E402


def _experts(held, H, W, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    normal = lambda k, *shape: jax.random.normal(k, shape) / shape[-2] ** 0.5  # noqa: E731
    return {"w_gate": normal(keys[0], held, H, W), "w_up": normal(keys[1], held, H, W),
            "w_down": normal(keys[2], held, W, H)}


ROUTINGS = {
    "every": lambda held: list(range(held)),
    "some": lambda held: [e for e in range(held) if e % 3 != 1],
    "one": lambda held: [held // 2],
    "last": lambda held: [held - 1],
    "none": lambda held: [],
}


def _routed(N, held, experts, seed=1):
    """(weights_here [N, held], sizes [held]): each of the `experts` chosen
    by a few tokens (by the first of them at least), no other by any."""
    rng = np.random.default_rng(seed)
    picked = np.zeros((N, held), bool)
    picked[:, experts] = rng.random((N, len(experts))) < 0.4
    picked[0, experts] = True
    weights = np.where(picked, 0.1 + rng.random((N, held)), 0.0)
    return jnp.asarray(weights, jnp.float32), jnp.asarray(picked.sum(0), jnp.int32)


def _run(fn, *args):
    return jax.jit(fn)(*args)


# -- the kernel against the batched matmuls -----------------------------------

@pytest.mark.parametrize("cd, tol", [("float32", 2e-6), ("bfloat16", 2e-6)])
@pytest.mark.parametrize("N, held, H, W, tile", [
    (2, 4, 128, 128, None),       # the check's two rows, padded to a tile
    (8, 16, 128, 256, None),      # the window's eight, the width as one block
    (8, 16, 128, 256, 128),       # the width as two blocks
    (64, 4, 256, 384, 128),       # 64 rows, three blocks
])
def test_the_kernel_equals_the_batched_matmuls(N, held, H, W, tile, cd, tol, monkeypatch):
    """Same operands, same roundings: only the order of the float32 sum
    over experts and tiles differs."""
    if tile:
        monkeypatch.setattr(moe_decode, "VMEM_BLOCK_BYTES",
                            6 * H * tile * jnp.dtype(cd).itemsize)
    assert moe_decode.block_width(H, W, jnp.dtype(cd).itemsize) == (tile or W)
    experts, h = _experts(held, H, W), jax.random.normal(jax.random.key(7), (N, H))
    weights, sizes = _routed(N, held, ROUTINGS["some"](held))
    got = _run(lambda e, h, w, s: moe_decode.moe_decode(e, h, w, s, cd),
               experts, h, weights, sizes)
    want = _run(lambda e, h, w: moe_decode.reference(e, h, w, cd), experts, h, weights)
    assert got.shape == want.shape == (N, H) and got.dtype == want.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < tol * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("routing", list(ROUTINGS))
@pytest.mark.parametrize("held", [4, 16])
def test_the_kernel_under_every_routing(routing, held):
    """Every expert chosen, some, one, only the last, none (zeros: the
    output block is initialised in the kernel, whatever the routing)."""
    N, H, W = 8, 128, 128
    experts, h = _experts(held, H, W), jax.random.normal(jax.random.key(3), (N, H))
    weights, sizes = _routed(N, held, ROUTINGS[routing](held))
    got = _run(lambda e, h, w, s: moe_decode.moe_decode(e, h, w, s, "float32"),
               experts, h, weights, sizes)
    want = _run(lambda e, h, w: moe_decode.reference(e, h, w, "float32"),
                experts, h, weights)
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-6 * max(
        float(jnp.max(jnp.abs(want))), 1.0)
    if routing == "none":
        assert not bool(jnp.any(got))


@pytest.mark.parametrize("routing", ["some", "one", "last", "none"])
def test_nan_in_an_unchosen_experts_weights_reaches_no_output(routing):
    """The unchosen experts' three matrices are not read: NaN there gives,
    bit for bit, the result on zeros there. (The batched matmuls multiply
    them by a weight of zero: 0 x NaN.)"""
    N, held, H, W = 8, 16, 128, 256
    experts, h = _experts(held, H, W), jax.random.normal(jax.random.key(3), (N, H))
    weights, sizes = _routed(N, held, ROUTINGS[routing](held))
    unchosen = (sizes == 0)[:, None, None]
    run = jax.jit(lambda e: moe_decode.moe_decode(e, h, weights, sizes, "bfloat16"))
    want = run(jax.tree.map(lambda w: jnp.where(unchosen, 0.0, w), experts))
    poisoned = jax.tree.map(lambda w: jnp.where(unchosen, jnp.nan, w), experts)
    got = run(poisoned)
    assert bool(jnp.all(jnp.isfinite(got))) and bool(jnp.array_equal(got, want))
    assert not bool(jnp.all(jnp.isfinite(
        moe_decode.reference(poisoned, h, weights, "bfloat16"))))


# -- what the grid fetches -----------------------------------------------------

def _weight_blocks(sizes, H, W, monkeypatch):
    """The block index of each weight operand at every grid step, in the
    grid's order, read off the `BlockSpec`s the kernel is called with."""
    seen = {}

    def fake_call(kernel, *, grid_spec, **_):
        def call(ids, n_active, *operands):
            ids, n_active = np.asarray(ids), np.asarray(n_active)
            steps = [(s, j) for s in range(grid_spec.grid[0])
                     for j in range(grid_spec.grid[1])]
            for name, spec in zip(("gate", "up", "down"), grid_spec.in_specs[2:]):
                seen[name] = [tuple(int(i) for i in spec.index_map(s, j, ids, n_active))
                              for s, j in steps]
            return jnp.zeros(operands[0].shape, jnp.float32)
        return call

    monkeypatch.setattr(pl, "pallas_call", fake_call)
    held = len(sizes)
    experts = _experts(held, H, W)
    with jax.disable_jit():
        moe_decode.moe_decode(experts, jnp.zeros((8, H)), jnp.zeros((8, held)),
                              jnp.asarray(sizes, jnp.int32), "float32")
    return seen


@pytest.mark.parametrize("sizes", [
    [0, 2, 0, 1, 0, 0, 3, 0], [1, 1, 1, 1], [0, 0, 0, 5], [0, 0, 0, 0], [4, 0, 0, 0]])
@pytest.mark.parametrize("W, tile", [(128, 128), (256, 128)])
def test_the_index_maps_name_the_chosen_experts_blocks_and_no_others(
        sizes, W, tile, monkeypatch):
    """Over the whole grid the weights' index maps name every block of the
    chosen experts, in order, and nothing else; from the first step past the
    last chosen expert on the block never changes, so the pipeline, which
    fetches a block only when its index changes, fetches nothing more. No
    expert chosen: one block, never changed."""
    H = 128
    monkeypatch.setattr(moe_decode, "VMEM_BLOCK_BYTES", 6 * H * tile * 4)
    seen = _weight_blocks(sizes, H, W, monkeypatch)
    chosen = [e for e, n in enumerate(sizes) if n > 0]
    n_tiles = W // tile
    want = [(e, j) for e in chosen for j in range(n_tiles)]
    assert [(e, j) for e, _, j in seen["gate"]][:len(want)] == want
    assert seen["up"] == seen["gate"]
    assert [(e, j) for e, j, _ in seen["down"]] == [(e, j) for e, _, j in seen["gate"]]
    rest = seen["gate"][len(want):]
    assert len(rest) == (len(sizes) - len(chosen)) * n_tiles
    assert set(rest) <= {seen["gate"][len(want) - 1] if want else (0, 0, n_tiles - 1)}
    fetched = {seen["gate"][0]} | {b for a, b in zip(seen["gate"], seen["gate"][1:])
                                   if a != b}
    assert {e for e, _, _ in fetched} == (set(chosen) or {0})
    assert len(fetched) == max(len(want), 1)


@pytest.mark.parametrize("sizes, ids, n_active", [
    ([0, 2, 0, 1, 0, 0, 3, 0], [1, 3, 6, 6, 6, 6, 6, 6], 3),
    ([1, 1, 1, 1], [0, 1, 2, 3], 4),
    ([0, 0, 0, 5], [3, 3, 3, 3], 1),
    ([0, 0, 0, 0], [0, 0, 0, 0], 0),
])
def test_what_the_kernel_prefetches_from_the_sizes(sizes, ids, n_active):
    got_ids, got_n = moe_decode.chosen(jnp.asarray(sizes, jnp.int32))
    assert got_ids.dtype == jnp.int32 and got_ids.tolist() == ids and int(got_n) == n_active


# -- which path a pass takes ---------------------------------------------------

@pytest.mark.parametrize("on_tpu, H, W, kernel", [
    (True, 128, 256, True), (True, 128, 96, False), (True, 64, 128, False),
    (False, 128, 256, False)])
def test_the_kernel_engages_on_a_tpu_where_the_experts_tile(
        on_tpu, H, W, kernel, monkeypatch):
    """Read from the input: the kernel on a TPU where hidden and width are
    whole tiles of 128 lanes, the batched matmuls elsewhere (the tiny
    presets, every CPU run); the kernel itself refuses what does not tile."""
    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: on_tpu)
    assert moe_decode.engages(H, W) is kernel
    assert moe_decode.tiles(H, W) is (H % 128 == 0 and W % 128 == 0)
    seq = dataclasses.replace(
        config_mod.PRESETS["impala_mellum2_tiny"].config.seq,
        hidden_size=H, moe_intermediate_size=W)
    layer = {"router": jnp.ones((H, seq.n_routed_experts)) / H,
             "experts": _experts(seq.experts_held, H, W)}
    jaxpr = jax.make_jaxpr(lambda h: sp.moe(layer, h, seq)[0])(jnp.ones((4, H)))
    assert ("pallas_call" in str(jaxpr)) is kernel
    if not moe_decode.tiles(H, W):
        weights, sizes = _routed(8, 4, [1, 2])
        with pytest.raises(ValueError, match="not whole tiles"):
            moe_decode.moe_decode(_experts(4, H, W), jnp.zeros((8, H)), weights, sizes,
                                  "float32")


@pytest.mark.parametrize("preset, N, chosen_only", [
    ("impala_mellum2", 8, True),         # the window: 8 x 8 / 64 = 1 an expert
    ("impala_mellum2", 2, True),         # its check
    ("impala_mellum2", 16, False),       # 2 an expert
    ("impala_joyai_flash", 64, False),   # the window: 64 x 8 / 256 = 2 an expert
    ("impala_joyai_flash", 8, True),     # its check: 0.25
    ("impala_joyai_flash", 63, True),
    ("impala_mellum2", 129, False),      # past `MOE_DENSE_TOKENS`: the grouped matmuls
])
def test_only_a_pass_of_few_assignments_an_expert_reads_the_chosen_experts_alone(
        preset, N, chosen_only, monkeypatch):
    """Static, from shapes: fewer than `MOE_KERNEL_ASSIGNMENTS` assignments an
    expert expected in the pass (`N x num_experts_per_tok / n_routed_experts`),
    on a TPU; never off one. The policy's carry counts only where it does."""
    seq = config_mod.PRESETS[preset].config.seq
    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: True)
    assert sp.reads_chosen_only(seq, N) is chosen_only
    monkeypatch.setattr(pallas_scan, "on_tpu", lambda: False)
    assert sp.reads_chosen_only(seq, N) is False


# -- the policy's decode step with the kernel forced ---------------------------

def _forced(monkeypatch):
    """Every small pass by the kernel, run by the interpreter: `engages` as
    on a TPU (`moe_decode` itself reads `on_tpu` for `interpret`), and no
    limit on the assignments an expert (the tiny presets expect 4)."""
    monkeypatch.setattr(moe_decode, "engages", moe_decode.tiles)
    monkeypatch.setattr(sp, "MOE_KERNEL_ASSIGNMENTS", float("inf"))


TILED = {"seq.hidden_size": "128", "seq.moe_intermediate_size": "128"}


def _toy(preset, sets=None):
    preset = config_mod.resolve(preset, None, None, {**TILED, **(sets or {})})
    cfg = preset.config
    env, _ = train.build_env(preset.env, preset.algo, cfg, 0,
                             env_kwargs=preset.env_kwargs)
    return env, cfg


@pytest.mark.parametrize("preset", ["impala_joyai_flash_tiny", "impala_mellum2_tiny"])
def test_the_decode_step_with_the_kernel_gives_the_einsum_paths_logits(
        preset, monkeypatch):
    """`seq_policy.step` through every layer, the experts by the kernel
    against the experts by the batched matmuls: the logits within float32
    rounding of a reordered sum."""
    env, cfg = _toy(preset, {"seq.compute_dtype": "float32"})
    params = impala.init_params(env, cfg, jax.random.key(0))
    E = cfg.num_envs
    obs = jnp.stack([jnp.arange(E) % 7 + 1, jnp.zeros(E, jnp.int32),
                     jnp.ones(E, jnp.int32)], axis=1).astype(jnp.int32)
    cache = sp.init_cache(cfg.seq, E, cfg.rollout_steps)
    step = lambda: jax.jit(lambda p, o, c: sp.step(p, o, c, cfg.seq))(  # noqa: E731
        params, obs, cache)
    want, _, _, dense_read = step()
    _forced(monkeypatch)
    got, _, _, read = step()
    assert dense_read is None and 0.0 < float(read) <= 1.0
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(jnp.max(jnp.abs(want)))


# -- the counter in the rows ---------------------------------------------------

def _rows(tmp_path, argv):
    path = tmp_path / "metrics.jsonl"
    # `train.main` points this process's compile cache at a directory for
    # good; `temporary_cache` as the snapshot and restore of that (a cache
    # left on serves later tests of this worker executables compiled under
    # other scope names).
    with compile_cache.temporary_cache(tmp_path / "cache"):
        train.main([*argv, "--metrics", str(path), "--quiet",
                    "--compile-cache-dir", "none"])
    with open(path) as fh:
        return [json.loads(line) for line in fh]


RUN = ["--preset", "impala_mellum2_tiny", "--iterations", "3", "--chunk", "1",
       "--log-every", "1", "--no-warmup"]


def test_the_rows_of_a_run_on_the_batched_matmuls_read_every_expert(tmp_path):
    rows = _rows(tmp_path, RUN)
    assert len(rows) == 3
    assert all(row["decode_experts_read_frac"] == 1.0 for row in rows)


def test_the_rows_of_a_run_on_the_kernel_count_the_actors_own_routing(
        tmp_path, monkeypatch):
    """The rows' counter against a count made by hand: the same rollout,
    every decode step's routing recomputed layer by layer from the actor's
    parameters, the held experts with at least one assignment counted."""
    _forced(monkeypatch)
    sets = [f"{k}={v}" for k, v in TILED.items()]
    rows = _rows(tmp_path, [*RUN, "--iterations", "1", "--seed", "5",
                            *[a for s in sets for a in ("--set", s)]])
    env, cfg = _toy("impala_mellum2_tiny")
    state = impala.init_state(env, cfg, jax.random.key(5))
    policy = impala.make_policy(env, cfg)
    _, rkey = jax.random.split(state.key)
    seq, held = cfg.seq, cfg.seq.experts_held
    shares = []
    real_route = sp.route

    def note(idx):
        local = np.asarray(idx) - seq.expert_offset
        shares.append(len({int(e) for e in local.ravel() if 0 <= e < held}) / held)

    def counting_route(p, h, cfg_seq):
        idx, weights = real_route(p, h, cfg_seq)
        if h.shape[0] == cfg.num_envs:          # a decode step, not the prefill
            jax.debug.callback(note, idx)
        return idx, weights

    monkeypatch.setattr(sp, "route", counting_route)
    _, _, counted = jax.jit(lambda params, rollout, key: common.rollout_scan(
        env, policy, params, rollout, key, cfg.rollout_steps,
        policy_metrics=True))(state.actor_params, state.rollout, rkey)
    jax.effects_barrier()
    decode_steps = cfg.rollout_steps - env.spec.prefill_len
    assert len(shares) == decode_steps * seq.num_hidden_layers
    by_hand = sum(shares) / len(shares)
    assert 0.0 < by_hand < 1.0
    assert float(counted["decode_experts_read_frac"]) == pytest.approx(by_hand, abs=1e-6)
    assert rows[0]["decode_experts_read_frac"] == pytest.approx(by_hand, abs=1e-6)
