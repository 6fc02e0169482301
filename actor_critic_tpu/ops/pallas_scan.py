"""Pallas TPU kernels for the hot trajectory scans (GAE, λ-returns, V-trace).

The fused trainers spend their non-matmul time in `lax.scan(reverse=True)`
over T with tiny per-step VPU work (ops/returns.py). These kernels run the
ENTIRE reverse time loop inside one Pallas program instead: the [T, E]
inputs for a block of environments sit in VMEM, the sequential recurrence
walks T in-kernel, and the env batch is tiled across the 128-lane axis —
one kernel launch, three input streams read once, two outputs written
once, no per-step XLA loop overhead (pallas_guide.md: Grid/BlockSpec,
Control Flow).

Env batches that are not a multiple of the 128-lane Mosaic tile are
zero-padded on the env axis before the launch and sliced back after: each
env column is an independent recurrence, so padded lanes compute junk that
is finite (all-zero inputs) and discarded. Only a T too long for any
VMEM-resident tile still falls back to lax.scan.

Numerics match `ops.returns.gae` / `ops.returns.vtrace` exactly (same
recurrences, f32 accumulation; golden-tested in tests/test_pallas_scan.py
via interpret mode on CPU and compiled on TPU).

Autodiff note: these are forward-only kernels. All trainers compute
advantage targets from rollout-time values with no gradient flowing
through the scan, so no custom VJP is defined; differentiating through
them raises, which is the desired loud failure.

Reference parity: the reference computes GAE on host NumPy per rollout
(SURVEY.md §3.1 [RECON]; reference mount empty at survey, §0) — there is
nothing to cite; this is the TPU-native replacement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from actor_critic_tpu.ops import returns as _returns

# Preferred lane-axis tile for the env batch (4 VPU lane groups per row
# op); `_pick_block` shrinks it whenever T × tile would blow the VMEM
# budget, and extreme T falls back to the lax.scan implementation.
_DEFAULT_BLOCK_E = 512


@functools.cache
def on_tpu() -> bool:
    """Whether the default device is a TPU, decided once per process from
    the device itself (`jax.devices()[0].platform`). On a TPU the kernels
    always compile through Mosaic — no route reaches `interpret=True`.
    Off a TPU the `*_auto` entry points take `lax.scan`; only a direct
    call to `gae` / `lambda_returns` / `vtrace` (tests, padsan) runs the
    Pallas interpreter, which has the kernels' exact semantics."""
    return jax.devices()[0].platform == "tpu"


# Stay well under the ~16 MB/core VMEM so inputs, outputs, and carries
# coexist with pipeline double-buffering.
_VMEM_BUDGET_BYTES = 10 * 2**20


# Live (T, be) f32 blocks per op: inputs + outputs + carries.
# "lambda" rides the GAE kernel (same streams; the advantage output is
# simply discarded), so it prices identically.
_N_ARRAYS = {"gae": 7, "lambda": 7, "vtrace": 11}


def kernel_block(op: str, T: int, E: int, block_envs: int = _DEFAULT_BLOCK_E) -> int:
    """The env-lane tile the `op` ("gae" | "lambda" | "vtrace") kernel
    would use on a [T, E] f32 batch — 0 means the call would silently fall
    back to the lax.scan reference (T too long for any VMEM-resident tile;
    ragged/small E no longer falls back, it is lane-padded to the next
    128 multiple first). Public so benches and tests can ASSERT the kernel
    actually engages before attributing a measurement to it."""
    return _pick_block(E, block_envs, T, _N_ARRAYS[op])


def _pad_env(E: int) -> int:
    """E rounded up to the 128-lane f32 Mosaic tile the kernels run on."""
    return max(-(-E // 128) * 128, 128)


def _pick_block(E: int, block_e: int, T: int, n_arrays: int) -> int:
    """Env-lane tile that (a) divides the LANE-PADDED env batch (`_pad_env`
    — ragged E is zero-padded before launch, so the tile never sees a
    partial block), (b) is a multiple of the 128-lane f32 Mosaic tile, and
    (c) keeps n_arrays live (T, be) f32 blocks inside the VMEM budget.
    Returns 0 if no such tile exists (caller falls back to lax.scan)."""
    Ep = _pad_env(E)
    max_be = _VMEM_BUDGET_BYTES // (max(T, 1) * 4 * n_arrays)
    b = (min(block_e, Ep, max(max_be, 0)) // 128) * 128
    while b >= 128 and Ep % b:
        b -= 128
    return b if b >= 128 else 0


def _pad_lanes(Ep: int, *arrays: jax.Array) -> list[jax.Array]:
    """Zero-pad the trailing env axis of each [T, E] / [1, E] array to Ep
    lanes. Zeros are safe: every kernel recurrence is independent per env
    column, and all-zero inputs produce finite (all-zero or rho=1) junk in
    the padded lanes, which the caller slices away."""
    out = []
    for a in arrays:
        pad = Ep - a.shape[-1]
        out.append(jnp.pad(a, ((0, 0), (0, pad))) if pad else a)
    return out


def _gae_kernel(gamma, lam, r_ref, v_ref, d_ref, b_ref, adv_ref, ret_ref):
    T = r_ref.shape[0]

    def body(i, carry):
        adv, v_next = carry
        t = T - 1 - i
        r = r_ref[pl.ds(t, 1), :]
        v = v_ref[pl.ds(t, 1), :]
        nonterm = 1.0 - d_ref[pl.ds(t, 1), :]
        delta = r + gamma * v_next * nonterm - v
        adv = delta + gamma * lam * nonterm * adv
        adv_ref[pl.ds(t, 1), :] = adv
        ret_ref[pl.ds(t, 1), :] = adv + v
        return adv, v

    boot = b_ref[:]
    jax.lax.fori_loop(0, T, body, (jnp.zeros_like(boot), boot))


def gae(
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    bootstrap_value: jax.Array,
    gamma: float,
    lam: float,
    *,
    block_envs: int = _DEFAULT_BLOCK_E,
    name: str = "gae",
) -> tuple[jax.Array, jax.Array]:
    """Drop-in for `ops.returns.gae` on [T, E] f32 batches via one Pallas
    kernel; any other shape/dtype falls back to the lax.scan version.
    `name` is the kernel's name in the HLO text and in a profiler trace
    (`lambda_returns` passes its own)."""
    if rewards.ndim != 2 or rewards.dtype != jnp.float32:
        return _returns.gae(rewards, values, dones, bootstrap_value, gamma, lam)
    T, E = rewards.shape
    be = _pick_block(E, block_envs, T, _N_ARRAYS["gae"])  # 3 in + 2 out + 2 carry
    if be == 0:  # T too long for any VMEM-resident tile
        return _returns.gae(rewards, values, dones, bootstrap_value, gamma, lam)
    Ep = _pad_env(E)
    rewards, values, dones, boot = _pad_lanes(
        Ep,
        rewards,
        values,
        dones.astype(jnp.float32),
        bootstrap_value.reshape(1, E),
    )

    kernel = functools.partial(_gae_kernel, float(gamma), float(lam))
    row = lambda i: (0, i)  # block i owns rows [0,T), env cols [i*be,(i+1)*be)
    adv, ret = pl.pallas_call(
        kernel,
        grid=(Ep // be,),
        in_specs=[
            pl.BlockSpec((T, be), row, memory_space=pltpu.VMEM),
            pl.BlockSpec((T, be), row, memory_space=pltpu.VMEM),
            pl.BlockSpec((T, be), row, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, be), row, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((T, be), row, memory_space=pltpu.VMEM),
            pl.BlockSpec((T, be), row, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, Ep), jnp.float32),
            jax.ShapeDtypeStruct((T, Ep), jnp.float32),
        ],
        interpret=not on_tpu(),
        name=name,
    )(rewards, values, dones, boot)
    return (adv[:, :E], ret[:, :E]) if Ep != E else (adv, ret)


def lambda_returns(
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    bootstrap_value: jax.Array,
    gamma: float,
    lam: float,
    *,
    block_envs: int = _DEFAULT_BLOCK_E,
) -> jax.Array:
    """Drop-in for `ops.returns.lambda_returns` via the GAE kernel — the
    λ-return IS the GAE return plane (adv + V), so the same fused program
    serves both; the advantage output is discarded."""
    if rewards.ndim != 2 or rewards.dtype != jnp.float32:
        return _returns.lambda_returns(
            rewards, values, dones, bootstrap_value, gamma, lam
        )
    return gae(
        rewards, values, dones, bootstrap_value, gamma, lam,
        block_envs=block_envs, name="lambda_returns",
    )[1]


def gae_auto(*args, **kwargs):
    """`gae` via the Pallas kernel on real TPU backends, via `lax.scan`
    everywhere else (interpret mode is only for tests/debugging — a
    Python-interpreted time loop inside a training loop would crawl).
    The trainers import this as their single GAE entry point.

    Advantage/return targets are gradient-CONSTANTS in every consumer
    (the losses stop_gradient them at use), so inputs are detached here;
    that also keeps JAX from attempting to linearize through the
    forward-only kernel when an input (e.g. truncation-bootstrapped
    rewards) happens to carry a gradient path."""
    if not on_tpu():
        return _returns.gae(*args, **kwargs)
    return gae(*map(_detach, args), **kwargs)


def lambda_returns_auto(*args, **kwargs):
    """`lambda_returns` with the same backend dispatch (and input detach
    rationale) as `gae_auto`."""
    if not on_tpu():
        return _returns.lambda_returns(*args, **kwargs)
    return lambda_returns(*map(_detach, args), **kwargs)


def vtrace_auto(*args, **kwargs):
    """`vtrace` with the same backend dispatch (and input detach
    rationale) as `gae_auto`."""
    if not on_tpu():
        return _returns.vtrace(*args, **kwargs)
    return vtrace(*map(_detach, args), **kwargs)


def _detach(x):
    # Arrays/tracers only — scalar hyperparameters stay Python floats so
    # the kernels can bake them in as compile-time constants.
    return jax.lax.stop_gradient(x) if isinstance(x, (jax.Array, jnp.ndarray)) else x


def _vtrace_kernel(
    gamma, rho_bar, c_bar, lam,
    tlp_ref, blp_ref, r_ref, v_ref, d_ref, b_ref,
    vs_ref, pg_ref, rho_ref,
):
    T = tlp_ref.shape[0]

    def body(i, carry):
        acc, v_next, vs_next = carry
        t = T - 1 - i
        # Same LOG_RATIO_CAP as the lax reference — the kernel/fallback
        # parity contract requires the capped ratio on both sides.
        raw_rho = jnp.exp(jnp.minimum(
            tlp_ref[pl.ds(t, 1), :] - blp_ref[pl.ds(t, 1), :],
            _returns.LOG_RATIO_CAP,
        ))
        rho = jnp.minimum(rho_bar, raw_rho)
        # c clips the RAW ratio (independent of rho_bar) — matters when
        # c_bar > rho_bar (golden: ops/returns.vtrace).
        c = lam * jnp.minimum(c_bar, raw_rho)
        r = r_ref[pl.ds(t, 1), :]
        v = v_ref[pl.ds(t, 1), :]
        disc = gamma * (1.0 - d_ref[pl.ds(t, 1), :])
        delta = rho * (r + disc * v_next - v)
        acc = delta + disc * c * acc
        vs = acc + v
        vs_ref[pl.ds(t, 1), :] = vs
        pg_ref[pl.ds(t, 1), :] = rho * (r + disc * vs_next - v)
        rho_ref[pl.ds(t, 1), :] = rho
        return acc, v, vs

    boot = b_ref[:]
    jax.lax.fori_loop(0, T, body, (jnp.zeros_like(boot), boot, boot))


def vtrace(
    target_log_probs: jax.Array,
    behaviour_log_probs: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    bootstrap_value: jax.Array,
    gamma: float,
    rho_bar: float = 1.0,
    c_bar: float = 1.0,
    lam: float = 1.0,
    *,
    block_envs: int = _DEFAULT_BLOCK_E,
) -> _returns.VTraceOutput:
    """Drop-in for `ops.returns.vtrace` on [T, E] f32 batches via one
    Pallas kernel; other shapes/dtypes fall back to the lax.scan version."""
    if rewards.ndim != 2 or rewards.dtype != jnp.float32:
        return _returns.vtrace(
            target_log_probs, behaviour_log_probs, rewards, values, dones,
            bootstrap_value, gamma, rho_bar, c_bar, lam,
        )
    T, E = rewards.shape
    be = _pick_block(E, block_envs, T, _N_ARRAYS["vtrace"])  # 5 in + 3 out + 3 carry
    if be == 0:  # T too long for any VMEM-resident tile
        return _returns.vtrace(
            target_log_probs, behaviour_log_probs, rewards, values, dones,
            bootstrap_value, gamma, rho_bar, c_bar, lam,
        )
    Ep = _pad_env(E)
    tlp, blp, rewards, values, dones, boot = _pad_lanes(
        Ep,
        target_log_probs,
        behaviour_log_probs,
        rewards,
        values,
        dones.astype(jnp.float32),
        bootstrap_value.reshape(1, E),
    )

    kernel = functools.partial(
        _vtrace_kernel, float(gamma), float(rho_bar), float(c_bar), float(lam)
    )
    row = lambda i: (0, i)
    spec = pl.BlockSpec((T, be), row, memory_space=pltpu.VMEM)
    vs, pg, rho = pl.pallas_call(
        kernel,
        grid=(Ep // be,),
        in_specs=[spec] * 5 + [pl.BlockSpec((1, be), row, memory_space=pltpu.VMEM)],
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((T, Ep), jnp.float32)] * 3,
        interpret=not on_tpu(),
        name="vtrace",
    )(tlp, blp, rewards, values, dones, boot)
    if Ep != E:
        vs, pg, rho = vs[:, :E], pg[:, :E], rho[:, :E]
    return _returns.VTraceOutput(vs=vs, pg_advantages=pg, clipped_rhos=rho)
