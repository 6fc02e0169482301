"""Sequence (time-axis) parallelism for trajectory scans.

The reference has no attention, so there is no ring-attention/Ulysses
counterpart to port (SURVEY.md §2.3, §5.7; reference mount empty at
survey, §0). Its long-sequence analogue is the trajectory-return scan:
GAE, discounted returns, and V-trace are all first-order linear
recurrences run in reverse over time,

    y_t = b_t + a_t * y_{t+1},        y_T = y_init.

That structure is exactly what makes a TPU-native *time-sharded*
implementation cheap: split T over a mesh axis "sp", and the recurrence
over a contiguous segment composes into a single affine map

    y_seg_start = B_seg + A_seg * y_next_seg_start,
    A_seg = prod(a_t over segment),  B_seg = local reverse scan @ 0 init,

so the cross-device dependency is one affine chain of length n_devices.
The implementation needs only:

  1. a halo exchange (`ppermute` shift by one along "sp") so each device
     sees the *next* segment's first value — the v_{t+1} lookahead that
     GAE's δ_t and V-trace's deltas require;
  2. a local reverse `lax.scan` (per device, O(T/D));
  3. an `all_gather` of the per-segment (A, B) summaries + a tiny
     replicated scan over the D segments to solve the boundary chain.

Collectives ride ICI; per-device work drops from O(T) to O(T/D). With
D=1 all of it degrades to the plain scans in `ops/returns.py`, which the
tests use as golden references (tests/test_seqpar.py, 8-device CPU mesh
per SURVEY.md §4).

All `seqpar_*` functions are written to be called INSIDE `shard_map`
with the time axis sharded over `axis_name`; `make_seqpar_fn` wraps one
of them into a jitted, mesh-ready callable for [T, ...] global arrays.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from actor_critic_tpu.ops import returns

SP_AXIS = "sp"


def _halo_from_next(x_first, bootstrap, axis_name):
    """Each device receives `x_first` from the device holding the NEXT
    time segment; the last device gets `bootstrap` instead.

    `ppermute` with perm [(i, i-1)] sends device i's value to i-1 and
    leaves unaddressed receivers (the last device) at zero, which the
    `where` on the axis index then replaces.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, i - 1) for i in range(1, n)]
    received = jax.lax.ppermute(x_first, axis_name, perm)
    return jnp.where(idx == n - 1, bootstrap, received)


def _solve_boundary_chain(a_seg, b_seg, y_init, axis_name):
    """Solve y_start_i = b_i + a_i * y_start_{i+1} over the device axis and
    return this device's INCOMING boundary y_start_{i+1} (y_init for the
    last device).

    The per-segment summaries are [batch...]-shaped; with D devices the
    gathered chain is [D, batch...] — tiny — so every device solves the
    whole chain redundantly (replicated compute beats a sequential
    D-step ppermute pipeline at these sizes, and XLA dedupes it).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    a_all = jax.lax.all_gather(a_seg, axis_name)  # [D, ...] in time order
    b_all = jax.lax.all_gather(b_seg, axis_name)

    def step(y_next, ab):
        a, b = ab
        y = b + a * y_next
        return y, y_next  # emit the INCOMING boundary for this segment

    _, y_in_all = jax.lax.scan(step, y_init, (a_all, b_all), reverse=True)
    return jnp.take(y_in_all, idx, axis=0)


def _local_affine_scan(a, b):
    """Reverse scan of y_t = b_t + a_t*y_{t+1} with y=0 past the segment,
    plus the suffix products P_t = prod_{s>=t} a_s. Returns (B_t, P_t)
    so the true solution is y_t = B_t + P_t * y_boundary_in."""

    def step(carry, ab):
        y, p = carry
        a_t, b_t = ab
        y = b_t + a_t * y
        p = a_t * p
        return (y, p), (y, p)

    ones = jnp.ones_like(b[0])
    (_, _), (B, Pr) = jax.lax.scan(
        step, (jnp.zeros_like(b[0]), ones), (a, b), reverse=True
    )
    return B, Pr


def seqpar_discounted_returns(rewards, dones, bootstrap_value, gamma, *, axis_name):
    """Time-sharded Monte-Carlo returns; matches
    `ops.returns.discounted_returns` on the gathered result."""
    a = gamma * (1.0 - dones.astype(rewards.dtype))
    B, Pr = _local_affine_scan(a, rewards)
    y_in = _solve_boundary_chain(Pr[0], B[0], bootstrap_value, axis_name)
    return B + Pr * y_in


def seqpar_gae(
    rewards, values, dones, bootstrap_value, gamma, lam, *, axis_name
):
    """Time-sharded GAE; matches `ops.returns.gae` on the gathered result.

    δ_t needs V(s_{t+1}) across the segment boundary → one halo exchange
    of each segment's first value.
    """
    dones = dones.astype(rewards.dtype)
    v_halo = _halo_from_next(values[0], bootstrap_value, axis_name)
    values_tp1 = jnp.concatenate([values[1:], v_halo[None]], axis=0)
    nonterm = 1.0 - dones
    deltas = rewards + gamma * values_tp1 * nonterm - values
    a = gamma * lam * nonterm
    B, Pr = _local_affine_scan(a, deltas)
    adv_in = _solve_boundary_chain(Pr[0], B[0], jnp.zeros_like(bootstrap_value), axis_name)
    advantages = B + Pr * adv_in
    return advantages, advantages + values


def seqpar_vtrace(
    target_log_probs,
    behaviour_log_probs,
    rewards,
    values,
    dones,
    bootstrap_value,
    gamma,
    rho_bar: float = 1.0,
    c_bar: float = 1.0,
    lam: float = 1.0,
    *,
    axis_name,
):
    """Time-sharded V-trace; matches `ops.returns.vtrace` on the gathered
    result. Two boundary dependencies: V(x_{t+1}) for the deltas (halo of
    `values`) and vs_{t+1} for the pg advantages (the solved boundary
    itself, since vs_next_first = y_in + v_halo)."""
    dones = dones.astype(rewards.dtype)
    discounts = gamma * (1.0 - dones)
    # Same LOG_RATIO_CAP as ops.returns.vtrace — the gathered-equality
    # contract requires the capped ratio on both sides.
    rhos = jnp.exp(
        jnp.minimum(
            target_log_probs - behaviour_log_probs, returns.LOG_RATIO_CAP
        )
    )
    clipped_rhos = jnp.minimum(rho_bar, rhos)
    cs = lam * jnp.minimum(c_bar, rhos)

    v_halo = _halo_from_next(values[0], bootstrap_value, axis_name)
    values_tp1 = jnp.concatenate([values[1:], v_halo[None]], axis=0)
    deltas = clipped_rhos * (rewards + discounts * values_tp1 - values)

    a = discounts * cs
    B, Pr = _local_affine_scan(a, deltas)
    y_in = _solve_boundary_chain(
        Pr[0], B[0], jnp.zeros_like(bootstrap_value), axis_name
    )
    vs_minus_v = B + Pr * y_in
    vs = vs_minus_v + values

    # vs at the next segment's first index; for the last device y_in is the
    # global init (0) and v_halo is the bootstrap, giving exactly bootstrap.
    vs_halo = y_in + v_halo
    vs_tp1 = jnp.concatenate([vs[1:], vs_halo[None]], axis=0)
    pg_advantages = clipped_rhos * (rewards + discounts * vs_tp1 - values)

    from actor_critic_tpu.ops.returns import VTraceOutput

    return VTraceOutput(vs=vs, pg_advantages=pg_advantages, clipped_rhos=clipped_rhos)


def make_sp_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """A 1-D mesh over the time axis (for standalone seq-parallel use;
    inside a larger program, carve "sp" out of the trainer's own mesh)."""
    devices = jax.devices() if devices is None else devices
    if n_devices is not None:
        devices = devices[:n_devices]
    return jax.make_mesh((len(devices),), (SP_AXIS,), devices=devices)


def make_seqpar_fn(fn, mesh: Mesh, n_time_sharded_args: int, axis_name: str = SP_AXIS):
    """Wrap a `seqpar_*` function into a jitted callable on global [T, ...]
    arrays.

    The first `n_time_sharded_args` positional args are sharded over the
    time axis (T must divide by mesh size); remaining positional args
    (bootstrap value, scalars) are replicated. Returns outputs sharded
    the same way, visible to the caller as global [T, ...] arrays.
    """
    time_spec = P(axis_name)
    rep = P()

    def wrapped(*args):
        sharded = args[:n_time_sharded_args]
        rest = args[n_time_sharded_args:]
        in_specs = (time_spec,) * len(sharded) + (rep,) * len(rest)

        shmapped = jax.shard_map(
            partial(fn, axis_name=axis_name),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=time_spec,
            check_vma=False,
        )
        return shmapped(*args)

    return jax.jit(wrapped)
