"""Data-parallel execution of the fused on-policy train step.

TPU-native replacement for the reference's MirroredStrategy/NCCL
data-parallel path (BASELINE.json:5; SURVEY.md §2.3-2.4 — reference mount
empty, §0). The fused trainer keeps its env batch *inside* `TrainState`,
so data parallelism here means sharding the state itself over the mesh:

    params / opt_state / update_step / avg_return  → replicated  (P())
    rollout (env states + obs), ep_return/length   → sharded     (P("dp"))
    key                                            → per-device  (P("dp"))

Each device then runs the whole fused program (rollout → GAE → grads) on
its shard of envs with its own PRNG stream; the single cross-device
communication is the gradient/metric `pmean` the trainer already does
over `axis_name` — which XLA lowers to an ICI all-reduce, exactly the
role NCCL plays in the reference.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from actor_critic_tpu.algos.common import TrainState
from actor_critic_tpu.parallel.mesh import DP_AXIS


def train_state_specs() -> TrainState:
    """Prefix-tree of PartitionSpecs for TrainState under dp sharding."""
    return TrainState(
        params=P(),
        opt_state=P(),
        rollout=P(DP_AXIS),
        key=P(DP_AXIS),
        update_step=P(),
        ep_return=P(DP_AXIS),
        ep_length=P(DP_AXIS),
        avg_return=P(),
    )


def impala_state_specs():
    """PartitionSpecs for the IMPALA trainer state: same dp layout, with
    the stale actor params replicated alongside the learner params."""
    from actor_critic_tpu.algos.impala import ImpalaTrainState

    return ImpalaTrainState(
        params=P(),
        actor_params=P(),
        opt_state=P(),
        rollout=P(DP_AXIS),
        key=P(DP_AXIS),
        update_step=P(),
        ep_return=P(DP_AXIS),
        ep_length=P(DP_AXIS),
        avg_return=P(),
    )


def replay_specs():
    """PartitionSpecs for the HBM replay ring under dp (BASELINE.json:5
    'replay buffer lives in HBM as a sharded DeviceArray'): the storage's
    leading (capacity) axis is split over dp, so each device owns an
    independent sub-ring of capacity/ndev transitions fed by its own env
    shard and read by its own sampler — no collectives touch the ring.
    The cursor scalars stay replicated: every device inserts the same
    (static) batch size against the same local capacity each step, so
    their values evolve identically on all devices. The quantizer's
    running stats (ReplayState.quant, replay/quantize.py) are replicated
    too — unlike the cursors their inputs DIFFER per device (each shard
    sees its own envs), so `replay.add_batch(..., axis_name=dp)`
    pmean/pmax-syncs the batch moments, the one (tiny, item-shaped)
    collective the quantized ring adds."""
    from actor_critic_tpu.replay.buffer import ReplayState

    return ReplayState(storage=P(DP_AXIS), insert_pos=P(), size=P(), quant=P())


def offpolicy_state_specs():
    """PartitionSpecs for the DDPG/TD3 fused-trainer state under dp.

    Layout: params/targets/optimizers replicated (grads pmean per update,
    like the on-policy path); replay sharded per `replay_specs`; env batch
    and episode accounting sharded; the learner PRNG key per-device (one
    independent sampling/noise stream each). `env_steps` counts LOCAL
    per-device steps, so `warmup_steps` gates each device by its own
    collection count. Effective update batch = ndev × cfg.batch_size
    (each device samples its sub-ring; gradients are pmean-ed).
    """
    from actor_critic_tpu.algos.ddpg import LearnerState, OffPolicyState

    learner = LearnerState(
        actor_params=P(),
        critic_params=P(),
        target_actor=P(),
        target_critic=P(),
        actor_opt=P(),
        critic_opt=P(),
        replay=replay_specs(),
        key=P(DP_AXIS),
        update_count=P(),
    )
    return OffPolicyState(
        learner=learner,
        rollout=P(DP_AXIS),
        env_steps=P(),
        update_step=P(),
        ep_return=P(DP_AXIS),
        ep_length=P(DP_AXIS),
        avg_return=P(),
    )


def sac_state_specs():
    """PartitionSpecs for the SAC fused-trainer state under dp (same
    layout rationale as `offpolicy_state_specs`; log-α and its optimizer
    are replicated scalars)."""
    from actor_critic_tpu.algos.sac import SACLearnerState, SACState

    learner = SACLearnerState(
        actor_params=P(),
        critic_params=P(),
        target_critic=P(),
        actor_opt=P(),
        critic_opt=P(),
        log_alpha=P(),
        alpha_opt=P(),
        replay=replay_specs(),
        key=P(DP_AXIS),
        update_count=P(),
    )
    return SACState(
        learner=learner,
        rollout=P(DP_AXIS),
        env_steps=P(),
        update_step=P(),
        ep_return=P(DP_AXIS),
        ep_length=P(DP_AXIS),
        avg_return=P(),
    )


# Key accessors: the on-policy states carry their PRNG key at the top
# level; the off-policy states carry it inside `.learner`. distribute_state
# and make_dp_train_step use these to split/unwrap the per-device streams.

def _get_key(state):
    return state.learner.key if hasattr(state, "learner") else state.key


def _set_key(state, key):
    if hasattr(state, "learner"):
        return state._replace(learner=state.learner._replace(key=key))
    return state._replace(key=key)


def distribute_state(state, mesh: Mesh, specs=None):
    """Place a host-built trainer state onto the mesh.

    The scalar PRNG key (top-level or `.learner.key`) becomes a [ndev]
    batch (one independent stream per device); leaves under a P("dp")
    spec are sharded on their leading axis (which must divide by the dp
    size — env batch, replay capacity); everything else is replicated.
    `specs` defaults to the on-policy TrainState layout; pass
    `impala_state_specs()` / `offpolicy_state_specs()` /
    `sac_state_specs()` (or any matching prefix-tree of PartitionSpecs)
    for other state shapes.
    """
    ndev = mesh.shape[DP_AXIS]
    state = _set_key(state, jax.random.split(_get_key(state), ndev))
    if specs is None:
        specs = train_state_specs()

    def check_divisible(spec, subtree):
        if spec == P(DP_AXIS):
            for leaf in jax.tree.leaves(subtree):
                if leaf.shape[0] % ndev != 0:
                    raise ValueError(
                        f"dp-sharded leading axis {leaf.shape[0]} not "
                        f"divisible by dp={ndev} (num_envs and replay "
                        "capacity must divide the mesh size)"
                    )
        return spec

    jax.tree.map(
        check_divisible, specs, state, is_leaf=lambda x: isinstance(x, P)
    )

    def expand(spec, subtree):
        return jax.tree.map(lambda _: NamedSharding(mesh, spec), subtree)

    shardings = jax.tree.map(
        expand, specs, state, is_leaf=lambda x: isinstance(x, P)
    )
    return jax.tree.map(jax.device_put, state, shardings)


def make_dp_train_step(
    train_step: Callable[[TrainState], tuple[TrainState, dict]],
    mesh: Mesh,
    specs=None,
) -> Callable[[TrainState], tuple[TrainState, dict]]:
    """shard_map + jit the fused train step over the dp axis (built once).

    `train_step` must be built with `axis_name=DP_AXIS` so its gradient
    pmean becomes the cross-device all-reduce. The per-device view of
    the PRNG key (top-level or `.learner.key`) is a [1] slice of the
    [ndev] key batch; the wrapper unwraps it. `specs` defaults to the
    on-policy TrainState layout.
    """
    if specs is None:
        specs = train_state_specs()

    def local_step(state):
        state = _set_key(state, _get_key(state)[0])
        new_state, metrics = train_step(state)
        return _set_key(new_state, _get_key(new_state)[None]), metrics

    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(specs,),
        out_specs=(specs, P()),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=0)
