"""Device mesh + collectives — the distributed communication backend.

TPU-native replacement for the reference's tf.distribute
MirroredStrategy/NCCL gradient-all-reduce path and its Python-queue
actor↔learner transport (BASELINE.json:5,11; SURVEY.md §2.4 — reference
mount empty at survey, §0). Instead of wrapping a transport library, the
framework expresses parallelism as shardings over a `jax.sharding.Mesh`
and lets XLA insert collectives that ride ICI (intra-slice) or DCN
(multi-host, via `jax.distributed.initialize`).

Axes convention (SURVEY.md §2.3):
- "dp": data parallel — env batch and minibatches sharded; gradients
  `psum`-ed. The only axis the RL workloads need.
- "model": reserved stub for tensor parallelism (unused by these model
  sizes; kept so the mesh API doesn't change if TP is ever added).

All trainers are written against `axis_name=...` pmean/psum helpers that
degrade to no-ops off-mesh, so the same train-step code runs single-chip
and under `shard_map`.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DP_AXIS = "dp"
MODEL_AXIS = "model"

@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """How to lay the process's devices out as a mesh."""

    dp: int = -1  # -1 → all remaining devices
    model: int = 1


def make_mesh(cfg: MeshConfig = MeshConfig(), devices=None) -> Mesh:
    devices = jax.devices() if devices is None else devices
    n = len(devices)
    model = cfg.model
    dp = n // model if cfg.dp == -1 else cfg.dp
    if dp * model != n:
        raise ValueError(f"mesh {dp}x{model} != {n} devices")
    return jax.make_mesh((dp, model), (DP_AXIS, MODEL_AXIS), devices=devices)


def multihost_init(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host (DCN) initialization (SURVEY §5.8).

    Must be called before anything initializes the XLA backend (JAX's
    `distributed.initialize` raises otherwise), so the single-process
    check CANNOT use `jax.process_count()` — that call would itself
    initialize the backend. Instead we let JAX's own cluster
    auto-detection (SLURM, Open MPI, Cloud TPU pod metadata,
    JAX_COORDINATOR_ADDRESS, ...) decide: if it finds no cluster, its
    error is swallowed and the process runs single-host.

    With an explicit `coordinator` the init is NOT optional — failures
    propagate. Outside auto-detectable clusters (e.g. a hand-rolled
    launcher, or the two-process localhost exercise in
    tests/test_multihost.py) pass `num_processes`/`process_id` too;
    inside one, JAX infers them.
    """
    if coordinator is not None:
        kwargs = {}
        if num_processes is not None:
            kwargs["num_processes"] = num_processes
        if process_id is not None:
            kwargs["process_id"] = process_id
        jax.distributed.initialize(coordinator_address=coordinator, **kwargs)
        return
    try:
        jax.distributed.initialize()
    except RuntimeError:
        # Backend already initialized — a real misuse worth surfacing.
        raise
    except Exception as e:
        # No recognizable cluster environment: single-process no-op. The
        # exception is logged because a *detected-but-misconfigured*
        # cluster (malformed SLURM/pod env vars) lands here too, and
        # silently running N independent single-host trainings would be
        # much worse than a startup crash.
        import logging

        logging.getLogger(__name__).warning(
            "jax.distributed.initialize() failed (%s: %s); continuing "
            "single-host. If this job was meant to be multi-host, fix the "
            "cluster env or pass coordinator= explicitly.", type(e).__name__, e,
        )


# --- collective helpers: no-op when axis_name is None ---------------------
# axis_name may also be a TUPLE of mesh-axis names (lax.pmean/psum reduce
# over all of them in one collective — the sp×dp learner update uses this).

AxisName = Optional["str | tuple[str, ...]"]


def pmean(x, axis_name: AxisName):
    if axis_name is None:
        return x
    return jax.lax.pmean(x, axis_name)


def psum(x, axis_name: AxisName):
    if axis_name is None:
        return x
    return jax.lax.psum(x, axis_name)


def pmean_tree(tree, axis_name: AxisName):
    if axis_name is None:
        return tree
    return jax.tree.map(partial(jax.lax.pmean, axis_name=axis_name), tree)


# --- sharding helpers ------------------------------------------------------

def shard_batch_spec(mesh: Mesh) -> NamedSharding:
    """Sharding for a [B, ...] batch: B split over dp, rest replicated."""
    return NamedSharding(mesh, P(DP_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
