"""Test configuration: run the suite on a fake 8-device CPU mesh.

Per SURVEY.md §4 ("Distributed tests without a cluster"): tests validate
sharding/collective semantics with
`--xla_force_host_platform_device_count=8` CPU devices. The flag is read
when the backend is created, which is lazy, so setting it here (conftest
import time, before the first computation) takes effect. The platform is
pinned through `jax.config` as well as by the tier-1 command's
`JAX_PLATFORMS=cpu`, so a bare `pytest` on a machine with a chip still
runs the suite on the CPU mesh.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def new_compile_records(c0: int) -> list:
    """Compile records since event-count snapshot `c0`
    (`profiler.compile_event_count()`)."""
    from actor_critic_tpu.telemetry import profiler

    return profiler.compile_records_since(c0)
