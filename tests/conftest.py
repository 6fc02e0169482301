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
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def new_compile_records(c0: int) -> list:
    """Compile records since event-count snapshot `c0`
    (`profiler.compile_event_count()`)."""
    from actor_critic_tpu.telemetry import profiler

    return profiler.compile_records_since(c0)


@pytest.fixture(scope="module")
def v5e():
    """One chip of a described (not attached) v5e host, for the TPU's own
    compiler; described here, inside a test, and nowhere at import time."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])
