"""Policy-serving gateway (ISSUE 10): the acting path as a production
inference service — GA3C-style micro-batching (arxiv 1611.06256) over
stdlib HTTP, AOT-warm bucket programs, multi-policy hot-swap, serving
metrics on /metrics. `scripts/serve.py` is the CLI.

Importing this package registers the serving warmup planner
(`engine.make_act_program`) — `analysis/warmup.py`'s registry lint
covers `serving/` and validates against it.
"""

from actor_critic_tpu.serving.batcher import (
    DispatcherDown,
    MicroBatcher,
    Overloaded,
    QueueFull,
    ServingMetrics,
)
from actor_critic_tpu.serving.fleet_proxy import (
    FleetProxy,
    MailboxPolicySyncer,
)
from actor_critic_tpu.serving.engine import (
    DEFAULT_BUCKETS,
    PolicyEngine,
    abstract_params,
    init_params,
    make_act_program,
)
from actor_critic_tpu.serving.gateway import ServeGateway, standalone_metrics
from actor_critic_tpu.serving.policy_store import (
    PolicyHandle,
    PolicyStore,
    UnknownPolicy,
    export_policy_params,
    restore_policy_params,
)

__all__ = [
    "DEFAULT_BUCKETS",
    "DispatcherDown",
    "FleetProxy",
    "MailboxPolicySyncer",
    "MicroBatcher",
    "Overloaded",
    "PolicyEngine",
    "PolicyHandle",
    "PolicyStore",
    "QueueFull",
    "ServeGateway",
    "ServingMetrics",
    "UnknownPolicy",
    "abstract_params",
    "export_policy_params",
    "init_params",
    "make_act_program",
    "restore_policy_params",
    "standalone_metrics",
]
