"""flexflow_tpu.serve — continuous-batching inference.

The training half of the framework compiles an op graph into one jitted
SPMD step; this package opens the inference half: a block-paged KV-cache
with refcounted prefix caching (:mod:`kv_cache`), a continuous-batching
scheduler with chunked prefill, watermark admission and preemption
(:mod:`scheduler`), host-side drafting for verified speculative decode
(:mod:`speculative`), and a :class:`ServeEngine` (:mod:`engine`) that
wraps a built LM into ONE fixed-shape mixed prefill+decode step so XLA
compiles a single serving program, ever. :mod:`disagg` splits serving
into dedicated prefill and decode engine roles with a host-side KV
page handoff between them (:class:`DisaggCluster`) — decode steps stop
paying for prefill lanes, the tail-latency win the placement search
prices via ``optimize_serve(..., disaggregated=True)``. :mod:`router`
builds the tier ABOVE one replica: a :class:`ReplicaPool` of N engines
behind a prefix-affinity router with load-aware spill and a
telemetry-driven :class:`Autoscaler`, serving the seeded timed traffic
:mod:`traffic` synthesizes — goodput-under-SLO as a reproducible
number (docs/serving.md "Multi-replica routing").
"""

from .kv_cache import (KVCacheConfig, KVPool, PagedKVCache,
                       prefix_page_keys)
from .scheduler import (ChunkPlan, ContinuousBatchingScheduler,
                        RejectedRequest, Request, RequestOutcome,
                        RequestState, SampleParams, StepPlan)
from .speculative import DraftControl, Drafter, PromptLookupDrafter
from .engine import ServeEngine, ServeSession, StepEvents
from .disagg import (DisaggCluster, PageShipment, engine_for,
                     normalize_on_step)
from .router import Autoscaler, Replica, ReplicaPool
from .traffic import (TrafficRequest, TrafficSpec, make_traffic,
                      rescale_arrivals)
from .transport import (ShipmentReceiver, ShipmentSender,
                        ShipmentWireError, dumps_shipment,
                        loads_shipment)

__all__ = [
    "Autoscaler",
    "Replica",
    "ReplicaPool",
    "ServeSession",
    "StepEvents",
    "TrafficRequest",
    "TrafficSpec",
    "make_traffic",
    "rescale_arrivals",
    "DisaggCluster",
    "PageShipment",
    "engine_for",
    "normalize_on_step",
    "ShipmentReceiver",
    "ShipmentSender",
    "ShipmentWireError",
    "dumps_shipment",
    "loads_shipment",
    "KVCacheConfig",
    "PagedKVCache",
    "prefix_page_keys",
    "ChunkPlan",
    "ContinuousBatchingScheduler",
    "RejectedRequest",
    "Request",
    "RequestOutcome",
    "RequestState",
    "SampleParams",
    "StepPlan",
    "DraftControl",
    "Drafter",
    "PromptLookupDrafter",
    "ServeEngine",
]
