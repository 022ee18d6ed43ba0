"""Online serving subsystem: admission queue, bucketed dynamic batcher,
and SLO-aware scheduling over the AOT predictor.

The inference predictor (inference/predictor.py) is a single-request
engine: one call, one AOT-compiled executable, one answer. This package
turns it into a service. The design follows the prediction-serving
literature — Clipper's (NSDI'17) dynamic batching behind an admission
front-end and Orca's (OSDI'22) batch-window scheduling — re-based on the
TPU constraint that every served shape must be a pre-compiled bucket:
the batcher only ever forms (batch, seq-len) shapes drawn from a fixed
bucket lattice, so a warmed engine never retraces.

Layers (each its own module, composable and separately testable):

* `request`  — Request/Response futures + the structured serving errors
  (`RejectedError` carries retry-after for backpressure,
  `DeadlineExceededError` for SLO misses, `RequestError` for per-request
  failures that must not fail batchmates).
* `queue`    — `RequestQueue`: bounded-depth admission queue with
  priority lanes and deadline expiry; rejects loudly instead of queueing
  unboundedly.
* `batcher`  — `BucketLattice` (the fixed shape grid + total bucket
  mapping) and `DynamicBatcher` (coalesce queued requests into padded
  lattice batches under a max-wait timer).
* `engine`   — `ServingEngine`: worker loop over one or more Predictor
  replicas; scatter/gather of per-request rows, failure isolation,
  graceful drain, and the `stats()` snapshot.
* `metrics`  — always-on serving counters + latency reservoirs, mirrored
  into profiler.py's event/counter machinery when profiling is enabled.
* `decode`   — the continuous-batching generation subsystem (serving
  v2): iteration-level scheduler over a slotted KV arena, multi-tenant
  model registry, AOT warm start (`GenerationEngine`, `DecodeModel`,
  `build_decoder_model`).
* `fleet`    — the multi-replica tier (serving v3): `FleetRouter` over
  N engine replicas with prefix-affinity routing, health-tracked
  at-most-once-visible re-dispatch, load shedding, autoscaling, and
  rolling deploys (`LocalReplica`, `SubprocessReplica`).
"""

from paddle_tpu.serving.batcher import BucketLattice, DynamicBatcher
from paddle_tpu.serving.decode import (
    DecodeModel,
    GenerationEngine,
    build_afmoe_model,
    build_decoder_model,
    build_granite_hybrid_model,
    build_latent_moe_model,
    build_lfm2_model,
    build_nemotron_h_model,
    build_ouro_model,
    build_keye_vl_model,
    build_sdar_model,
)
from paddle_tpu.serving.engine import ServingEngine
from paddle_tpu.serving.fleet import (
    FleetRouter,
    LocalReplica,
    SubprocessReplica,
)
from paddle_tpu.serving.metrics import ServingMetrics
from paddle_tpu.serving.queue import RequestQueue
from paddle_tpu.serving.request import (
    DeadlineExceededError,
    Priority,
    RejectedError,
    ReplicaLostError,
    Request,
    RequestError,
    Response,
    ServingError,
)

__all__ = [
    "BucketLattice",
    "DeadlineExceededError",
    "DecodeModel",
    "DynamicBatcher",
    "FleetRouter",
    "GenerationEngine",
    "LocalReplica",
    "SubprocessReplica",
    "build_afmoe_model",
    "build_decoder_model",
    "build_nemotron_h_model",
    "build_granite_hybrid_model",
    "build_latent_moe_model",
    "build_lfm2_model",
    "build_ouro_model",
    "build_keye_vl_model",
    "build_sdar_model",
    "Priority",
    "RejectedError",
    "ReplicaLostError",
    "Request",
    "RequestError",
    "RequestQueue",
    "Response",
    "ServingEngine",
    "ServingError",
    "ServingMetrics",
]
