"""Fleet serving: a chaos-proven request router over decode replicas.

Everything below this package serves from one process; this is the tier
ROADMAP item 3 and PAPER.md's L6 layer name — a front-end router over N
``GenerationEngine`` replicas (in-process handles or subprocess workers
over a length-prefixed RPC) that turns replica death from an outage
into a re-dispatch:

* `router`  — ``FleetRouter``: prefix-affinity routing (rendezvous hash
  of the prompt prefix, spill to least-loaded), at-most-once-VISIBLE
  re-dispatch under the caller's original deadline, fleet-wide load
  shedding on the measured drain-rate retry-after, occupancy-driven
  scale-up/down, and rolling ``(model, version)`` deploys with
  drain-before-retire.
* `replica` — ``LocalReplica`` / ``SubprocessReplica``: one transport-
  blind handle surface (submit / poll_many / heartbeat / steal_queued /
  deploy / close); the ``replica.kill`` fault site makes death
  deterministically injectable on both transports.
* `health`  — ``ReplicaHealth``: the PR-2 circuit-breaker contract
  (quarantine after K consecutive failures, cooldown probe re-admission)
  under an explicit DEAD latch for hard failures.
* `worker`  — the subprocess replica entrypoint
  (``python -m paddle_tpu.serving.fleet.worker``).
* `metrics` — ``FleetMetrics``: the acceptance/outcome accounting whose
  identity (accepted == completed + deadline + failed + drained) IS the
  zero-loss gate in ``tools/chaos_serve.py``.

Locking adopts ``lockdep.named_lock`` from day one; the declared
hierarchy is ``fleet.router -> serving.queue -> decode.tenant``
(witnessed live by tests/test_concurrency.py::
test_witnessed_lock_hierarchy_has_no_cycle_and_obeys_declared_chains).
"""

from paddle_tpu.serving.fleet.health import ReplicaHealth
from paddle_tpu.serving.fleet.metrics import FleetMetrics
from paddle_tpu.serving.fleet.replica import (
    LocalReplica,
    ReplicaError,
    SubprocessReplica,
)
from paddle_tpu.serving.fleet.router import FleetRouter, RoutedRequest

__all__ = [
    "FleetMetrics",
    "FleetRouter",
    "LocalReplica",
    "ReplicaError",
    "ReplicaHealth",
    "RoutedRequest",
    "SubprocessReplica",
]
