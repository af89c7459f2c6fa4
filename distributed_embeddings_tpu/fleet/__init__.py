"""Serving fleet tier (ISSUE 16): consistent-hash routing, admission
control, elastic membership, and canaried version rollout over the
existing one-replica serving stack (`InferenceEngine` + `MicroBatcher`
+ the publish stream).

The soak's replica "fleet" was N independent engines polled from a
callback; this package is the traffic tier that composes the parts the
ROADMAP's "millions of users" claims need: a `FleetRouter` front-end
(stable key -> replica affinity so HBM caches warm per key subset),
typed load shedding driven by the batcher's queue instruments, replicas
that join/leave at runtime with bounded key movement, and published
versions that serve fleet-wide only after canaries report bit-exact
parity against the publisher — with automatic rollback to the pinned
version when one lands degraded. Semantics in docs/serving.md
"Fleet tier".
"""

from distributed_embeddings_tpu.fleet.admission import (AdmissionController,
                                                        RouteResult)
from distributed_embeddings_tpu.fleet.ring import HashRing, stable_hash64
from distributed_embeddings_tpu.fleet.rollout import CanaryController
from distributed_embeddings_tpu.fleet.router import FleetRouter

__all__ = [
    "AdmissionController",
    "CanaryController",
    "FleetRouter",
    "HashRing",
    "RouteResult",
    "stable_hash64",
]
