"""Serving plane: continuous-batching inference for the transformer LM.

The training side of the repo negotiates gradients; this package serves
the same model under live traffic (docs/serving.md). The pieces:

  * queue.py     — admission control: bounded queue, deadline/SLO tags,
                   loud rejection (never silent backpressure)
  * kv_cache.py  — slot-based KV cache: dense device arrays, host-side
                   block-granular accounting with leak invariants
  * scheduler.py — slot assignment: continuous (join/retire at any
                   step) vs drain (static batch — the baseline)
  * sampling.py  — greedy / temperature sampling, jit-safe per-row mix
  * decode.py    — prefill + single-token decode forwards that apply
                   the training checkpoint's param leaves exactly
  * engine.py    — the step loop tying it together + SLO metrics
  * replica.py   — replica-group liveness on the negotiation
                   control plane (bounded-time loss detection)
  * tracing.py   — request-path spans + latency decomposition: every
                   request is one trace (queue_wait/prefill/decode/
                   requeue/scheduler_stall ms), feeding the flight
                   recorder, hvd_serve_phase_seconds, and the
                   tools/hvd_slo.py tail analyzer

Import surface is lazy-free and light: importing the package pulls jax
only when the engine/decode modules are touched.
"""

from .queue import AdmissionQueue, Request, RequestResult
from .scheduler import SlotScheduler
from .kv_cache import BlockLedger
from .tracing import RequestTrace

__all__ = [
    "AdmissionQueue", "Request", "RequestResult", "SlotScheduler",
    "BlockLedger", "RequestTrace", "ServeEngine", "ReplicaGroup",
]


def __getattr__(name):
    # jax-heavy modules load on first touch, keeping queue/scheduler
    # tests and hvdlint import-cheap
    if name == "ServeEngine":
        from .engine import ServeEngine
        return ServeEngine
    if name == "ReplicaGroup":
        from .replica import ReplicaGroup
        return ReplicaGroup
    raise AttributeError(name)
