"""Streaming retrieval service: admission, microbatching, resident sessions.

Public surface:

* :class:`~repro.serve.service.RetrievalService` — submit/poll/drain facade
  (``try_submit`` for typed admission outcomes).
* :class:`~repro.serve.session.LexicalSession` /
  :class:`~repro.serve.session.DenseSession` — resident-corpus scan state.
* :class:`~repro.serve.session.ShardedLexicalSession` /
  :class:`~repro.serve.session.ShardedDenseSession` — the same session
  surfaces with the corpus resident *sharded* across a JAX mesh, reducing
  through the `repro.cluster` merge contract.
* :class:`~repro.serve.microbatch.Microbatcher` — deadline/size triggers +
  MXU-bucket padding, capped ladder (importable standalone for tests).
* :class:`~repro.serve.admission.AdmissionController` — bounded queue,
  per-tenant token buckets, QoS lanes; typed Admitted/Shed/Blocked.
* :class:`~repro.serve.policy.AdaptiveBatchPolicy` — the SLO closed loop
  over the microbatch triggers.
* :mod:`repro.serve.loadgen` — open-loop sustained-load generation on a
  virtual clock (Poisson/burst schedules, metered sessions).
* :mod:`repro.serve.bench` — the C1 batch-size/latency sweep.
"""

from repro.serve.admission import (
    Admitted,
    AdmissionController,
    Blocked,
    Shed,
    TokenBucket,
)
from repro.serve.loadgen import (
    MeteredSession,
    OpenLoopResult,
    VirtualClock,
    burst_schedule,
    poisson_schedule,
    run_open_loop,
)
from repro.serve.microbatch import Microbatcher, QueryBlock, SearchRequest
from repro.serve.policy import AdaptiveBatchPolicy
from repro.serve.service import (
    BatchRecord,
    RejectedError,
    RetrievalService,
    SearchResult,
)
from repro.serve.session import (
    DenseSession,
    LexicalSession,
    ShardedDenseSession,
    ShardedLexicalSession,
)

__all__ = [
    "AdaptiveBatchPolicy",
    "Admitted",
    "AdmissionController",
    "BatchRecord",
    "Blocked",
    "DenseSession",
    "LexicalSession",
    "MeteredSession",
    "Microbatcher",
    "OpenLoopResult",
    "QueryBlock",
    "RejectedError",
    "RetrievalService",
    "SearchRequest",
    "SearchResult",
    "ShardedDenseSession",
    "ShardedLexicalSession",
    "Shed",
    "TokenBucket",
    "VirtualClock",
    "burst_schedule",
    "poisson_schedule",
    "run_open_loop",
]
