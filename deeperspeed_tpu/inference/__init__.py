"""Serving subsystem: continuous batching, paged KV cache, and the
Pallas paged decode-attention kernel (`docs/inference.md`).

- `InferenceEngine` — the serving loop: bucketed prefill/decode split
  at fixed compiled shapes, params-only checkpoint loading, telemetry.
- `PagedKVCache` — the preallocated, mesh-sharded page pool + its
  host-side refcounting allocator.
- `PrefixCache` — the radix-style prefix registry over the page pool
  (cross-request KV reuse; docs/inference.md "Prefix/radix cache").
- `ContinuousBatchingScheduler` / `Request` — per-step admission and
  eviction under a token + page budget.
- `AdmissionController` + the typed request-terminal errors
  (`RequestRejected` / `DeadlineExceeded` / `RequestFailed` /
  `DrainAborted`) — the SLO-aware robustness layer
  (docs/inference.md "Serving under failure").
- `HandoffChannel` / `HandoffRejected` + `ServeRouter` — disaggregated
  prefill/decode serving: the cross-pool KV-page handoff wire and the
  SLO-aware front-end router (docs/inference.md "Disaggregated
  serving").
"""

import time as _time

_IMPORT_T0 = _time.perf_counter()   # runtime/telemetry.py, `setup_report`

from .admission import (AdmissionController, DeadlineExceeded,
                        DrainAborted, PRIORITIES, RequestFailed,
                        RequestRejected, REQUEST_STATUSES)
from .engine import InferenceEngine
from .handoff import HandoffChannel, HandoffRejected
from .kv_cache import PagedKVCache, PrefixCache, pages_for_tokens
from .router import ServeRouter
from ..runtime.telemetry import note_import as _note_import
from .scheduler import ContinuousBatchingScheduler, Request, StepPlan

__all__ = ["InferenceEngine", "PagedKVCache", "PrefixCache",
           "pages_for_tokens",
           "ContinuousBatchingScheduler", "Request", "StepPlan",
           "AdmissionController", "RequestRejected", "DeadlineExceeded",
           "RequestFailed", "DrainAborted", "PRIORITIES",
           "REQUEST_STATUSES",
           "HandoffChannel", "HandoffRejected", "ServeRouter"]

_note_import(_IMPORT_T0)    # the last line: counted once where nested
