"""Serving stack: paged KV allocator (§5.3), pure-Python scheduler
(control plane) and jitted executor (data plane) behind the
``ServingEngine`` facade — plus in-jit ``sampling`` (greedy /
temperature / top-k / top-p), speculative-decoding proposers
(``spec``), and the request-lifecycle fault-tolerance layer: typed
``errors``, the invariant ``watchdog``, and the deterministic
``faults`` injection harness.  The asyncio streaming front door
(``frontend``) bridges per-token streams, mid-stream cancellation and
watermark backpressure onto the engine loop; ``tracing`` holds the
spans and counters of that path (off unless enabled)."""

from . import errors
from .engine import ServingEngine
from .errors import (AdmissionRejected, BackpressureRejected,
                     BucketOverflow, DeadlineExceeded, FaultInjected,
                     PoolExhausted, RequestFailed, ServingError)
from .frontend import AsyncFrontend, StreamEvent
from .executor import Executor
from .faults import FaultInjector, FaultSpec
from .kv_cache import PagedKVCache, PagePool
from .legacy import LegacyServingEngine
from .sampling import SamplingParams
from .scheduler import Request, RequestState, Scheduler, StepPlan
from .spec import (DraftModelProposer, FixedProposer, NgramProposer,
                   Proposer)
from .tracing import Tracer
from .watchdog import Violation, Watchdog

__all__ = ["ServingEngine", "LegacyServingEngine", "PagedKVCache",
           "PagePool", "Scheduler", "Executor", "Request", "StepPlan",
           "RequestState", "errors", "ServingError", "AdmissionRejected",
           "BackpressureRejected", "AsyncFrontend", "StreamEvent",
           "PoolExhausted", "BucketOverflow", "DeadlineExceeded",
           "RequestFailed", "FaultInjected", "FaultInjector",
           "FaultSpec", "Watchdog", "Violation", "SamplingParams",
           "Proposer", "NgramProposer", "DraftModelProposer",
           "FixedProposer", "Tracer"]
