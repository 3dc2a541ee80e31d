"""``repro.serve`` — the public serving API.

The documented surface is deliberately small:

* :class:`Request` — one request: ``Request(prompt, max_new_tokens,
  tenant=, priority=, slo_ttft_ms=, tag=)`` (multi-tenant descriptors are
  keyword-only; everything after them in the dataclass is scheduler-owned
  runtime state).
* :class:`ServingEngine` — the continuous-batching engine; its contract is
  ``submit()`` / ``run()`` / ``summary()``.
* :func:`make_draft_source` — speculative-decoding draft factory
  (:class:`NGramDraft` / :class:`ModelDraft` are its products; construct
  through the factory unless a test needs one directly).
* ``random_stream`` / ``make_trace`` / ``parse_mix`` / ``per_class_report``
  / ``WORKLOADS`` — synthetic streams and multi-tenant trace workloads.
* ``greedy_generate`` and the eager ``make_prefill_step`` /
  ``make_decode_step`` — the whole-batch fallback path (also the parity
  oracle).
* :class:`FaultInjector` + the fault-tolerance error types
  (:class:`TransientDeviceError`, :class:`StallError`,
  :class:`LadderExhausted`) — the chaos harness and the exceptions the
  hardened engine raises (see ``docs/ROBUSTNESS.md``).
* :class:`Observability` (re-exported from :mod:`repro.obs`) — the
  metrics + tracing bundle the engine accepts via
  ``ServingEngine(..., obs=)`` (see ``docs/OBSERVABILITY.md``).

Everything else (``Scheduler``, ``BlockAllocator``, ``PrefixIndex``,
``make_mixed_step``, the slab-packing helpers) is engine internals:
importable from their modules for tests and extensions, but not part of the
stable seam — PR 7+ should build on the names in ``__all__``.
"""

from repro.obs import Observability
from repro.serve.engine import (
    ServingEngine,
    greedy_generate,
    make_decode_step,
    make_prefill_step,
)
from repro.serve.faults import (
    FaultInjector,
    LadderExhausted,
    StallError,
    TransientDeviceError,
)
from repro.serve.scheduler import Request, random_stream
from repro.serve.speculative import make_draft_source
from repro.serve.workload import (
    WORKLOADS,
    WorkloadClass,
    make_trace,
    parse_mix,
    per_class_report,
)

__all__ = [
    # engine
    "ServingEngine",
    "Request",
    # fault tolerance / chaos harness
    "FaultInjector",
    "TransientDeviceError",
    "StallError",
    "LadderExhausted",
    # observability bundle (repro.obs)
    "Observability",
    # draft sources
    "make_draft_source",
    # streams / workloads
    "random_stream",
    "WORKLOADS",
    "WorkloadClass",
    "make_trace",
    "parse_mix",
    "per_class_report",
    # eager fallback + oracle
    "greedy_generate",
    "make_prefill_step",
    "make_decode_step",
]
