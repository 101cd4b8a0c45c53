"""Serving layer: T-REX dynamic batching extended to continuous batching.

Public surface (``__all__``) — everything a serving caller needs:

* ``Engine`` + ``EngineConfig`` — the slot engine and its validated,
  frozen construction config (``Engine(model, params, config=...)``;
  loose legacy kwargs still work behind a deprecation shim).
* ``Request`` + ``SamplingParams`` — one generation request, optionally
  carrying per-request sampling overrides (mixed greedy + sampled
  batches share one jitted step).
* ``Frontend`` — asyncio submit/stream/cancel tier over any steppable
  engine; ``Dispatcher`` — N replicas behind the same steppable
  protocol, joined by a fleet-shared prefix index.
* ``FaultPlan`` — the seeded chaos harness; ``TERMINAL_STATUSES`` — the
  closed set of per-request terminal statuses
  (``ok | rejected | shed | timed_out | failed | cancelled``).

Architecture:

* :mod:`repro.serve.scheduler` — iteration-level admission queue.
  ``Scheduler`` packs short prompts into shared prefill rows (the paper's
  ≤max/2-pairs / ≤max/4-quads policy), chunks long ones instead of
  rejecting them, and emits row-per-request admissions (``pack=False``) for
  recurrent stacks; it absorbed the old ``DynamicBatcher`` (kept as an
  alias).
* :mod:`repro.serve.kv_slots` — ``SlotKVCache`` (a.k.a. ``SlotStateTable``),
  a fixed-capacity table of per-request cache lanes inside one fixed-shape
  model cache. Lanes are kind-aware: full-attention KV, ring-buffered
  windowed KV (canonical ring phase), and fixed-shape recurrent states
  (RG-LRU / SSD). Per-step slot occupancy is the serving analogue of the
  paper's PE utilization.
* :mod:`repro.serve.pages` — ``PagePool``: attention lanes paged into
  ``page_size``-token physical pages behind per-slot int32 block tables,
  so cache *memory* scales with occupancy (the paper's reduced external
  memory access) the way the TDA kernel makes compute scale. The pool's
  ``memory_ratio`` is the footprint counterpart of the blocks-visited
  ratio. ``FleetPrefixIndex`` adds a cross-replica host-memory page tier.
* :mod:`repro.serve.sampling` — in-graph temperature/top-k sampling with
  per-(request, position) PRNG keys; greedy (``temperature=0``) stays the
  bit-identical default. ``SamplingParams`` carries per-request overrides.
* :mod:`repro.serve.config` — ``EngineConfig``: every construction-time
  engine knob in one frozen dataclass, with all model/mesh compatibility
  checks (``UnsupportedConfigError``) in one ``validate``.
* :mod:`repro.serve.engine` — ``Engine``: prefill → lane assign → one
  jitted decode step over all slots per token, with mid-decode admissions,
  per-request stop conditions, page-budget admission and
  preempt-and-requeue when the pool exhausts, for every ``configs/``
  architecture. ``Engine.step()`` exposes the loop one iteration at a
  time (admit → one jitted dispatch → retire) for external drivers.
* :mod:`repro.serve.frontend` — ``Frontend``: asyncio submit / per-token
  ``async for`` streaming / mid-decode cancellation over a steppable
  engine, token-identical to ``Engine.run`` on the same trace.
* :mod:`repro.serve.dispatch` — ``Dispatcher``: deterministic
  least-loaded routing over engine replicas, fleet prefix sharing, and
  merged fleet ``decode_stats``.
* :mod:`repro.serve.faults` — ``FaultPlan`` / ``FaultInjector``: the
  seeded, deterministic chaos harness behind the engine's failure
  hardening (page-allocation failures, forced preemptions, NaN logits,
  artificial stalls). Every request the engine returns carries a terminal
  ``status``; the opt-in ``audit=True`` mode re-checks the pool/CoW
  invariants each step with a structured ``AuditError``.

See ``docs/serving.md`` for the slot-engine lifecycle, the page-table
contract, the serving failure model, and the async front-end / replica
tier.
"""
from repro.core.errors import AuditError, UnsupportedConfigError  # noqa: F401
from repro.serve.config import EngineConfig  # noqa: F401
from repro.serve.dispatch import Dispatcher  # noqa: F401
from repro.serve.engine import Engine, StepResult  # noqa: F401
from repro.serve.faults import FaultInjector, FaultPlan  # noqa: F401
from repro.serve.frontend import Frontend, StreamHandle  # noqa: F401
from repro.serve.kv_slots import SlotKVCache, SlotStateTable  # noqa: F401
from repro.serve.pages import FleetPrefixIndex, PagePool  # noqa: F401
from repro.serve.sampling import SamplingParams, sample_tokens  # noqa: F401
from repro.serve.scheduler import (  # noqa: F401
    TERMINAL_STATUSES,
    Admission,
    DynamicBatcher,
    Request,
    Scheduler,
)

# The supported serving API. Internals (Scheduler, SlotKVCache, PagePool,
# sample_tokens, ...) stay importable for tests/benchmarks but are not
# part of the stable surface.
__all__ = [
    "Engine",
    "EngineConfig",
    "Request",
    "SamplingParams",
    "Frontend",
    "Dispatcher",
    "FaultPlan",
    "TERMINAL_STATUSES",
]
