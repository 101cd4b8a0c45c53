"""Iteration-level request scheduler for continuous batching.

T-REX packs 2/4 short inputs through one parameter load (Fig. 23.1.4); the
serving analogue packs short prompts into shared prefill rows. The scheduler
extends that from batch granularity to *iteration* granularity: every decode
step the engine asks for admissions to fill freed KV slots, so one weight
sweep keeps serving a full complement of requests instead of draining a
static batch in lock-step.

Admission groups come in two flavors:

* **packed** — up to ``free_slots`` short prompts (≤ ``max_len``) packed
  first-fit-decreasing into shared ``(rows, max_len)`` prefill rows with
  segment ids (``core/packing.py``), the paper's ≤max/2-pairs / ≤max/4-quads
  policy included.
* **solo** — a prompt longer than ``max_len`` is *chunked*
  (``chunk_prompt``) instead of rejected: it is admitted alone with prefill
  width ``len(chunks) * max_len``, bounding the set of compiled prefill
  shapes.

``Scheduler`` also keeps the legacy :meth:`next_batch` drain interface so
callers of the absorbed ``DynamicBatcher`` keep working (``DynamicBatcher``
is now an alias of this class).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core.packing import (
    PackedBatch,
    PackingPolicy,
    chunk_prompt,
    pack_requests,
)
from repro.serve.sampling import SamplingParams

__all__ = ["Request", "Admission", "Scheduler", "DynamicBatcher",
           "TERMINAL_STATUSES"]

# Every request the engine returns carries exactly one of these in
# ``status`` (docs/serving.md, "Serving failure model"):
#   ok        — completed normally (budget reached or eos)
#   rejected  — never admissible (page/cache capacity); refused at submit
#   shed      — dropped by load-shedding (bounded pending queue)
#   timed_out — deadline (ttl_steps) expired while queued or in a slot
#   failed    — quarantined at runtime (non-finite logits, preemption
#               budget exhausted, watchdog escalation, unrecoverable growth)
#   cancelled — withdrawn by the caller (Engine.cancel / a front-end
#               handle's cancel) while queued or mid-decode; its slot and
#               pages were freed immediately
TERMINAL_STATUSES = ("ok", "rejected", "shed", "timed_out", "failed",
                     "cancelled")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int = 16
    # PRNG seed for sampled decoding (engine temperature > 0); None derives
    # a per-request seed from the engine's base seed and the rid, so two
    # requests never share a stream by accident.
    seed: Optional[int] = None
    # Deadline in engine virtual-clock ticks (one tick per run-loop
    # iteration, plus injected stall ticks) counted from submission; None
    # defers to the engine's default_ttl_steps (None there too = no
    # deadline). Deterministic by construction — no wall clock involved.
    ttl_steps: Optional[int] = None
    # How many preempt-and-requeue cycles this request may survive before
    # the engine escalates it to status="failed"; None defers to the
    # engine's max_preemptions_per_request (None = unbounded).
    max_preemptions: Optional[int] = None
    # Per-request sampling overrides (serve/sampling.py): None inherits
    # the engine-wide temperature/top_k defaults; SamplingParams.seed wins
    # over Request.seed. Mixed greedy + sampled batches share one jitted
    # step (sample_tokens_batch threads per-slot parameters in-graph).
    sampling: Optional[SamplingParams] = None
    # filled by the engine:
    output: Optional[List[int]] = None
    status: Optional[str] = None         # one of TERMINAL_STATUSES when done
    status_reason: Optional[str] = None  # human-readable cause for non-ok
    # Host-clock stamps (time.perf_counter seconds): submit, first
    # admission (a slot claimed or a prefill sweep), first output token,
    # terminal status. A preempted request keeps its first stamps.
    submitted_s: Optional[float] = None
    admitted_s: Optional[float] = None
    first_token_s: Optional[float] = None
    finished_s: Optional[float] = None

    def __post_init__(self):
        if self.output is None:
            self.output = []


@dataclasses.dataclass
class Admission:
    """One prefill sweep's worth of admitted requests.

    Three layouts:

    * ``packed`` — the shared-row batch for short prompts (attention-cache
      stacks, where segment masking makes packing exact).
    * ``chunks`` — a solo long prompt whose ``chunks`` concatenate back to
      the full prompt and whose prefill width is ``len(chunks) * max_len``.
    * ``shared_prefix > 0`` — request(s) whose leading prompt tokens are
      resident in the paged prefix cache (``serve/pages.py``): the engine
      maps the shared pages and prefills only each suffix. One request per
      row (a packed row cannot give each segment its own prefix-KV
      memory), but *several hit requests with distinct prefixes share one
      sweep* — ``shared_prefixes[i]`` is request i's own estimate and
      ``shared_prefix`` the max (legacy single-hit consumers).
    * neither (``row_width`` set) — one request per row, emitted by a
      no-pack scheduler (recurrent stacks: the prefill cache stores only
      each row's end-of-sequence state, so requests cannot share a row; the
      engine right-aligns them at width ``row_width``).
    """

    requests: List[Request]
    packed: Optional[PackedBatch] = None
    chunks: Optional[List[np.ndarray]] = None
    row_width: Optional[int] = None  # row-per-request layout width
    shared_prefix: int = 0  # prefix tokens expected to come from the cache
    # per-request prefix estimates for a batched shared sweep (len ==
    # len(requests) when set; entries may be 0 if a hit went stale)
    shared_prefixes: Optional[List[int]] = None

    @property
    def utilization(self) -> float:
        """Filled fraction of the prefill token slots this sweep."""
        if self.packed is not None:
            return float((self.packed.segment_ids > 0).mean())
        if self.chunks is not None:
            total = sum(len(c) for c in self.chunks)
            width = len(self.chunks) * len(self.chunks[0])
            return total / max(width, 1)
        total = sum(len(r.prompt) for r in self.requests)
        return total / max(len(self.requests) * self.row_width, 1)


class Scheduler:
    """Length-aware admission queue over a slotted KV cache.

    FIFO with packing: each call to :meth:`next_admissions` walks the queue
    head, groups short prompts into one packed prefill, and emits long
    prompts as solo chunked prefills, never admitting more requests than
    there are free slots.
    """

    def __init__(self, max_len: int = 128, max_per_row: int = 4,
                 max_rows: int = 8, max_prompt_len: Optional[int] = None,
                 pack: bool = True):
        self.policy = PackingPolicy(max_len=max_len, max_per_row=max_per_row)
        self.max_rows = max_rows
        self.max_prompt_len = max_prompt_len
        # pack=False: row-per-request admissions (recurrent stacks — only
        # the *last* segment of a packed row could recover its end state).
        self.pack = pack
        self.queue: List[Request] = []

    # ------------------------------------------------------------------

    def submit(self, req: Request) -> None:
        """Queue a request. Prompts longer than ``max_len`` are accepted and
        routed through the chunking path; only the engine's hard cache bound
        (``max_prompt_len``, when set) rejects."""
        n = len(req.prompt)
        if n == 0:
            raise ValueError("empty prompt")
        if self.max_prompt_len is not None and n > self.max_prompt_len:
            raise ValueError(
                f"prompt len {n} > max_prompt_len {self.max_prompt_len} "
                "(cache capacity); raise the engine's max_prompt_len")
        self.queue.append(req)

    def pending(self) -> int:
        return len(self.queue)

    def requeue(self, req: Request) -> None:
        """Put a preempted request back at the queue *head* (it was
        admitted before anything still queued, and FIFO resume order keeps
        paged admission deterministic). Skips :meth:`submit`'s prompt-length
        check: a resumed prompt carries its generated tokens, and the
        original admission already proved the total fits a cache lane."""
        self.queue.insert(0, req)

    def drop_where(self, pred: Callable[[Request], bool]) -> List[Request]:
        """Remove and return every queued request matching ``pred``
        (queue order preserved for both kept and dropped). The engine's
        deadline sweep uses this to expire queued requests without
        disturbing FIFO order for the rest."""
        kept: List[Request] = []
        dropped: List[Request] = []
        for r in self.queue:
            (dropped if pred(r) else kept).append(r)
        self.queue = kept
        return dropped

    def next_admissions(self, free_slots: int, reserve=None,
                        probe=None) -> List[Admission]:
        """Admit up to ``free_slots`` queued requests as admission groups.

        With a paged lane pool the engine also passes ``reserve`` — a
        stateful callable (``Engine._page_reserve`` wrapping the pool's
        per-width-class budget) that claims the pages a lane admitted for
        ``req`` will use — *net of expected prefix-cache hits* — and
        returns False once the pool would overcommit: admission then stops
        at the queue head that no longer fits — FIFO head-blocking, not
        skip-ahead, so the admission sequence (and therefore every token)
        is deterministic for a given workload.

        ``probe`` (prefix sharing): callable returning the number of a
        request's leading prompt tokens resident in the prefix cache.
        Requests with a hit ride row-per-request **shared** admissions
        (``shared_prefix``/``shared_prefixes`` set) — a packed row cannot
        give each segment its own prefix-KV memory, but hit requests
        *adjacent in admission order* batch into one multi-row suffix
        sweep (short non-hits never break adjacency: they reorder into
        the trailing packed group anyway). The engine re-probes at
        prefill time, so a stale estimate only costs packing efficiency,
        never correctness.
        """
        def fits(req: Request) -> bool:
            return reserve is None or reserve(req)

        if not self.pack:
            take = min(free_slots, self.max_rows, len(self.queue))
            reqs: List[Request] = []
            while len(reqs) < take and self.queue and fits(self.queue[0]):
                reqs.append(self.queue.pop(0))
            if not reqs:
                return []
            ml = self.policy.max_len
            width = max(-(-len(r.prompt) // ml) * ml for r in reqs)
            return [Admission(requests=reqs, row_width=width)]
        groups: List[Admission] = []
        shorts: List[Request] = []
        hits: List[Request] = []
        hit_ns: List[int] = []

        def flush_hits() -> None:
            if hits:
                groups.append(Admission(requests=list(hits),
                                        shared_prefix=max(hit_ns),
                                        shared_prefixes=list(hit_ns)))
                hits.clear()
                hit_ns.clear()

        taken = 0
        while self.queue and taken < free_slots and fits(self.queue[0]):
            req = self.queue[0]
            shared = probe(req) if probe is not None else 0
            if shared > 0:
                self.queue.pop(0)
                hits.append(req)
                hit_ns.append(shared)
                if len(hits) >= self.max_rows:
                    flush_hits()
            elif len(req.prompt) > self.policy.max_len:
                # A solo chunked prefill sits between two hit groups in
                # admission order, so the buffered hits flush first.
                flush_hits()
                self.queue.pop(0)
                groups.append(Admission(
                    requests=[req],
                    chunks=chunk_prompt(req.prompt, self.policy.max_len)))
            else:
                shorts.append(self.queue.pop(0))
            taken += 1
        flush_hits()
        if shorts:
            packed = pack_requests([r.prompt for r in shorts], self.policy)
            while packed.rows > self.max_rows and len(shorts) > 1:
                self.queue.insert(0, shorts.pop())
                packed = pack_requests([r.prompt for r in shorts], self.policy)
            groups.append(Admission(requests=shorts, packed=packed))
        return groups

    def next_mixed(self, free_slots: int, reserve=None,
                   probe=None) -> List:
        """Chunk-granular admissions for the mixed-step engine: pop up to
        ``free_slots`` queue-head requests that ``reserve`` accepts and
        return ``[(request, shared_estimate), ...]`` — no prefill layout
        at all. The mixed engine claims a slot per request and streams the
        prompt through per-step chunk columns of the jitted mixed step, so
        there are no rows to pack and no chunk list to build; prompt
        length no longer factors into *how* a request is admitted, only
        into how many steps it takes to finish prefilling. Same FIFO
        head-blocking contract as :meth:`next_admissions` (deterministic
        admission sequence), same ``probe`` semantics (estimate only; the
        engine re-probes)."""
        out: List = []
        while (self.queue and len(out) < free_slots
               and (reserve is None or reserve(self.queue[0]))):
            req = self.queue.pop(0)
            shared = probe(req) if probe is not None else 0
            out.append((req, shared))
        return out

    # ------------------------------------------------------------------
    # legacy DynamicBatcher drain interface
    # ------------------------------------------------------------------

    def next_batch(self) -> Optional[Dict]:
        """Drain-style batches (the absorbed ``DynamicBatcher`` API): packed
        prefill batches for short prompts, solo chunked entries (``packed``
        is ``None``) for long ones."""
        if not self.queue:
            return None
        head = self.queue[0]
        if len(head.prompt) > self.policy.max_len:
            adm = self.next_admissions(1)[0]
            return {"requests": adm.requests, "packed": None,
                    "chunks": adm.chunks, "utilization": adm.utilization}
        # contiguous run of short prompts from the head, packed together
        take: List[Request] = []
        limit = self.max_rows * self.policy.max_per_row
        while (self.queue and len(take) < limit
               and len(self.queue[0].prompt) <= self.policy.max_len):
            take.append(self.queue.pop(0))
        packed = pack_requests([r.prompt for r in take], self.policy)
        while packed.rows > self.max_rows and len(take) > 1:
            self.queue.insert(0, take.pop())
            packed = pack_requests([r.prompt for r in take], self.policy)
        util = float((packed.segment_ids > 0).mean())
        return {"requests": take, "packed": packed, "utilization": util}


# DynamicBatcher was absorbed into Scheduler; the name stays as an alias so
# existing imports (and its submit/next_batch interface) keep working.
DynamicBatcher = Scheduler
