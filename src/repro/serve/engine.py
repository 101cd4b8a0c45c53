"""Continuous-batching decode engine: packed prefill + slot-based decode.

The seed engine applied the paper's dynamic batching only at prefill, then
decoded each drained batch in a lock-step Python loop — per-token host sync,
re-prefilling from scratch, and no admissions until the whole batch finished.
This engine extends the weight-reuse idea to the decode phase, where real
serving traffic lives, for **every** architecture in ``configs/`` (full
attention, short-window ring caches, and SSM/RG-LRU recurrent states —
the lock-step fallback those stacks used to take is gone):

1. **Prefill**: the scheduler packs queued short prompts into shared
   ``(rows, max_len)`` rows with segment ids; one weight sweep prefills
   them all and yields each request's first token. Prompts longer than
   ``max_len`` are chunked and prefilled solo instead of being rejected.
   Stacks with recurrent layers prefill one request per row,
   *right-aligned* with padding masked to identity updates, because the
   prefill cache stores only each row's end-of-sequence state (see
   ``docs/serving.md``). Prefill caches are always full-length
   (``init_cache(..., ring=False)``) so the lane gather below can address
   any row position even under a short window.
2. **Lane assign**: each admitted request's cached state is gathered out of
   the prefill cache into a free lane of a fixed-capacity
   :class:`~repro.serve.kv_slots.SlotKVCache` — a KV segment for attention
   lanes (ring lanes land in canonical ring phase), the end-of-row state
   for recurrent lanes.
3. **Continuous decode**: every step is ONE jitted fixed-shape call over all
   ``num_slots`` lanes — per-slot cache indices, active-slot masking, and
   the next-token choice (greedy argmax, or temperature/top-k sampling with
   per-slot PRNG keys when ``temperature > 0``) inside the graph — so the
   only host traffic per step is a single ``(num_slots,)`` token fetch, not
   a round-trip per request per token. Finished requests (per-request
   ``max_new_tokens`` or ``eos_id``) release their slot; freed slots are
   refilled from the queue *mid-decode*, keeping the slot table — the
   serving analogue of the paper's PE array — full.

**Paged KV lanes** (default for attention stacks): attention cache lanes
live in a :class:`~repro.serve.pages.PagePool` of ``page_size``-token
physical pages behind per-slot block tables, so cache *memory* scales with
occupancy the same way the TDA kernel's ``[lo, hi)`` predication makes
compute scale — the serving analogue of the paper's reduced external
memory access. The scheduler admits on free **pages** (not just free
slots); if the pool still exhausts mid-decode (lanes grow a page at a
time), the engine preempts the youngest request and requeues it as a
continuation — prompt + generated-so-far — whose resumed decode is
token-identical to an uninterrupted run (greedy trivially; sampled decode
because step keys derive from absolute position, see
``serve/sampling.py``). ``paged=False`` keeps the dense contiguous lanes.

**Prefix sharing** (paged, all-attention stacks): prompts that share a
page-aligned token prefix with an earlier request — a popular system
prompt, a duplicate query, a preempted continuation resuming — skip both
the *compute* and the *writes* for that prefix: the scheduler's probe
admits them solo, the engine maps the shared physical pages into the new
lane (``PagePool.map_shared``), and one *suffix prefill* computes only the
remaining tokens while attending to the shared prefix KV gathered
straight out of the pool. Copy-on-write keeps sharing invisible: any
write that would land in a shared page (the suffix spilling into a
partially-shared tail page, a ring lane wrapping past its window, a
resumed continuation growing again) first duplicates it.
``prefix_share=False`` disables the cache.

``stats`` records one entry per prefill sweep (legacy keys ``rows`` /
``n_requests`` / ``utilization``); ``decode_stats`` aggregates the per-step
slot utilization, token counts, the predicated-attention blocks-visited
accounting, and — in paged mode — ``kv_memory_ratio`` (mean pages in use
over pool capacity, the footprint metric), ``preemptions``,
``prefix_hit_ratio`` (prompt tokens served from shared pages over prompt
tokens admitted) and ``pages_shared`` after :meth:`run`.

**Mixed step** (default for paged attention-only stacks): instead of
phase-serializing whole-prompt prefill sweeps against the decode loop, the
engine streams each admitted prompt through per-iteration *chunks*: the
jitted chunk step ``mixed_fn`` runs a row of ``S`` columns for each of the
oldest mid-prefill slots (as many as stream this iteration, up to every
slot or ``ceil(prefill_budget / S)``, at a few compiled row counts), their
fresh prompt tokens, writing chunk K/V straight into the paged lanes
(``Model.mixed_step``), while the decode step advances every decoding slot
in the same iteration. A new request claims a free
slot when a row takes its first chunk (no prefill cache, no lane copy),
and its time-to-first-token is bounded by the chunks ahead of it, in
iterations that keep decoding everyone else, instead of by whoever's
full-prompt sweep is in front of it. Token-identical to the serialized
engine by construction: chunk queries attend [resident lane ∥ causal
in-row chunk] at absolute positions, the completion token is sampled from
the same logits position with the same keys, and preemption/CoW/prefix
sharing compose unchanged. ``mixed=False`` forces the serialized phases;
``mixed=True`` on an unsupported stack (recurrent layers, contiguous
lanes, quantized KV) raises. :meth:`run` accepts ``arrivals`` — a list of
``(tick, Request)`` submitted when the virtual clock reaches ``tick`` — so
bursty mid-decode traffic is replayable, and ``decode_stats["ttft"]``
records each finished request's first-token latency (wall seconds and
clock ticks) for the ``ttft_p50``/``ttft_p99`` bench sidecars.

**Failure hardening** (``docs/serving.md``, "Serving failure model"):
every request the engine returns carries a terminal ``status`` (``ok |
rejected | shed | timed_out | failed``) and the engine degrades instead
of stalling or crashing when the workload misbehaves:

* **admission control at the door** — :meth:`submit` sheds the newest
  request when the bounded pending queue (``max_pending``) is full, and
  rejects never-admissible requests (a lane that can never be allocated
  from the pool's total page budget) immediately instead of letting them
  head-block the FIFO forever.
* **deadlines** — per-request ``ttl_steps`` (or the engine-wide
  ``default_ttl_steps``) expire queued *and* in-flight requests against a
  deterministic virtual clock (one tick per run-loop iteration).
* **numeric guard** — the decode step carries an in-graph finiteness
  check on the logits: a slot whose logits go NaN/Inf reports the ``-1``
  sentinel through the existing single token fetch (no extra device
  sync) and is quarantined alone — pages freed, ``status="failed"`` —
  while every other slot's tokens stay bit-identical.
* **progress guards** — a per-request preemption budget
  (``max_preemptions`` / ``max_preemptions_per_request``) escalates
  admit→preempt thrash to ``failed``, and a no-progress watchdog fails
  the queue head after ``watchdog_patience`` consecutive idle iterations
  so a run can never deadlock.
* **audits** — ``audit=True`` (or env ``REPRO_SERVE_AUDIT=1``) re-checks
  the pool invariants, each active lane's block-table/``[lo, hi)``
  consistency, and the CoW write-target-is-private postcondition every
  iteration, raising a structured ``AuditError``.
* **fault injection** — ``faults=FaultPlan(...)`` threads a seeded
  :class:`~repro.serve.faults.FaultInjector` through the allocation,
  preemption, logit, and clock seams for deterministic chaos testing;
  a fresh injector is built per :meth:`run` so every run replays the
  same schedule.

**Estimated HBM traffic** (``weight_bytes_per_token``,
``kv_bytes_per_token``, ``bytes_per_token``): every decode step streams
the full weight set once — audited sub-byte bits via the
``weight_stream_bits`` kwarg (from ``Model.compress_params``), byte-width
fallback otherwise — plus the KV bytes of the blocks the predicated
decode attention actually visits, per attention layer. Serving
``weight_format="compressed"`` params must drive ``bytes_per_token``
strictly below the dense-factorized run of the same workload
(``tools/check_bench.py``).
"""
from __future__ import annotations

import dataclasses
import os
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.errors import AuditError, UnsupportedConfigError
from repro.core.factorized import params_stream_bits
from repro.core.packing import chunk_prompt
from repro.kernels.common import resolve_decode_attn
from repro.kernels.tda.ref import block_stats
from repro.launch import sharding as shd
from repro.launch.mesh import data_parallel_size, tensor_parallel_size
from repro.models.transformer import Model
from repro.serve.config import RECURRENT_KINDS, EngineConfig
from repro.serve.faults import FaultInjector, FaultPlan
from repro.serve.kv_slots import SlotKVCache
from repro.serve.pages import PrefixHit, prefix_digests
from repro.serve import spans
from repro.serve.sampling import sample_tokens_batch
from repro.serve.scheduler import (
    TERMINAL_STATUSES,
    Admission,
    Request,
    Scheduler,
)

__all__ = ["Engine", "EngineConfig", "StepResult"]

# The legacy per-kwarg construction surface warns once per process (the
# shim keeps every old call site working while steering new code to
# Engine(model, params, config=EngineConfig(...))).
_LEGACY_KWARGS_WARNED = False


def _resolve_engine_config(config: Optional[EngineConfig],
                           legacy: Dict) -> EngineConfig:
    """Merge the two construction surfaces: an explicit ``EngineConfig``
    or the legacy per-knob kwargs (never both)."""
    global _LEGACY_KWARGS_WARNED
    if not legacy:
        return config if config is not None else EngineConfig()
    if config is not None:
        raise TypeError(
            "pass either config=EngineConfig(...) or the legacy per-knob "
            f"kwargs, not both (got config plus {sorted(legacy)})")
    names = {f.name for f in dataclasses.fields(EngineConfig)}
    unknown = sorted(set(legacy) - names)
    if unknown:
        raise TypeError(f"Engine() got unexpected keyword arguments "
                        f"{unknown}; see EngineConfig for the serving "
                        "knobs (mesh/faults/fleet stay Engine kwargs)")
    if not _LEGACY_KWARGS_WARNED:
        warnings.warn(
            "Engine's per-knob kwargs are deprecated; pass "
            "config=EngineConfig(...) instead (docs/serving.md has the "
            "migration table)", DeprecationWarning, stacklevel=3)
        _LEGACY_KWARGS_WARNED = True
    return EngineConfig(**legacy)


@dataclasses.dataclass
class StepResult:
    """What one :meth:`Engine.step` iteration did, for external drivers
    (``serve/frontend.py``): the tokens streamed this step as
    ``(request, token)`` events in emission order (continuations resolved
    to their origin request), the requests that reached a terminal status
    this step, and the modeled device time after the step's dispatch —
    the emission timestamp behind the inter-token-latency metrics.

    The step's dispatch, when there was one: ``kind`` (``"decode"`` or
    ``"mixed"``; ``None`` when nothing was dispatched), per slot the lane
    length before the dispatch (``starts``) and the fresh tokens it
    processed (``n_new``: 1 for a decode row, the chunk for a chunk row, 0
    for an inert or empty row), the pool's pages in use (paged), and the
    slots the chunk step ran, in row order (``chunk_slots``; ``None`` when
    it did not run). A ``"mixed"`` iteration ran the chunk step, and the
    decode step too when a slot was decoding."""

    emitted: List[Tuple[Request, int]] = dataclasses.field(
        default_factory=list)
    finished: List[Request] = dataclasses.field(default_factory=list)
    device_time: int = 0
    kind: Optional[str] = None
    starts: Optional[np.ndarray] = None
    n_new: Optional[np.ndarray] = None
    pages_in_use: Optional[int] = None
    chunk_slots: Optional[np.ndarray] = None


@dataclasses.dataclass
class _RunState:
    """Per-session loop state, lifted out of the old ``run`` loop so
    :meth:`Engine.step` can be driven externally one iteration at a time.
    ``done`` accumulates every terminal request of the session (what
    ``run`` returns; ``StepResult.finished`` is the per-step tail)."""

    cur: np.ndarray       # next input token per slot
    emitted: np.ndarray   # tokens emitted so far per slot
    budget: np.ndarray    # per-slot output budget
    # Mixed-step chunk state: pending[s] is the un-prefilled prompt suffix
    # still to stream through slot s's chunk rows (None once prefill
    # completes / for decode rows); pending_full[s] keeps the admitted
    # prompt for the completion-time prefix publish.
    pending: List[Optional[np.ndarray]]
    pending_full: List[Optional[np.ndarray]]
    done: List[Request] = dataclasses.field(default_factory=list)
    iters: int = 0
    steps: int = 0
    active_slot_steps: int = 0
    decoded_tokens: int = 0
    blocks_visited: int = 0
    blocks_dense: int = 0
    kv_bytes: float = 0.0
    preemptions: int = 0
    preempt_recovered: int = 0
    pages_used_steps: int = 0
    mixed_steps: int = 0
    chunk_tokens: int = 0  # fresh prompt tokens streamed via mixed steps
    mixed_columns: int = 0  # chunk-step columns paid: R x S a chunk step
    chunk_waits: int = 0  # queued requests x iterations held for a row
    idle: int = 0  # consecutive iterations with nothing decoded/admitted


class Engine:
    def __init__(self, model: Model, params,
                 config: Optional[EngineConfig] = None, *,
                 mesh=None, faults=None, fleet=None, **legacy):
        # One construction surface: every serving knob lives in the frozen
        # EngineConfig (serve/config.py), whose validate() holds ALL the
        # construction-time UnsupportedConfigError checks — unsupported
        # deployments fail here, not mid-decode. Legacy per-knob kwargs
        # keep working via a warn-once shim. Runtime collaborators (mesh,
        # faults, fleet) stay keyword arguments: they are live objects,
        # not serializable knobs.
        cfg_e = _resolve_engine_config(config, legacy)
        self.config = cfg_e
        traits = cfg_e.validate(model.cfg, mesh)
        self._tp = tensor_parallel_size(mesh)
        max_len = cfg_e.max_len
        num_slots = cfg_e.num_slots
        self.model = model
        self.params = params
        # Column/row-parallel weight placement (launch/sharding.py): dense
        # 'w' and factorized 'wd' leaves split across ranks; compressed
        # weight streams (wd_first/wd_deltas/wd_vq/...) fall through the
        # spec rules to replication — the bit-exact fallback that lets
        # non-MoE compressed models serve on a mesh (the old blanket
        # refusal is retired; only compressed *MoE experts* remain
        # unsupported, above). Leaves already in their shards — what
        # launch/serve.init_params builds — are kept as they are; only
        # the rest move, each straight into its own shards.
        if self._tp > 1 and params is not None:
            pspecs = shd.param_specs(jax.eval_shape(lambda: params), mesh)
            self.params = jax.tree.map(
                lambda x, s: x if getattr(x, "sharding", None) == s
                else jax.device_put(x, s),
                params, shd.named(pspecs, mesh))
        self.max_len = max_len
        self.max_new = cfg_e.max_new_tokens
        self.mesh = mesh
        self.eos_id = cfg_e.eos_id
        self.num_slots = num_slots
        self.temperature = float(cfg_e.temperature)
        self.top_k = cfg_e.top_k
        self._base_seed = int(cfg_e.seed)
        # Cache lanes must hold the longest admissible prompt plus the
        # decode budget; prompts up to 2*max_len are admitted by default via
        # the chunking path (raise max_prompt_len for longer traffic).
        self.max_prompt_len = cfg_e.max_prompt_len or 2 * max_len
        self.cache_len = self.max_prompt_len + self.max_new
        kinds = traits["kinds"]
        has_attn = traits["has_attn"]
        # Recurrent prefill caches hold one end-of-sequence state per row,
        # so those stacks admit one request per row (no intra-row packing);
        # the weight sweep is still shared across the admitted rows.
        self._recurrent = traits["recurrent"]
        self.scheduler = Scheduler(max_len=max_len, max_rows=cfg_e.max_rows,
                                   max_prompt_len=self.max_prompt_len,
                                   pack=not self._recurrent)
        # SSD's chunked scan needs prefill widths that are chunk multiples.
        self._ssd_chunk = model.cfg.ssm.chunk \
            if "ssd" in kinds and model.cfg.ssm else None
        # Decode-attention impl on the jitted hot path: "auto" compiles the
        # fused TDA kernel on TPU and keeps the dense jnp path elsewhere
        # (interpret-mode Pallas on CPU would lose to one einsum). Prefill
        # always runs on the original model — flash attention is unaffected.
        self.decode_attn = resolve_decode_attn(cfg_e.decode_attn) \
            if has_attn else "dense"
        dmodel = model.with_decode_attn(self.decode_attn,
                                        cfg_e.decode_block_k)
        self._block_k = dmodel.cfg.decode_block_k
        # Paged lane pool: only attention lanes page (recurrent state lanes
        # are fixed-shape); one page is one TDA kv block, so the default
        # page size is the predication block size.
        self.paged = traits["paged"]
        self.page_size = (cfg_e.page_size or self._block_k) \
            if self.paged else None
        if self.paged:
            self._block_k = self.page_size  # grid == pages: keep stats honest
        self.slots = SlotKVCache(model, num_slots, self.cache_len,
                                 page_size=self.page_size,
                                 pool_frac=cfg_e.pool_frac,
                                 page_cap=cfg_e.page_cap
                                 if self.paged else None,
                                 mesh=mesh)
        # Page-level prefix sharing: only meaningful for paged stacks whose
        # cache is *entirely* per-token kv lanes — a recurrent layer would
        # need its end-of-prefix state, which is neither paged nor
        # content-addressable, so hybrids and SSM stacks degrade to cold
        # prefills (probe never fires).
        self.prefix_share = bool(cfg_e.prefix_share) and self.paged and all(
            s == "kv" for s in jax.tree.leaves(self.slots.specs))
        self._shared_tokens = 0
        self._prompt_tokens = 0
        self._pages_shared = 0
        # Cross-replica prefix sharing: a FleetPrefixIndex (serve/pages.py)
        # shared by N replicas — publishes mirror host copies of full
        # prefix pages, probes restore fleet-only pages into the local
        # pool. Attached at construction or later (Dispatcher wires it).
        self._fleet = None
        self._fleet_restored_pages = 0
        if fleet is not None:
            self.attach_fleet(fleet)
        # ---- mixed step (chunked prefill interleaved with decode): each
        # iteration streams up to ``prefill_budget`` fresh prompt tokens
        # through the chunk step beside the decode step's slots.
        # Needs paged attention lanes (chunk K/V scatters straight through
        # the block tables — there is no prefill cache to lane-copy from)
        # and an attention-only stack (a recurrent layer has no
        # multi-token decode form here). kv_quant is gated off: a later
        # chunk would attend the *quantized* K/V of earlier chunks while
        # the serialized prefill attends unquantized — not token-identical.
        # (validate() already refused an explicit mixed=True on an
        # unsupported stack, with the actionable message.)
        self.mixed = traits["mixed_ok"] if cfg_e.mixed is None \
            else bool(cfg_e.mixed)
        self.prefill_budget = cfg_e.prefill_budget
        # Static chunk-row width of the mixed step (one compiled shape):
        # no row ever carries more fresh tokens than the whole-step budget
        # or a serialized prefill row would.
        self._chunk_width = max(1, min(max_len,
                                       cfg_e.prefill_budget or max_len))
        # Rows of the chunk step: one per prompt streaming this iteration,
        # at most R — every slot, or ceil(budget / S) under a
        # ``prefill_budget`` — so a step pays S columns only for rows that
        # carry a chunk. It is compiled at a few row counts (the powers of
        # two below R, and R), a mesh's batch axes a multiple of their
        # size (so the rows shard instead of running on every batch
        # rank), and an iteration takes the largest count its prompts
        # fill.
        dp = data_parallel_size(mesh)
        rows = num_slots if cfg_e.prefill_budget is None else min(
            num_slots, -(-cfg_e.prefill_budget // self._chunk_width))
        self._chunk_rows = -(-rows // dp) * dp
        self._row_counts = tuple(sorted(
            {min(self._chunk_rows, dp << i)
             for i in range(self._chunk_rows.bit_length())}
            | {self._chunk_rows}))
        self._warm_rows: set = set()  # sampling modes warmed at every count
        # Static layer -> lane-width map for the paged decode step: one
        # width for uniform stacks, per-layer (None on recurrent layers)
        # otherwise. Derived from the slot table's per-leaf widths — the
        # same source the pool's block-table keys come from — so the
        # tables[w] lookup in decode_fn cannot drift out of sync.
        self._page_struct = None
        if self.paged:
            def layer_width(spec):
                ws = {w for w in jax.tree.leaves(spec) if w > 0}
                assert len(ws) <= 1, f"mixed widths in one layer: {ws}"
                return ws.pop() if ws else None
            if model.cfg.uniform_layers:
                self._page_struct = layer_width(self.slots.widths)
            else:
                self._page_struct = {
                    name: layer_width(spec)
                    for name, spec in self.slots.widths.items()}
        # Distinct attention-lane shapes for the blocks-visited accounting:
        # one (ring, block_k) descriptor per distinct window among the
        # attention layers (pure-recurrent stacks have none). The per-ring
        # layer counts additionally weight the estimated-KV-bytes metric
        # (every attention layer streams its own lane's blocks per step).
        self._ring_layers: Dict[int, int] = {}
        for i in range(model.cfg.n_layers):
            k = model.cfg.block_kind(i)
            if k in ("attn", "local"):
                ring = model._block_ring(k, self.cache_len)
                self._ring_layers[ring] = self._ring_layers.get(ring, 0) + 1
        self._attn_rings = sorted(self._ring_layers)
        # ---- estimated HBM traffic per decode step (observability; the
        # gateable analogue of the paper's external-memory-access numbers).
        # Weights: every decode step streams the full weight set once.
        # `weight_stream_bits` carries the audited number from
        # Model.compress_params (sub-byte streams); the fallback prices
        # every param leaf at its in-memory width.
        self._weight_stream_bits = (
            float(cfg_e.weight_stream_bits)
            if cfg_e.weight_stream_bits is not None
            else float(params_stream_bits(params)) if params is not None
            else 0.0)
        # KV: bytes per cached token actually visited by the predicated
        # decode attention (int8 codes + per-(token, head) f32 scales under
        # kv_quant, else K/V at the compute dtype).
        c = model.cfg
        if c.kv_quant:
            self._kv_token_bytes = 2 * c.kv_heads * (c.head_dim + 4)
        else:
            self._kv_token_bytes = (2 * c.kv_heads * c.head_dim
                                    * c.compute_dtype.itemsize)
        # Per-slot sampling state (seed / temperature / top-k resolved at
        # admission from the request's SamplingParams, engine defaults
        # otherwise) + admission order (preemption victims are
        # youngest-first, vLLM-style, so older requests always progress).
        self._seeds = np.zeros(num_slots, np.uint32)
        self._temps = np.zeros(num_slots, np.float32)
        self._topks = np.zeros(num_slots, np.int32)  # 0 = no truncation
        self._admit_seq = np.zeros(num_slots, np.int64)
        self._seq = 0
        self.stats: List[Dict] = []  # one entry per prefill sweep
        self.decode_stats: Dict = {}
        # ---- failure hardening (docs/serving.md, "Serving failure model")
        # Audit mode: env-defaulted so CI can run the whole equivalence
        # suite with production invariant audits on (REPRO_SERVE_AUDIT=1)
        # without duplicating any test.
        audit = cfg_e.audit
        if audit is None:
            audit = bool(int(os.environ.get("REPRO_SERVE_AUDIT", "0") or 0))
        self.audit = bool(audit)
        self.max_pending = cfg_e.max_pending
        self.default_ttl = cfg_e.default_ttl_steps
        self.max_preempt = cfg_e.max_preemptions_per_request
        self.watchdog_patience = int(cfg_e.watchdog_patience)
        # Fault injection: a FaultPlan builds a FRESH injector per run()
        # (every run replays the same seeded schedule); an injector
        # instance is used as-is (schedule continues across runs).
        self._fault_plan: Optional[FaultPlan] = None
        self.fault_injector: Optional[FaultInjector] = None
        if isinstance(faults, FaultPlan):
            self._fault_plan = faults if faults.any_faults() else None
        elif isinstance(faults, FaultInjector):
            self.fault_injector = faults
        elif faults is not None:
            raise TypeError("faults must be a FaultPlan or FaultInjector")
        self._inj: Optional[FaultInjector] = None  # current run's injector
        # Deterministic virtual clock: one tick per run-loop iteration
        # (plus injected stall ticks); deadlines count against it.
        self._clock = 0
        # Modeled device time: every jitted forward dispatch advances this
        # by its SEQUENCE width (decode steps by 1, a width-S chunk step by
        # S, a solo whole-prompt sweep by its full concatenated width).
        # Batch rows ride in parallel PE lanes and are free, matching the
        # paper's dynamic-batching utilization argument — and the same
        # modeled-cost convention as the bytes-per-token accounting. TTFT
        # deltas against this counter are the deterministic, CI-gateable
        # latency proxy at smoke scale, where wall time measures host FLOPs
        # (row-linear) instead of dispatch latency.
        self._device_time = 0
        # Per-engine terminal-status counters, reported (then reset) in
        # decode_stats["status_counts"]; requests finished outside a slot
        # (shed/rejected at submit) park in _terminal until the next run().
        self._counts: Dict[str, int] = {s: 0 for s in TERMINAL_STATUSES}
        self._terminal: List[Request] = []
        self._audit_violations = 0
        # All-false nan-injection mask: committed once so the no-fault hot
        # path re-passes the same device array every step.
        self._no_nan = jnp.zeros(num_slots, bool)
        # Stepping session (serve/frontend.py drives step() directly;
        # run() is a thin loop over it). None = no session in flight.
        self._st: Optional[_RunState] = None
        self._events: Optional[List[Tuple[Request, int]]] = None
        self._admitted: List[int] = []  # rids admitted by this step

        def prefill_fn(params, batch):
            rows, width = batch["inputs"].shape
            # Full-length caches (no ring clamp): the slot-lane gather must
            # be able to address every row position (kv_slots.py).
            caches = model.init_cache(rows, width, ring=False)
            logits, new_caches, _ = model.apply(
                params, batch, caches=caches, cache_index=jnp.int32(0),
                mesh=mesh)
            return logits, new_caches

        def prefill_shared_fn(params, batch, pk, pv, plen):
            # Suffix prefill over a shared prefix: the row holds only the
            # suffix tokens (absolute positions in batch["positions"]);
            # every attention layer prepends the gathered prefix KV. The
            # fresh cache holds suffix K/V at row positions [0, suffix) —
            # the lane assign scatters them behind the shared pages.
            rows, width = batch["inputs"].shape
            caches = model.init_cache(rows, width, ring=False)
            logits, new_caches, _ = model.apply(
                params, batch, caches=caches, cache_index=jnp.int32(0),
                mesh=mesh, prefix_kv={"k": pk, "v": pv, "len": plen})
            return logits, new_caches

        # One compile per prefill shape — widths are max_len multiples and
        # packed row counts are padded to powers of two, so the set is small
        # and bounded — and exactly one each for the decode and the chunk
        # step: shapes never depend on which requests are in flight.
        self._dmodel = dmodel
        self._prefill = jax.jit(prefill_fn)
        self._prefill_shared = jax.jit(prefill_shared_fn) \
            if self.prefix_share else None
        # (CPU doesn't implement donation; skip there.)
        self._decode, self._mixed = self._step_programs(
            mesh, donate=jax.default_backend() != "cpu")

        def sample1(row, seed, pos, temp, topk):
            return sample_tokens_batch(row[None], seed[None], pos[None],
                                       temp[None], topk[None])[0]

        # First tokens come from prefill logits on the host; one jit of
        # the very same batch sampler (as a 1-row batch) keeps them
        # bit-identical to decode, per-request parameters included.
        self._sample1 = jax.jit(sample1)

    def _step_programs(self, mesh, donate: bool):
        """The jitted decode step, ``(num_slots, 1)``, and chunk step,
        ``(R, S)`` at each row count R it runs (``None`` without the mixed
        step), serving on ``mesh``, with the page pool donated when
        ``donate``. Their names (``decode_fn``, ``mixed_fn``) name the
        programs in a profiler trace."""
        dmodel, num_slots = self._dmodel, self.num_slots

        def page_tables(tables, rows):
            if not self.paged:
                return None

            def entry(w):
                return {"bt": tables[w][rows], "width": w,
                        "page_size": self.page_size}
            if isinstance(self._page_struct, dict):
                return {name: (entry(w) if w is not None else None)
                        for name, w in self._page_struct.items()}
            return entry(self._page_struct)

        def next_tokens(row, nan_mask, seeds, pos, temps, topks, sampled):
            # Fault injection lands *after* the model: caches never see
            # the poison and other rows are untouched by construction.
            row = jnp.where(nan_mask[:, None], jnp.nan, row)
            if sampled:
                # ``pos`` is the drawn token's absolute position: the same
                # (request, position) key the serialized engine and a
                # preempted-then-resumed request derive
                # (serve/sampling.py). Per-row temperature/top-k ride
                # in-graph; greedy rows (temps == 0) take the batch
                # sampler's argmax lane.
                nxt = sample_tokens_batch(row, seeds, pos, temps, topks)
            else:
                # ``sampled`` is a trace-time flag: an all-greedy batch
                # compiles (and stays bit-identical to) the plain argmax
                # graph — no sort/categorical ops to build or pay for.
                nxt = jnp.argmax(row, axis=-1).astype(jnp.int32)
            # In-graph finiteness guard: a row whose logits went NaN/Inf
            # (flaky kernel, injected fault) reports the -1 sentinel —
            # vocab ids are >= 0 — through the iteration's single token
            # fetch, so quarantine costs no extra device sync.
            bad = ~jnp.all(jnp.isfinite(row), axis=-1)
            return jnp.where(bad, jnp.int32(-1), nxt)

        def decode_fn(params, tokens, caches, lengths, active, seeds,
                      temps, topks, tables, nan_mask, sampled):
            logits, new_caches = dmodel.decode_step(
                params, {"inputs": tokens}, caches, lengths,
                slot_mask=active,
                pages=page_tables(tables, slice(None, num_slots)),
                mesh=mesh)
            return next_tokens(logits[:, 0, :], nan_mask, seeds,
                               lengths + 1, temps, topks,
                               sampled), new_caches

        def mixed_fn(params, tokens, caches, rows, lengths, n_new, active,
                     seeds, temps, topks, tables, nan_mask, sampled):
            # The chunk step: row r carries slot rows[r]'s fresh tokens in
            # columns [0, n_new) at absolute positions [lengths, lengths +
            # n_new). The per-slot inputs gather on the device; a padding
            # row names the sentinel slot num_slots, which gathers as an
            # inert row (n_new 0, inactive) over the FREE block-table row.
            def take(a):
                return jnp.take(a, rows, axis=0, mode="fill",
                                fill_value=0)
            lengths, n_new, active, seeds, temps, topks, nan_mask = map(
                take, (lengths, n_new, active, seeds, temps, topks,
                       nan_mask))
            # The row's token comes from chunk column n_new - 1 (clamped;
            # an inert row reads column 0 and the host ignores it).
            row, new_caches = dmodel.mixed_step(
                params, {"inputs": tokens}, caches, lengths, n_new,
                slot_mask=active, pages=page_tables(tables, rows),
                mesh=mesh)
            # Absolute position of the sampled token: lengths + n_new
            # tokens precede it — the serialized engine's prefill first
            # token (L) and decode (lengths + 1) keys alike.
            return next_tokens(row, nan_mask, seeds, lengths + n_new, temps,
                               topks, sampled), new_caches

        # Donating the cache lets accelerators update it in place.
        # ``sampled`` is static: an all-greedy step compiles (and caches)
        # exactly the argmax-only graph — at most two compiled variants of
        # each program.
        donated = (2,) if donate else ()
        decode = jax.jit(decode_fn, donate_argnums=donated,
                         static_argnums=(10,))
        mixed = jax.jit(mixed_fn, donate_argnums=donated,
                        static_argnums=(12,)) if self.mixed else None
        return decode, mixed

    # ------------------------------------------------------------------

    def submit(self, req: Request) -> None:
        """Queue a request, applying admission control at the door.

        * **Load shedding**: with ``max_pending`` set, a submit that finds
          the pending queue full is shed deterministically (the *newest*
          request loses; everything already queued keeps its FIFO place)
          with ``status="shed"`` — it is returned by the next :meth:`run`
          instead of being queued.
        * **Never-admissible rejection**: a prompt whose lane can never be
          allocated — its per-class page demand exceeds the pool's *total*
          page budget (reachable only under an explicit ``page_cap``;
          ``pool_frac`` floors every class at one full lane) — would
          head-block the FIFO forever. It is refused here with
          ``status="rejected"`` and a reason naming the short class.
        * The scheduler's hard cache-capacity bound (prompt longer than
          ``max_prompt_len``) still raises ``ValueError`` — that is a
          caller bug, not traffic — with ``status`` set for uniformity.
        """
        req.submitted_s = time.perf_counter()
        if (self.max_pending is not None
                and self.scheduler.pending() >= self.max_pending):
            self._finish_terminal(
                req, "shed",
                f"pending queue full ({self.scheduler.pending()} queued >= "
                f"max_pending={self.max_pending})")
            return
        if self.paged:
            pool = self.slots.pool
            for w, need in pool.class_needs(len(req.prompt) + 1).items():
                cap = pool.classes[w].num_pages
                if need > cap:
                    self._finish_terminal(
                        req, "rejected",
                        f"never admissible: prompt ({len(req.prompt)} "
                        f"tokens) needs {need} width-{w} pages but the "
                        f"pool holds {cap} total — would head-block the "
                        "queue forever")
                    return
        try:
            self.scheduler.submit(req)
        except ValueError as e:
            req.status = "rejected"
            req.status_reason = str(e)
            raise
        req._submit_clock = self._clock  # type: ignore[attr-defined]
        req._submit_dev = self._device_time  # type: ignore[attr-defined]

    def run(self, arrivals: Optional[Sequence[Tuple[int, Request]]] = None
            ) -> List[Request]:
        """Serve until queue and slots are empty; returns finished requests
        in completion order (every one carrying a terminal ``status``,
        including requests shed/rejected at submit time).

        ``arrivals``: optional ``(tick, Request)`` pairs submitted when the
        run-loop iteration count reaches ``tick`` — a deterministic,
        replayable way to drive bursty mid-decode traffic into either
        engine mode (the TTFT benchmark's workload contract).

        This is now a thin loop over :meth:`step` — token-identical to the
        old monolithic loop by construction (same iteration body, same
        arrival schedule) — so external drivers (``serve/frontend.py``)
        reuse the exact engine semantics one step at a time."""
        if self._st is not None:
            raise RuntimeError(
                "a stepping session is already in flight; drive it to "
                "completion via step()/finish_run() before calling run()")
        arr = sorted(arrivals or [], key=lambda a: a[0])
        ai = 0
        st = self._session()
        while (self.scheduler.pending() or self.slots.active.any()
               or ai < len(arr)):
            # The old loop submitted arrivals due at the *incremented*
            # iteration count; step() bumps st.iters first, so everything
            # with tick <= st.iters + 1 is due this step.
            due: List[Request] = []
            while ai < len(arr) and arr[ai][0] <= st.iters + 1:
                due.append(arr[ai][1])
                ai += 1
            self.step(submits=due)
        return self.finish_run()

    def has_work(self) -> bool:
        """True while a step could still make progress: requests queued or
        decoding. External drivers loop ``while has_work(): step()``."""
        return bool(self.scheduler.pending() or self.slots.active.any())

    @property
    def iteration(self) -> int:
        """The current session's iteration count (0 outside a session) —
        the tick axis trace arrivals are scheduled on: a request with
        ``tick <= iteration + 1`` is due for the next :meth:`step`,
        matching :meth:`run`'s arrival semantics."""
        return self._st.iters if self._st is not None else 0

    def _session(self) -> _RunState:
        """The current stepping session, lazily started: fresh per-session
        loop state, a fresh injector when a :class:`FaultPlan` is attached
        (every session replays the same seeded schedule), zeroed sharing
        counters, and any submit-time terminal requests drained into the
        session's ``done``."""
        if self._st is None:
            inj = FaultInjector(self._fault_plan) \
                if self._fault_plan is not None else self.fault_injector
            self._inj = inj
            self.fault_injector = inj
            self._shared_tokens = 0   # prompt tokens from shared pages
            self._prompt_tokens = 0   # prompt tokens admitted (+ resumes)
            self._pages_shared = 0    # page mappings served from the cache
            self._fleet_restored_pages = 0
            st = _RunState(
                cur=np.zeros(self.num_slots, np.int32),
                emitted=np.zeros(self.num_slots, np.int32),
                budget=np.zeros(self.num_slots, np.int32),
                pending=[None] * self.num_slots,
                pending_full=[None] * self.num_slots)
            st.done.extend(self._terminal)  # shed/rejected at submit
            self._terminal.clear()
            self._st = st
        return self._st

    def _emit(self, req: Request, tok: int) -> None:
        """Append one output token (resolving continuations to their
        origin), stamp its modeled-device-time emission point (the ITL
        metric's clock), and surface it as a ``StepResult`` event for
        streaming drivers."""
        target = getattr(req, "_origin", req)
        target.output.append(int(tok))
        devs = getattr(target, "_token_dev", None)
        if devs is None:
            devs = []
            target._token_dev = devs  # type: ignore[attr-defined]
        devs.append(self._device_time)
        if self._events is not None:
            self._events.append((target, int(tok)))

    def _step_result(self, res: StepResult, n_done0: int) -> StepResult:
        """Seal a step: the requests that reached a terminal status during
        it (the session ``done`` tail) and the post-dispatch device time."""
        res.finished = self._st.done[n_done0:]
        res.device_time = self._device_time
        return res

    def cancel(self, req: Request) -> bool:
        """Withdraw a request mid-flight: drop it from the queue and/or
        release its slot (pages return to the pool immediately), finishing
        it with ``status="cancelled"``. Safe between steps of a live
        session (the front-end's cancellation path) and outside one
        (the request is returned by the next run/session). Returns False
        when the request already holds a terminal status or is unknown to
        this engine."""
        target = getattr(req, "_origin", req)
        if target.status is not None:
            return False
        dropped = self.scheduler.drop_where(
            lambda r: getattr(r, "_origin", r) is target)
        hit = bool(dropped)
        sl = self.slots
        for s in np.flatnonzero(sl.active):
            if sl.request[s] is target:
                sl.release(int(s))
                if self._st is not None:
                    self._st.pending[s] = None
                    self._st.pending_full[s] = None
                hit = True
        if not hit:
            return False
        if self._st is not None:
            self._finish(target, "cancelled", "cancelled by caller",
                         self._st.done)
        else:
            self._finish_terminal(target, "cancelled",
                                  "cancelled by caller")
        return True

    def compiled_steps(self) -> Dict[str, str]:
        """Compiled HLO text of the jitted serving steps at this engine's
        shapes: ``"decode"``, plus ``"mixed"`` (the chunk step at its
        fewest rows) when the mixed step is on — greedy variants, the ones
        an all-greedy batch dispatches. For auditing which kernels the hot
        path runs (a Pallas kernel shows as a ``tpu_custom_call`` on a
        TPU)."""
        n, S, r = self.num_slots, self._chunk_width, self._row_counts[0]
        x = self._slot_inputs()
        out = {"decode": self._decode.lower(*self._step_args(
            x, np.zeros((n, 1), np.int32))).compile().as_text()}
        if self._mixed is not None:
            out["mixed"] = self._mixed.lower(*self._step_args(
                x, np.zeros((r, S), np.int32), n_new=np.zeros(n, np.int32),
                rows=np.zeros(r, np.int32))).compile().as_text()
        return out

    def _slot_inputs(self, inj=None) -> dict:
        """The per-slot device inputs that both step programs read — lane
        lengths, sampling state, block tables, the NaN-injection mask and
        whether any active slot samples — uploaded once an iteration."""
        sl = self.slots
        mask = inj.nan_mask() if inj is not None else None
        return {"lengths": jnp.asarray(sl.lengths),
                "seeds": jnp.asarray(self._seeds),
                "temps": jnp.asarray(self._temps),
                "topks": jnp.asarray(self._topks),
                "tables": sl.pool.device_tables() if self.paged else {},
                "nan_mask": self._no_nan if mask is None
                else jnp.asarray(mask),
                "sampled": bool(np.any(self._temps[sl.active] > 0))}

    def _step_args(self, x: dict, toks, active=None, n_new=None,
                   rows=None) -> list:
        """Positional arguments of one ``_decode`` dispatch over the slots
        in ``active`` (all active slots by default), or of one ``_mixed``
        dispatch of the chunk rows ``rows`` (slot indices; ``num_slots``
        pads) given ``n_new`` per slot, from the iteration's
        :meth:`_slot_inputs` ``x`` — the one signature the serving loop
        and :meth:`compiled_steps` use."""
        lead = (x["lengths"],)
        if rows is not None:
            lead = (jnp.asarray(rows), *lead, jnp.asarray(n_new))
        active = self.slots.active if active is None else active
        return [self.params, jnp.asarray(toks), self.slots.caches, *lead,
                jnp.asarray(active), x["seeds"], x["temps"], x["topks"],
                x["tables"], x["nan_mask"], x["sampled"]]

    def _rows_filled(self, n: int) -> int:
        """The largest row count the chunk step is compiled at that ``n``
        prompts fill (``n`` itself below the smallest)."""
        return max((r for r in self._row_counts if r <= n), default=n)

    def _warm_row_counts(self, args: list, rows: int) -> None:
        """At the chunk step's first dispatch in a sampling mode, run it
        once on inert rows (the sentinel slot's) at each of its other row
        counts, so that every shape an iteration may pick compiles then —
        in a warm-up — and not while serving."""
        if args[-1] in self._warm_rows:
            return
        self._warm_rows.add(args[-1])
        for r in self._row_counts:
            if r != rows:
                a = list(args)
                a[1] = jnp.zeros((r, self._chunk_width), jnp.int32)
                a[2] = self.slots.caches
                a[3] = jnp.full((r,), self.num_slots, jnp.int32)
                _, self.slots.caches = self._mixed(*a)

    def step(self, submits: Sequence[Request] = ()) -> StepResult:
        """ONE engine iteration — admit, the jitted decode and/or chunk
        step, retire — exactly the old ``run`` loop body, externally
        driveable.

        ``submits`` are submitted after this step's clock tick, matching
        the arrival semantics of :meth:`run`. Returns the step's streamed
        ``(request, token)`` events, newly terminal requests and the
        record of its dispatch; call :meth:`finish_run` once
        :meth:`has_work` goes False to collect the session's ``done`` list
        and ``decode_stats``. The call and each of its phases are host
        spans of a profiler trace (``serve/spans.py``)."""
        with TraceAnnotation(spans.STEP):
            return self._step(submits)

    def _step(self, submits: Sequence[Request]) -> StepResult:
        st = self._session()
        sl = self.slots
        inj = self._inj
        # Slot-indexed session state: arrays/lists are mutated in place,
        # so the loop body below reads exactly like the old run loop.
        cur, emitted, budget = st.cur, st.emitted, st.budget
        pending, pending_full = st.pending, st.pending_full
        done = st.done
        res = StepResult()
        self._events = res.emitted
        n_done0 = len(done)
        try:
            # Virtual clock: one tick per iteration, plus injected stall
            # ticks — so deadlines age deterministically even while the
            # queue is head-blocked with nothing decoding.
            self._clock += 1
            if inj is not None:
                self._clock += inj.begin_step(st.iters, self.num_slots,
                                              sl.active)
            st.iters += 1
            for r in submits:
                self.submit(r)
            if self._terminal:  # shed/rejected by a submission
                done.extend(self._terminal)
                self._terminal.clear()
            with TraceAnnotation(spans.EXPIRE):
                progressed = self._expire(done) > 0
                if (inj is not None and inj.forced_preempt()
                        and sl.active.any()):
                    victims = np.flatnonzero(sl.active)
                    victim = int(max(victims,
                                     key=lambda v: self._admit_seq[v]))
                    if self._preempt_or_fail(victim, done):
                        st.preempt_recovered += 1
                    st.preemptions += 1
            with TraceAnnotation(spans.GROW):
                if self.paged:
                    # Lanes grow one page at a time; make every active
                    # slot's next write position resident, preempting the
                    # youngest request(s) when the pool runs dry. Growth
                    # runs BEFORE admission so a fresh admission can only
                    # reserve pages the in-flight lanes don't need this
                    # step — together with assign_many's one-ahead
                    # allocation, an admitted request always survives to
                    # its first decode step.
                    rec, esc = self._ensure_pages(done)
                    st.preemptions += rec + esc
                    st.preempt_recovered += rec
                if self.mixed:
                    # Expiry / forced preemption / page growth above may
                    # have released mid-prefill slots: drop their chunk
                    # state.
                    for s in range(self.num_slots):
                        if not sl.active[s]:
                            pending[s] = None
                            pending_full[s] = None
            n_rows = 0
            if self.mixed:
                # The chunk step's rows this iteration: the largest
                # compiled count that the mid-prefill slots and the queued
                # requests with a free slot fill.
                staged = sum(p is not None for p in pending)
                queued = min(sl.free_slots().size, self.scheduler.pending())
                n_rows = self._rows_filled(staged + queued)
            if self.scheduler.pending():
                free = sl.free_slots()
                if self.mixed:
                    # A request claims a slot when a chunk row takes its
                    # first chunk this iteration: mid-prefill slots keep
                    # their rows (oldest first) until their prompt is in,
                    # and a request past the rows waits in the queue, not
                    # in a slot that would run nothing.
                    take = max(n_rows - staged, 0)
                    st.chunk_waits += max(queued - take, 0)
                    free = free[:take]
                if free.size:
                    n_done = len(done)
                    self._admitted = []
                    with TraceAnnotation(spans.ADMIT) as span:
                        if self.mixed:
                            admitted = self._admit_mixed(
                                free, cur, emitted, budget, pending,
                                pending_full, done)
                        else:
                            admitted = self._admit(free, cur, emitted,
                                                   budget, done)
                        if self._admitted and TraceAnnotation.is_enabled():
                            span.set_metadata(rids=" ".join(
                                map(str, self._admitted)))
                    progressed |= admitted > 0 or len(done) > n_done
            active_ix = np.flatnonzero(sl.active)
            if self.audit:
                self._audit_step()
            if active_ix.size == 0:
                # Nothing to decode: either everything admitted finished
                # at prefill (progress) or the queue head is blocked. The
                # watchdog bounds the blocked case — after
                # ``watchdog_patience`` consecutive no-progress iterations
                # the head is escalated to status="failed", so the loop
                # can never spin forever.
                if progressed:
                    st.idle = 0
                else:
                    st.idle += 1
                    if st.idle > self.watchdog_patience:
                        self._watchdog_escalate(done)
                        st.idle = 0
                return self._step_result(res, n_done0)
            st.idle = 0

            # ---- the iteration's dispatches: slots past their prompt
            # decode one token each in the (num_slots, 1) decode step;
            # with the mixed step, the ``n_rows`` oldest mid-prefill slots
            # (the serialized engine's FIFO prefill order) stream up to S
            # prompt tokens each, at most ``prefill_budget`` in all,
            # through the (R, S) chunk step, R the smallest compiled row
            # count that holds them. Both are dispatched, then read back
            # in one sync.
            with TraceAnnotation(spans.PACK):
                S = self._chunk_width
                n_new = np.zeros(self.num_slots, np.int32)
                chunk = sorted(
                    (s for s in active_ix if pending[s] is not None),
                    key=lambda s: self._admit_seq[s])[:n_rows]
                left = self.prefill_budget
                for s in chunk:
                    c = min(len(pending[s]), S)
                    if left is not None:
                        c = min(c, left)
                        left -= c
                    n_new[s] = c
                # Chunk writes span [len, len + c): allocate + CoW each
                # span (oldest first; a dry pool preempts the youngest,
                # like _ensure_pages — make_range_writable is
                # all-or-nothing so the retry after eviction is safe).
                for s in chunk:
                    if not sl.active[s] or n_new[s] <= 0:
                        continue
                    ok, rec, esc = self._grow_span(
                        int(s), int(sl.lengths[s]) + int(n_new[s]), done)
                    st.preemptions += rec + esc
                    st.preempt_recovered += rec
                    if not ok:
                        # deferred (pool dry, this slot youngest): its
                        # chunk waits, intact, for the next iteration.
                        n_new[s] = 0
                for s in range(self.num_slots):
                    if not sl.active[s]:
                        pending[s] = None
                        pending_full[s] = None
                chunk = [s for s in chunk if sl.active[s] and n_new[s] > 0]
                decode = sl.active & np.array([p is None for p in pending])
                n_new = np.where(decode, 1, np.where(sl.active, n_new, 0)
                                 ).astype(np.int32)
                active_ix = np.flatnonzero(sl.active)
                if not chunk and not decode.any():
                    return self._step_result(res, n_done0)
                for ring in self._attn_rings:
                    bs = block_stats(
                        np.where(sl.active,
                                 np.minimum(sl.lengths + n_new, ring), 0),
                        ring, min(self._block_k, ring))
                    st.blocks_visited += bs["visited"]
                    st.blocks_dense += bs["dense"]
                    st.kv_bytes += (bs["visited"] * min(self._block_k, ring)
                                    * self._ring_layers[ring]
                                    * self._kv_token_bytes)
                self._record_dispatch(res, "mixed" if chunk else "decode",
                                      n_new)
            with TraceAnnotation(spans.UPLOAD):
                x = self._slot_inputs(inj)
                dispatches = []
                if decode.any():
                    dispatches.append(("decode", self._decode, self._step_args(
                        x, cur[:, None], active=decode)))
                if chunk:
                    res.chunk_slots = np.asarray(chunk, np.int32)
                    R = next(r for r in self._row_counts if r >= len(chunk))
                    rows = np.full(R, self.num_slots, np.int32)
                    rows[:len(chunk)] = chunk
                    toks = np.zeros((R, S), np.int32)
                    for r, s in enumerate(chunk):
                        toks[r, :n_new[s]] = pending[s][:n_new[s]]
                    dispatches.append(("chunk", self._mixed, self._step_args(
                        x, toks, active=sl.active, n_new=n_new, rows=rows)))
            with TraceAnnotation(spans.DISPATCH):
                if chunk:
                    self._warm_row_counts(dispatches[-1][2], R)
                outs = []
                for _, program, args in dispatches:
                    args[2] = sl.caches  # the previous dispatch's pool
                    out, sl.caches = program(*args)
                    outs.append(out)
            with TraceAnnotation(spans.SYNC):
                outs = jax.device_get(outs)  # the iteration's single sync
                nxt = np.zeros(self.num_slots, np.int32)
                for (kind, _, _), out in zip(dispatches, outs):
                    if kind == "decode":
                        nxt[decode] = out[decode]
                    else:
                        nxt[chunk] = out[:len(chunk)]
            with TraceAnnotation(spans.RETIRE):
                self._device_time += int(decode.any()) + (S if chunk else 0)
                st.steps += 1
                if chunk:
                    st.mixed_steps += 1
                    st.mixed_columns += R * S
                st.active_slot_steps += active_ix.size
                if self.paged:
                    st.pages_used_steps += res.pages_in_use
                ran = decode.copy()
                ran[chunk] = True
                for s in np.flatnonzero(ran):
                    tok = int(nxt[s])
                    req = sl.request[s]
                    if tok < 0:
                        # Non-finite logits: quarantine exactly this slot —
                        # free its pages, mark it failed, keep serving the
                        # rest (their tokens are bit-identical by
                        # construction: lanes are independent and the
                        # poison never reached the caches).
                        sl.release(int(s))
                        pending[s] = None
                        pending_full[s] = None
                        step = "decode" if decode[s] else "chunk"
                        self._finish(req, "failed",
                                     "non-finite logits (NaN/Inf) in the "
                                     f"{step} step", done)
                        continue
                    if pending[s] is not None:
                        c = int(n_new[s])
                        sl.advance_n(int(s), c)
                        st.chunk_tokens += c
                        rest = pending[s][c:]
                        if len(rest):
                            # still mid-prefill: the sampled column is a
                            # mid-prompt continuation, never an output
                            pending[s] = rest
                            continue
                        # Prefill complete: ``tok`` IS the first token —
                        # sampled from the same logits position (and, in
                        # sampled mode, the same key) as the serialized
                        # prefill's first token. Publish the prompt's full
                        # pages now that they hold their final bytes.
                        if self.prefix_share:
                            self._publish_prefix(int(s), pending_full[s])
                        pending[s] = None
                        pending_full[s] = None
                        self._emit(req, tok)
                        self._note_ttft(req)
                        emitted[s] = len(req.output)
                    else:
                        sl.advance(s)
                        self._emit(req, tok)
                        emitted[s] += 1
                        st.decoded_tokens += 1
                    cur[s] = tok
                    if emitted[s] >= budget[s] or tok == self.eos_id:
                        self._finish(req, "ok", None, done)
                        sl.release(int(s))
            return self._step_result(res, n_done0)
        finally:
            self._events = None

    def _record_dispatch(self, res: StepResult, kind: str,
                         n_new: np.ndarray) -> None:
        """Fill the step record of the dispatch about to run: its kind,
        each slot's lane length and fresh tokens, the pool's pages."""
        res.kind = kind
        res.starts = self.slots.lengths.copy()
        res.n_new = n_new.copy()
        if self.paged:
            res.pages_in_use = self.slots.pool.pages_in_use()

    def finish_run(self) -> List[Request]:
        """Close the stepping session: build ``decode_stats`` from the
        session's counters, reset the per-session state, and return every
        request that reached a terminal status (completion order) — what
        the old monolithic ``run`` returned."""
        st = self._session()  # an idle session still reports + drains
        sl = self.slots
        inj = self._inj
        done = st.done
        # Inter-token latency in modeled device tokens: gaps between each
        # request's consecutive emission stamps (see _emit). Deterministic
        # like the TTFT device_tokens metric — the trace benchmark's gated
        # itl_p50/itl_p99 source.
        itl = [b - a
               for r in done
               for a, b in zip(getattr(r, "_token_dev", []),
                               getattr(r, "_token_dev", [])[1:])]
        self.decode_stats = {
            "steps": st.steps,
            "decoded_tokens": st.decoded_tokens,
            "slot_utilization": (st.active_slot_steps
                                 / max(st.steps * self.num_slots, 1)),
            "kv_blocks_visited": st.blocks_visited,
            "kv_blocks_dense": st.blocks_dense,
            "kv_block_ratio": st.blocks_visited / max(st.blocks_dense, 1),
            "paged": self.paged,
            "preemptions": st.preemptions,
            # Footprint analogue of kv_block_ratio: mean fraction of the
            # page pool actually holding tokens (contiguous lanes allocate
            # everything up front — ratio 1.0 by definition).
            "kv_pages_total": sl.pool.total_pages if self.paged else 0,
            "kv_memory_ratio": (
                st.pages_used_steps / max(st.steps * sl.pool.total_pages, 1)
                if self.paged else 1.0),
            # Prefix sharing: fraction of admitted prompt tokens whose KV
            # came from shared pages (no recompute, no rewrite), and the
            # number of page mappings the prefix cache served.
            "prefix_hit_ratio": (self._shared_tokens
                                 / max(self._prompt_tokens, 1)),
            "pages_shared": self._pages_shared,
            # Estimated HBM bytes moved per decoded token (weights streamed
            # once per step + KV blocks actually visited) — the serving
            # analogue of the paper's EMA accounting. Gated by
            # tools/check_bench.py: compressed serving must move strictly
            # fewer bytes than dense at equal tokens.
            "weight_format": self.model.cfg.weight_format,
            "weight_bytes_per_step": self._weight_stream_bits / 8.0,
            "weight_bytes_per_token": (st.steps
                                       * self._weight_stream_bits / 8.0
                                       / max(st.decoded_tokens, 1)),
            "kv_bytes_per_token": st.kv_bytes / max(st.decoded_tokens, 1),
            # Tensor-parallel decode: each rank streams only its
            # kv_heads / tp_ranks head-slice of every visited page, so
            # per-rank KV traffic scales ~1/N with the mesh (gated by
            # tools/check_bench.py via the decode/sharded row).
            "tp_ranks": self._tp,
            "kv_bytes_per_token_per_rank": (
                st.kv_bytes / max(st.decoded_tokens, 1) / self._tp),
            "bytes_per_token": ((st.steps * self._weight_stream_bits / 8.0
                                 + st.kv_bytes)
                                / max(st.decoded_tokens, 1)),
            # Failure-model counters (docs/serving.md): terminal statuses
            # since the last run (submit-time sheds/rejects included),
            # preemption recovery split, audit trips (0 on any run that
            # returned — an audit failure raises), and the fault
            # injector's tally for chaos-test reconciliation.
            "status_counts": dict(self._counts),
            "completed_ok": self._counts["ok"],
            "shed": self._counts["shed"],
            "rejected": self._counts["rejected"],
            "timed_out": self._counts["timed_out"],
            "failed": self._counts["failed"],
            "cancelled": self._counts["cancelled"],
            "preemptions_recovered": st.preempt_recovered,
            "audit_violations": self._audit_violations,
            "faults_injected": dict(inj.counts) if inj is not None else {},
            "clock_ticks": self._clock,
            "device_time": self._device_time,
            # Cross-replica prefix sharing: pages restored into the local
            # pool from the fleet index's host tier this session.
            "fleet_restored_pages": self._fleet_restored_pages,
            "itl_p50": float(np.percentile(itl, 50)) if itl else 0.0,
            "itl_p99": float(np.percentile(itl, 99)) if itl else 0.0,
            # Mixed-step accounting + per-request time-to-first-token:
            # wall seconds since submit, deterministic clock ticks, and
            # ``device_tokens`` — modeled device time (each jitted dispatch
            # costs its sequence width; batch rows are free) between submit
            # and the first token. ``device_tokens`` is the benchmark's
            # gated ttft_p50/ttft_p99 sidecar source: deterministic and
            # dispatch-shaped, where wall at smoke scale just measures host
            # FLOPs and clock ticks hide whole-prompt admission sweeps.
            "mixed": self.mixed,
            "prefill_budget": self.prefill_budget,
            "mixed_steps": st.mixed_steps,
            "prefill_chunk_tokens": st.chunk_tokens,
            # The chunk step's engagement: the columns it paid (R x S a
            # chunk step; prefill_chunk_tokens over this is the paid
            # columns' fill), and the queued requests, counted each
            # iteration, that found a free slot but no row.
            "mixed_columns": st.mixed_columns,
            "chunk_waits": st.chunk_waits,
            "ttft": {
                r.rid: {"wall_s": r.first_token_s - (
                            r.submitted_s if r.submitted_s is not None
                            else r.first_token_s),
                        "clock": int(r._ttft_clock),
                        "device_tokens": int(getattr(r, "_ttft_dev", 0))}
                for r in done if r.first_token_s is not None},
        }
        self._counts = {s: 0 for s in TERMINAL_STATUSES}
        self._inj = None
        self._st = None
        return done

    # ------------------------------------------------------------------

    def _ensure_pages(self, done: List[Request]) -> Tuple[int, int]:
        """Make every active slot's next write position writable (oldest
        request first): allocate missing pages, copy-on-write pages other
        slots still share (a ring lane wrapping into the shared prefix),
        and unpublish sole-owner pages the prefix cache still indexes —
        a shared or published page is never mutated in place. When the
        pool is dry (free list empty *and* no refcount-0 retained pages
        left to evict — or a fault injector forces the failure),
        preempt-and-requeue the *youngest* active request until the write
        fits. The oldest request can always make progress: preempting
        every other holder drives its pages' refcounts to one.

        Returns ``(recovered, escalated)`` preemption counts: recovered
        victims were requeued; escalated ones exhausted their preemption
        budget (or, with no victim left to evict under a hard
        ``page_cap``, could not grow at all) and were failed."""
        sl, pool = self.slots, self.slots.pool
        inj = self._inj
        n_rec = n_esc = 0
        order = sorted(np.flatnonzero(sl.active),
                       key=lambda s: self._admit_seq[s])
        for s in order:
            if not sl.active[s]:
                continue  # preempted as a victim earlier in this pass
            suppress = False  # stop injecting once s is the sole survivor
            while True:
                injected = (not suppress and inj is not None
                            and inj.alloc_fail())
                if injected:
                    ok, copies = False, []
                else:
                    ok, copies = pool.make_writable(int(s),
                                                    int(sl.lengths[s]))
                if ok:
                    if copies:
                        sl.copy_pages(copies)
                    break
                victims = np.flatnonzero(sl.active)
                victim = int(max(victims, key=lambda v: self._admit_seq[v]))
                if victim == s and victims.size == 1:
                    if injected:
                        # An injected failure must not be fatal to the
                        # only in-flight request: retry for real.
                        suppress = True
                        continue
                    # Genuinely unrecoverable: even with every other slot
                    # evicted the pool (page_cap) cannot hold this lane's
                    # next page. Fail the request, not the engine.
                    req = sl.request[s]
                    sl.release(int(s))
                    self._finish(
                        req, "failed",
                        "page pool cannot hold the request's next page "
                        "even with every other slot evicted (page_cap too "
                        "small for its decode growth)", done)
                    n_esc += 1
                    break
                if self._preempt_or_fail(victim, done):
                    n_rec += 1
                else:
                    n_esc += 1
                if victim == s:
                    break
        return n_rec, n_esc

    def _grow_span(self, s: int, end: int,
                   done: List[Request]) -> Tuple[bool, int, int]:
        """Make lane positions ``[lengths[s], end)`` writable for a mixed
        step's chunk scatter: allocate the span's pages and copy-on-write
        any the slot still shares. Same dry-pool policy as
        :meth:`_ensure_pages` — preempt the youngest active request and
        retry (both ``alloc_prefix`` and ``make_range_writable`` are
        all-or-nothing, so a retry never observes half-applied state) —
        with one refinement: when the youngest IS the growing slot and
        others are still active, the chunk is **deferred** (``ok=False``,
        slot keeps its pages and streamed prefix, the caller zeroes this
        step's ``n_new``) instead of self-preempted — older requests
        drain and free pages within their budgets, and a decoder that
        genuinely needs a page still preempts this slot through
        ``_ensure_pages``, so deferral cannot deadlock. The sole survivor
        that still cannot grow is failed, not wedged. Returns ``(ok,
        recovered, escalated)``."""
        sl, pool = self.slots, self.slots.pool
        inj = self._inj
        n_rec = n_esc = 0
        suppress = False
        end = min(end, self.cache_len)
        while True:
            injected = (not suppress and inj is not None
                        and inj.alloc_fail())
            try:
                if injected:
                    raise RuntimeError("injected allocation failure")
                pool.alloc_prefix(s, end)
                copies = pool.make_range_writable(s, int(sl.lengths[s]),
                                                  end)
            except RuntimeError:
                victims = np.flatnonzero(sl.active)
                victim = int(max(victims,
                                 key=lambda v: self._admit_seq[v]))
                if victim == s:
                    if victims.size == 1:
                        if injected:
                            suppress = True
                            continue
                        req = sl.request[s]
                        sl.release(s)
                        self._finish(
                            req, "failed",
                            "page pool cannot hold the prefill chunk "
                            "span even with every other slot evicted "
                            "(page_cap too small for the prompt)", done)
                        n_esc += 1
                    # else: defer — this slot is the youngest, so let the
                    # older slots drain and retry the chunk next step
                    # with the streamed prefix intact.
                    return False, n_rec, n_esc
                if self._preempt_or_fail(victim, done):
                    n_rec += 1
                else:
                    n_esc += 1
                continue
            if copies:
                sl.copy_pages(copies)
            return True, n_rec, n_esc

    def _note_ttft(self, target: Request) -> None:
        """Record time-to-first-token the moment a request's FIRST output
        token lands (continuations resume with prior output, so only a
        genuine first token — len(output) == 1 — qualifies)."""
        if len(target.output) != 1 or target.first_token_s is not None:
            return
        target.first_token_s = time.perf_counter()
        target._ttft_clock = (  # type: ignore[attr-defined]
            self._clock - getattr(target, "_submit_clock", self._clock))
        target._ttft_dev = (  # type: ignore[attr-defined]
            self._device_time - getattr(target, "_submit_dev",
                                        self._device_time))

    def _note_admit(self, req: Request) -> Request:
        """Count ``req`` as admitted by this step and stamp its origin's
        first admission; returns the origin."""
        target = getattr(req, "_origin", req)
        if target.admitted_s is None:
            target.admitted_s = time.perf_counter()
        self._admitted.append(target.rid)
        return target

    def _admit_mixed(self, free: np.ndarray, cur, emitted, budget,
                     pending, pending_full, done: List[Request]) -> int:
        """Chunk-granular admission for the mixed step: claim a free slot
        per queued request (the caller passes as many free slots as the
        chunk step has free rows; FIFO, page-budget head-blocking — but the
        reservation covers only the FIRST chunk's span, so a long prompt
        never head-blocks the queue behind its whole page demand) and
        stage its prompt in ``pending`` for the chunk scheduler. No
        prefill sweep, no lane copy: the mixed step writes chunk K/V
        straight into the claimed lane. Prefix hits map their shared
        pages immediately, so the chunks stream only the suffix."""
        pool = self.slots.pool

        def probe_len(req: Request) -> int:
            hit = self._probe_req(req)
            return hit.n_shared if hit is not None else 0

        adms = self.scheduler.next_mixed(
            len(free), reserve=self._page_reserve(chunk=self._chunk_width),
            probe=probe_len if self.prefix_share else None)
        fi = 0
        n_processed = 0
        for req, _est in adms:
            n_processed += 1
            target = self._note_admit(req)
            total_budget = min(target.max_new_tokens, self.max_new)
            if len(target.output) >= total_budget:
                self._finish(target, "ok", None, done)  # nothing left
                continue
            prompt = np.asarray(req.prompt, np.int32)
            hit = self._probe_req(req) if self.prefix_share else None
            off = hit.n_shared if hit is not None else 0
            self._prompt_tokens += len(prompt)
            self._shared_tokens += off
            slot = int(free[fi])
            fi += 1
            if off:
                pool.map_shared(slot, hit)
                self._pages_shared += sum(
                    len(v) for v in hit.pages.values())
            self.slots.claim(slot, target, off)
            try:
                # Hold the first chunk's write position now (private,
                # CoW'd out of any shared tail page) so the per-iteration
                # audit's write-target invariant holds from claim on.
                pool.alloc_prefix(slot, min(off + 1, self.cache_len))
                copies = pool.make_range_writable(slot, off, off + 1) \
                    if off else []
            except RuntimeError:
                # Reservation makes this unreachable in normal operation;
                # degrade to a requeue rather than wedge the round.
                self.slots.release(slot)
                self.scheduler.requeue(req)
                break
            if copies:
                self.slots.copy_pages(copies)
            pending[slot] = prompt[off:]
            pending_full[slot] = prompt
            temp, topk, seed = self._resolve_sampling(target)
            cur[slot] = 0  # unused until the first token lands
            emitted[slot] = len(target.output)
            budget[slot] = total_budget
            self._seeds[slot] = seed
            self._temps[slot] = temp
            self._topks[slot] = topk
            self._admit_seq[slot] = self._seq
            self._seq += 1
        if n_processed:
            # One stats entry per admission round: chunk rows carry no
            # padding, so the legacy "prefill utilization" is 1 by
            # construction (rows=0 flags the sweepless mixed path).
            self.stats.append({"rows": 0, "n_requests": n_processed,
                               "utilization": 1.0})
        return n_processed

    # ------------------------------------------------------------------
    # failure hardening: lifecycle, deadlines, watchdog, audits
    # ------------------------------------------------------------------

    def _finish(self, req: Request, status: str, reason: Optional[str],
                done: List[Request]) -> None:
        """Mark ``req`` (resolving continuations to their origin) with a
        terminal status, count it, and hand it back via ``done``."""
        target = getattr(req, "_origin", req)
        target.status = status
        target.status_reason = reason
        target.finished_s = time.perf_counter()
        self._counts[status] += 1
        done.append(target)

    def _finish_terminal(self, req: Request, status: str,
                         reason: str) -> None:
        """Submit-time terminal outcome (shed / never-admissible reject):
        the request never enters the queue; it is returned — status set,
        counted — by the next :meth:`run`."""
        target = getattr(req, "_origin", req)
        target.status = status
        target.status_reason = reason
        target.finished_s = time.perf_counter()
        self._counts[status] += 1
        self._terminal.append(target)

    def _deadline(self, target: Request) -> Optional[int]:
        ttl = target.ttl_steps if target.ttl_steps is not None \
            else self.default_ttl
        if ttl is None:
            return None
        return getattr(target, "_submit_clock", 0) + int(ttl)

    def _expire(self, done: List[Request]) -> int:
        """Expire queued and in-flight requests whose deadline (in
        virtual-clock ticks since submission) has passed; returns the
        number expired. Continuations expire on their *origin's* clock —
        a preempt-requeue cycle never resets a deadline."""
        def expired(req: Request) -> bool:
            t = getattr(req, "_origin", req)
            dl = self._deadline(t)
            return dl is not None and self._clock > dl

        n = 0
        for req in self.scheduler.drop_where(expired):
            self._finish(req, "timed_out",
                         f"deadline exceeded in queue at clock tick "
                         f"{self._clock}", done)
            n += 1
        for s in np.flatnonzero(self.slots.active):
            req = self.slots.request[s]
            if expired(req):
                self.slots.release(int(s))
                self._finish(req, "timed_out",
                             f"deadline exceeded in-flight at clock tick "
                             f"{self._clock}", done)
                n += 1
        return n

    def _preempt_or_fail(self, slot: int, done: List[Request]) -> bool:
        """Preempt-and-requeue within the request's preemption budget
        (``Request.max_preemptions``, engine default
        ``max_preemptions_per_request``; None = unbounded). A request
        over budget — stuck in an admit→preempt cycle — is escalated to
        ``status="failed"`` instead of thrashing forever. Returns True
        when the victim was requeued (recoverable)."""
        req = self.slots.request[slot]
        target = getattr(req, "_origin", req)
        n = getattr(target, "_preempt_count", 0) + 1
        target._preempt_count = n  # type: ignore[attr-defined]
        limit = target.max_preemptions \
            if target.max_preemptions is not None else self.max_preempt
        if limit is not None and n > limit:
            self.slots.release(slot)
            self._finish(
                target, "failed",
                f"preemption budget exhausted ({n - 1} preempt-requeue "
                "cycles; stuck in an admit-preempt cycle)", done)
            return False
        self._preempt(slot)
        return True

    def _watchdog_escalate(self, done: List[Request]) -> None:
        """No-progress watchdog: after ``watchdog_patience`` consecutive
        iterations with nothing decoded, admitted, or expired, fail the
        queue head — whatever is blocking the FIFO — so the run loop is
        guaranteed to terminate."""
        if not self.scheduler.queue:
            return
        req = self.scheduler.queue.pop(0)
        self._finish(
            req, "failed",
            f"no-progress watchdog: queue head still not admitted after "
            f"{self.watchdog_patience} consecutive idle iterations", done)

    def _audit_step(self) -> None:
        """Opt-in per-iteration invariant audit (``Engine(audit=True)``):
        pool-wide refcount/partition/index conservation, every active
        lane's block-table bounds against its ``[lo, hi)`` occupancy, and
        the CoW write-target-is-private postcondition. Runs after page
        growth and admissions, before the decode step — the moment every
        write target must be exclusively owned."""
        sl = self.slots
        try:
            active = np.flatnonzero(sl.active)
            if self.paged:
                pool = sl.pool
                pool.check_invariants(ranks=self._tp)
                for s in active:
                    pool.check_lane_bounds(int(s), int(sl.lengths[s]))
                    pool.check_write_private(int(s), int(sl.lengths[s]))
            for s in active:
                if not 0 <= int(sl.lengths[s]) < self.cache_len:
                    raise AuditError(
                        "slot-length-bounds",
                        f"slot {int(s)} length {int(sl.lengths[s])} "
                        f"outside [0, {self.cache_len})")
        except AuditError:
            self._audit_violations += 1
            raise

    def _resolve_sampling(self, target: Request
                          ) -> Tuple[float, int, np.uint32]:
        """Resolve a request's effective (temperature, top_k, seed) at
        admission: its optional :class:`SamplingParams` override the
        engine-wide defaults field by field (``top_k=0`` explicitly
        disables truncation); the seed precedence is
        ``SamplingParams.seed`` > ``Request.seed`` > base_seed + rid —
        the same derivation the engine always used, so legacy runs are
        bit-identical."""
        sp = target.sampling
        temp = self.temperature if sp is None or sp.temperature is None \
            else float(sp.temperature)
        topk = self.top_k if sp is None or sp.top_k is None \
            else int(sp.top_k)
        if sp is not None and sp.seed is not None:
            seed_src = sp.seed
        elif target.seed is not None:
            seed_src = target.seed
        else:
            seed_src = self._base_seed + target.rid
        return (float(temp), int(topk or 0),
                np.uint32(int(seed_src) & 0xFFFFFFFF))

    # ------------------------------------------------------------------
    # prefix sharing: probe + hit-aware page reservation + fleet tier
    # ------------------------------------------------------------------

    def attach_fleet(self, fleet) -> None:
        """Join a cross-replica :class:`~repro.serve.pages.FleetPrefixIndex`
        (``serve/dispatch.py`` wires one across its replicas): local prefix
        publishes mirror page bytes into the fleet's host tier, and probes
        first restore any fleet-only pages into the local pool — so a hot
        prompt prefills once per fleet, and locally evicted pages remain
        restorable from host memory."""
        if not self.prefix_share:
            raise UnsupportedConfigError(
                "a fleet prefix index needs local prefix sharing: this "
                "engine has prefix_share disabled (or a non-paged / "
                "recurrent stack that cannot share)")
        if self._tp > 1:
            raise UnsupportedConfigError(
                "fleet prefix sharing reads/writes whole pages on the "
                "host and is single-rank: a KV-head-sharded cache would "
                "need per-rank page slices. Serve fleet replicas "
                "unsharded, or drop the fleet index.")
        self._fleet = fleet

    def _publish_prefix(self, slot: int, tokens) -> None:
        """Publish a freshly prefilled lane's full pages locally, then
        mirror each indexed page's bytes into the fleet tier (consecutive
        from logical page 0 — a fleet entry is only useful as part of an
        unbroken chain, exactly like the local probe's hit run)."""
        pool = self.slots.pool
        pool.publish_prefix(slot, np.asarray(tokens, np.int32))
        fleet = self._fleet
        if fleet is None:
            return
        toks = np.asarray(tokens, np.int32)
        ps = pool.page_size
        m = len(toks) // ps
        if m == 0:
            return
        digests = prefix_digests(toks, ps, m)
        for w, c in pool.classes.items():
            if len(toks) > c.width:
                continue  # wrapped ring: content not prefix-determined
            for lp in range(m):
                pg = c.index.get((lp, digests[lp]))
                if pg is None:
                    break
                if not fleet.has(w, lp, digests[lp]):
                    fleet.publish(w, lp, digests[lp],
                                  self.slots.read_page(w, pg))

    def _fleet_restore(self, tokens: np.ndarray) -> None:
        """Pull fleet-published prefix pages this pool is missing into the
        local retained tier, so the subsequent local probe hits them. A
        logical page is restored in EVERY width class or none
        (``probe_prefix`` takes the min over classes, so a partial
        restore buys nothing), and the walk stops at the first
        non-restorable page — hit runs must be consecutive."""
        fleet = self._fleet
        pool = self.slots.pool
        toks = np.asarray(tokens, np.int32)
        ps = pool.page_size
        m = len(toks) // ps
        if m == 0:
            return
        if any(len(toks) > c.width for c in pool.classes.values()):
            return  # a wrapping class can never share this prompt
        digests = prefix_digests(toks, ps, m)
        for lp in range(m):
            plan = []
            for w, c in pool.classes.items():
                if (lp, digests[lp]) in c.index:
                    continue  # already resident locally
                host = fleet.get(w, lp, digests[lp])
                if host is None or c.available() == 0:
                    return
                plan.append((w, host))
            for w, host in plan:
                pg = pool.adopt_published(w, lp, digests[lp])
                if pg is None:
                    return
                self.slots.write_page(w, pg, host)
                self._fleet_restored_pages += 1
                fleet.restored_pages += 1

    def _probe(self, prompt) -> Optional[PrefixHit]:
        """Prefix-cache lookup for a prompt (None when sharing is off or
        nothing matches). With a fleet attached, fleet-only pages are
        restored into the local pool first, so the local probe is the
        single source of truth for what a hit maps."""
        if not self.prefix_share:
            return None
        if self._fleet is not None:
            self._fleet_restore(np.asarray(prompt, np.int32))
        return self.slots.pool.probe_prefix(np.asarray(prompt, np.int32))

    def _probe_req(self, req: Request) -> Optional[PrefixHit]:
        """Memoized per-request probe: one admission is consulted up to
        three times (grouping, reservation, prefill) and a head-blocked
        queue front re-consults every engine step — re-hashing the prompt
        each time is pure waste while the prefix index is unchanged, so
        the hit is cached against ``PagePool.prefix_version``. The memo
        also keys on the pool *identity*: a Request object reused across
        engines must never replay a hit holding another pool's physical
        page ids."""
        pool = self.slots.pool
        # The fleet version rides in the memo key: a publish on another
        # replica must invalidate this replica's cached miss.
        ver = (pool.prefix_version,
               self._fleet.version if self._fleet is not None else -1)
        memo = getattr(req, "_probe_memo", None)
        if memo is not None and memo[0] is pool and memo[1] == ver:
            return memo[2]
        hit = self._probe(req.prompt)
        req._probe_memo = (pool, ver, hit)  # type: ignore[attr-defined]
        return hit

    def _page_reserve(self, chunk: Optional[int] = None):
        """Admission-control closure over the page budget, accounting for
        expected prefix-cache hits: a request with a resident prefix
        reserves only its *new* pages — lane pages minus shared ones, plus
        any shared page its writes will copy-on-write — and additionally
        pins the refcount-0 (retained) pages it will resurrect, since
        those stop being evictable the moment it maps them. Budgets are
        per width class over ``free + retained`` (retained pages are
        evictable on demand), so admission never overcommits even when an
        earlier admission in the same round evicts a probed page.

        ``chunk`` (mixed admission) caps the reserved span at the first
        prefill chunk: the mixed engine grows lanes page-by-page per step
        — preempting the youngest when the pool runs dry, exactly like
        mid-decode growth — so a long prompt does not head-block the FIFO
        behind its *whole* page demand the way a serialized admission
        sweep must. ``submit`` still rejects prompts no pool state could
        ever hold."""
        pool = self.slots.pool
        ps = pool.page_size
        avail = {w: c.available() for w, c in pool.classes.items()}

        def reserve(req: Request) -> bool:
            if self._inj is not None and self._inj.alloc_fail():
                return False  # injected pool failure: head-block this round
            L = len(req.prompt)
            span = L if chunk is None else min(L, chunk)
            hit = self._probe_req(req)
            consume = {}
            for w, c in pool.classes.items():
                need = -(-min(span + 1, c.width) // ps)
                if hit is not None:
                    shared = -(-hit.n_shared // ps)
                    writes = {(p % c.width) // ps
                              for p in range(min(hit.n_shared, span),
                                             span + 1)}
                    cow = sum(1 for lp in writes if lp < shared)
                    r0 = sum(1 for pg in hit.pages[w]
                             if c.refcount[pg] == 0)
                    consume[w] = max(0, need - shared) + cow + r0
                else:
                    consume[w] = need
            if any(n > avail[w] for w, n in consume.items()):
                return False
            for w, n in consume.items():
                avail[w] -= n
            return True

        return reserve

    def _preempt(self, slot: int) -> None:
        """Requeue the slot's request as a continuation: its prompt plus
        everything generated so far, at the queue head. Re-prefilling that
        sequence yields exactly the token the next decode step would have
        produced (greedy is deterministic; sampled decode keys on absolute
        position), so preemption is invisible in the output stream."""
        req = self.slots.request[slot]
        cont = Request(
            rid=req.rid,
            prompt=np.concatenate([np.asarray(req.prompt, np.int32),
                                   np.asarray(req.output, np.int32)]),
            max_new_tokens=req.max_new_tokens, seed=req.seed,
            sampling=req.sampling)
        cont._origin = req  # type: ignore[attr-defined]
        self.scheduler.requeue(cont)
        self.slots.release(slot)  # returns the lane's pages to the pool

    # ------------------------------------------------------------------

    def _admit(self, free: np.ndarray, cur, emitted, budget,
               done: List[Request]) -> int:
        """Prefill one round of admissions into the free slots; returns
        the number of requests processed (the run loop's progress
        signal for the no-progress watchdog)."""
        pool = self.slots.pool if self.paged else None
        # Reservation is per width class and one token ahead; assign_many
        # allocates that one-ahead page for real (kv_slots.py), and the run
        # loop grows active lanes *before* admitting, so a fresh admission
        # neither overcommits a class nor steals a page an in-flight lane
        # needs this step — it always reaches its first decode step.
        # Requests with a resident prompt prefix reserve only their net-new
        # pages (_page_reserve) and are admitted solo via the probe.
        def probe_len(req: Request) -> int:
            hit = self._probe_req(req)
            return hit.n_shared if hit is not None else 0

        groups = self.scheduler.next_admissions(
            len(free), reserve=self._page_reserve() if pool else None,
            probe=probe_len if self.prefix_share else None)
        fi = 0
        n_processed = 0
        for adm in groups:
            n_processed += len(adm.requests)
            for req in adm.requests:
                self._note_admit(req)
            logits, caches, slots_of, hits = self._prefill_admission(adm)
            logits = np.asarray(logits)
            assigns = []  # whole group lands in ONE fused lane copy
            pubs = []     # (slot, full token sequence) to publish
            for i, req in enumerate(adm.requests):
                # A requeued continuation carries its original request in
                # _origin: tokens and budgets accrue there, and the caller
                # gets the object it submitted back.
                target = getattr(req, "_origin", req)
                row, start, length, off = slots_of[i]
                total = off + length  # lane depth; off > 0 => shared prefix
                total_budget = min(target.max_new_tokens, self.max_new)
                if len(target.output) >= total_budget:
                    self._finish(target, "ok", None, done)  # nothing left
                    continue
                # Hit accounting covers every suffix prefill — including
                # requests that finish at prefill below (their prefix
                # compute was saved all the same; only the page *mappings*
                # require a slot).
                self._prompt_tokens += total
                self._shared_tokens += off
                temp, topk, seed = self._resolve_sampling(target)
                if temp > 0:
                    first = int(self._sample1(
                        jnp.asarray(logits[row, start + length - 1]),
                        jnp.asarray(seed), jnp.int32(total),
                        jnp.float32(temp), jnp.int32(topk)))
                else:
                    first = int(np.argmax(logits[row, start + length - 1]))
                self._emit(target, first)
                self._note_ttft(target)
                if len(target.output) >= total_budget or first == self.eos_id:
                    # finished at prefill; slot stays free
                    self._finish(target, "ok", None, done)
                    continue
                slot = int(free[fi])
                fi += 1
                if off:
                    # Point the fresh lane's block tables at the shared
                    # pages before assign_many allocates the remainder.
                    pool.map_shared(slot, hits[i])
                    self._pages_shared += sum(
                        len(v) for v in hits[i].pages.values())
                assigns.append((slot, target, row, start, length, off))
                if self.prefix_share:
                    pubs.append((slot, req.prompt))
                cur[slot] = first
                emitted[slot] = len(target.output)
                budget[slot] = total_budget
                self._seeds[slot] = seed
                self._temps[slot] = temp
                self._topks[slot] = topk
                self._admit_seq[slot] = self._seq
                self._seq += 1
            self.slots.assign_many(assigns, caches)
            # Publish after the fused copy: only then do the lane's full
            # pages hold their final, content-addressable bytes.
            for slot, toks in pubs:
                self._publish_prefix(slot, np.asarray(toks, np.int32))
        return n_processed

    def _prefill_admission(self, adm: Admission):
        """Run one prefill sweep; returns (all-position logits, filled
        caches, per-request (row, start, length, offset), per-request
        prefix hits). ``offset`` is nonzero only for a shared-prefix row:
        that request's first ``offset`` tokens came from mapped pages and
        only its suffix rode the sweep."""
        if adm.shared_prefix:
            # Re-probe at prefill time: the scheduler's estimates may be
            # stale (pages evicted since) or short (pages published by an
            # earlier group this round). Stale rows simply ride the same
            # sweep as cold rows with a zero-length prefix. (The memo
            # makes re-probing free while the prefix index is unchanged.)
            reqs = adm.requests
            hits = [self._probe_req(r) for r in reqs]
            if len(reqs) == 1 and hits[0] is None:
                # Solo full miss: degrade to a cold chunked prefill (the
                # legacy path; nothing to gather).
                adm = Admission(requests=reqs,
                                chunks=chunk_prompt(reqs[0].prompt,
                                                    self.max_len))
            else:
                batch, ids, plen, slots_of = self._shared_batch_many(
                    reqs, hits)
                pk, pv = self.slots.gather_prefix(ids)
                logits, caches = self._prefill_shared(
                    self.params, batch, pk, pv, plen)
                rows, width = batch["inputs"].shape
                self._device_time += width
                self.stats.append({
                    "rows": len(reqs), "n_requests": len(reqs),
                    "utilization": (sum(l for _, _, l, _ in slots_of)
                                    / max(rows * width, 1))})
                return logits, caches, slots_of, hits
        if adm.packed is not None:
            packed = adm.packed
            rows = packed.rows
            # Pad the row count to a power of two: bounds the set of packed
            # prefill shapes (and therefore XLA compiles) to log2(max_rows)
            # variants; padding rows ride segment id 0 => fully masked.
            pad_rows = 1 << (rows - 1).bit_length()
            pad = ((0, pad_rows - rows), (0, 0))
            batch = {"inputs": jnp.asarray(np.pad(packed.tokens, pad)),
                     "positions": jnp.asarray(np.pad(packed.positions, pad)),
                     "seg_ids": jnp.asarray(np.pad(packed.segment_ids, pad))}
            slots_of = [(r, s, l, 0) for r, s, l in packed.request_slots]
        elif adm.chunks is not None:  # solo long prompt
            prompt = np.concatenate(adm.chunks)
            width = len(adm.chunks) * self.max_len
            tokens = np.zeros((1, width), np.int32)
            seg = np.zeros((1, width), np.int32)
            L = len(prompt)
            tokens[0, :L] = prompt
            seg[0, :L] = 1
            batch = {"inputs": jnp.asarray(tokens),
                     "positions": jnp.asarray(
                         np.arange(width, dtype=np.int32)[None]),
                     "seg_ids": jnp.asarray(seg)}
            slots_of = [(0, 0, L, 0)]
            rows = 1
        else:  # row-per-request (recurrent stacks), right-aligned
            batch, slots_of, rows = self._rows_batch(adm)
        logits, caches = self._prefill(self.params, batch)
        self._device_time += int(batch["inputs"].shape[1])
        self.stats.append({"rows": rows, "n_requests": len(adm.requests),
                           "utilization": adm.utilization})
        return logits, caches, slots_of, [None] * len(adm.requests)

    def _shared_batch_many(self, reqs: List[Request],
                           hits: List[Optional[PrefixHit]]):
        """Batched suffix-prefill layout: one row per request, each with
        its OWN resident prefix — row i carries tokens
        ``prompt[n_i:]`` at absolute positions (``n_i = 0`` for stale
        probes: a cold row in the same sweep), padded to the widest
        suffix's ``max_len`` multiple; rows pad to a power of two (padding
        rows are fully masked via segment ids). The prefixes ride as
        2-D per-class page-id arrays for
        :meth:`SlotKVCache.gather_prefix` (``FREE`` padding clamps to
        garbage the sweep masks) plus the per-row prefix lengths the
        layers' ``prefix_kv`` masking broadcasts over. All paddings bound
        the set of compiled suffix shapes. Returns
        ``(batch, ids, plen, slots_of)``."""
        pool = self.slots.pool
        ns = [h.n_shared if h is not None else 0 for h in hits]
        prompts = [np.asarray(r.prompt, np.int32) for r in reqs]
        sufs = [len(p) - n for p, n in zip(prompts, ns)]
        width = max(-(-s // self.max_len) * self.max_len for s in sufs)
        R = len(reqs)
        pad_rows = 1 << (R - 1).bit_length()
        tokens = np.zeros((pad_rows, width), np.int32)
        seg = np.zeros((pad_rows, width), np.int32)
        pos = np.zeros((pad_rows, width), np.int32)
        slots_of = []
        for i, (prompt, n, suf) in enumerate(zip(prompts, ns, sufs)):
            tokens[i, :suf] = prompt[n:]
            seg[i, :suf] = 1
            pos[i, :suf] = np.arange(n, len(prompt), dtype=np.int32)
            slots_of.append((i, 0, suf, n))
        batch = {"inputs": jnp.asarray(tokens),
                 "positions": jnp.asarray(pos),
                 "seg_ids": jnp.asarray(seg)}
        # Padded prefix width: the widest row's prefix, floored at one
        # max_len block so an all-stale group still traces a valid shape.
        np_pad = max(max(-(-n // self.max_len) * self.max_len
                         for n in ns), self.max_len)
        n_pages = -(-np_pad // pool.page_size)
        ids = {}
        for w, c in pool.classes.items():
            padded = np.full((pad_rows, n_pages), c.FREE, np.int32)
            for i, h in enumerate(hits):
                if h is not None:
                    padded[i, :len(h.pages[w])] = h.pages[w]
            ids[w] = padded
        plen = np.zeros(pad_rows, np.int32)
        plen[:R] = ns
        return batch, ids, jnp.asarray(plen), slots_of

    def _rows_batch(self, adm: Admission):
        """Row-per-request prefill layout for stacks with recurrent state:
        each request rides its own row, **right-aligned**, so the row's
        end-of-sequence state (the only thing a recurrent prefill cache
        stores) is exactly the request's state. Leading padding carries
        segment id 0: attention masks it out and the recurrent blocks treat
        it as identity updates (models/rglru.py, models/ssd.py), so the
        result is bit-equivalent to prefilling each request alone."""
        width = adm.row_width
        q = self._ssd_chunk
        if q is not None and width > q and width % q:
            width = ((width + q - 1) // q) * q  # SSD scans fixed chunks
            adm.row_width = width  # keep the utilization stat honest
        rows = len(adm.requests)
        pad_rows = 1 << (rows - 1).bit_length()  # bounds compile variants
        tokens = np.zeros((pad_rows, width), np.int32)
        seg = np.zeros((pad_rows, width), np.int32)
        pos = np.zeros((pad_rows, width), np.int32)
        slots_of = []
        for i, req in enumerate(adm.requests):
            L = len(req.prompt)
            start = width - L
            tokens[i, start:] = req.prompt
            seg[i, start:] = 1
            pos[i, start:] = np.arange(L)
            slots_of.append((i, start, L, 0))
        batch = {"inputs": jnp.asarray(tokens),
                 "positions": jnp.asarray(pos),
                 "seg_ids": jnp.asarray(seg)}
        return batch, slots_of, rows
