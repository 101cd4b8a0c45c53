"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file that this module finds by its name in ``BENCHMARK.json``:

* ``configs/<config>.json`` (the ``file`` of a ``configs`` entry): the
  published sizes, how the program builds the model, the page size and the
  comparison's limit;
* ``traffic/<mix>.json``: the generator (closed loop or Poisson), the
  length distributions and the engine's sizing for that traffic;
* ``metrics/<metric>.py``: a ``read(ctx)`` that returns the metric's value,
  or ``None`` where the run holds nothing to read.

The window drives ``Engine.step()`` on an ``Engine`` built through the
launcher's ``build_model``, with the benchmark's own seeded weights. From
the program the harness takes the system under test, its counters and its
kernel names, nothing else.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import re
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import loadgen  # noqa: E402
import work  # noqa: E402

__all__ = ["NoChip", "load_cell", "metric_reader", "peaks_for", "run",
           "serve_window", "start", "Context"]

# Request statuses that count as failed in the result line.
FAILED = ("failed", "rejected", "shed", "timed_out", "cancelled")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer than the cell asks for."""


def log(*a):
    print(*a, flush=True)


# ---------------------------------------------------------------------------
# the data files
# ---------------------------------------------------------------------------


def _read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration file,
    its traffic file and the metrics it reports, by name."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config = _read_json(root / entry["file"])
    here = root / HERE.relative_to(ROOT)
    traffic = _read_json(here / "traffic" / f"{cell['traffic']}.json")

    def mine(m):
        return name in m.get("workloads", [name])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)],
            "run_seconds": bench["run_seconds"]}


def metric_reader(name: str, root: pathlib.Path = ROOT) -> Callable:
    """``read`` of ``metrics/<name>.py``."""
    path = root / HERE.relative_to(ROOT) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str) -> dict:
    table = _read_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have {sorted(k for k in table if k != 'source')})")
    return table[kind]


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Rec:
    """One request as the client sees it (host clock, seconds)."""
    spec: loadgen.Spec
    due: float
    submitted: float
    stamps: List[float] = dataclasses.field(default_factory=list)
    status: Optional[str] = None
    request: object = None


@dataclasses.dataclass
class StepRec:
    """What one engine step processed, from the slot table before and
    after it: ``rows`` are ``(start, n)`` token spans per sequence."""
    kind: str
    rows: List[Tuple[int, int]]
    emitted: int


def _counters(engine) -> dict:
    st = engine._st
    keys = ("steps", "mixed_steps", "chunk_tokens", "decoded_tokens",
            "active_slot_steps", "pages_used_steps", "preemptions")
    return {k: (getattr(st, k) if st is not None else 0) for k in keys}


def _rows(before, after, finished_ids) -> List[Tuple[int, int]]:
    """Token spans processed by one step, per slot. A slot that kept its
    request advanced by what the step wrote; a slot that took a request in
    this step wrote from 0 (no prefix is shared in these mixes); a request
    that finished in the step decoded one token."""
    act0, len0, req0 = before
    act1, len1, req1 = after
    rows = []
    for s in range(len(act0)):
        same = act0[s] and act1[s] and req1[s] is req0[s]
        if same:
            if len1[s] > len0[s]:
                rows.append((int(len0[s]), int(len1[s] - len0[s])))
        elif act1[s]:
            rows.append((0, int(len1[s])))
        elif act0[s] and id(req0[s]) in finished_ids:
            rows.append((int(len0[s]), 1))
    return rows


def _slots(engine):
    sl = engine.slots
    return sl.active.copy(), sl.lengths.copy(), list(sl.request)


def serve_window(engine, traffic: dict, seed: int, seconds: float,
                 vocab: int, *, trace_dir: Optional[str] = None,
                 on_step: Optional[Callable] = None) -> dict:
    """Offer the traffic to ``engine`` for ``seconds`` and record what the
    client sees. Returns the records, the steps and the window's bounds.
    ``on_step(t)`` is called after each step (the knee sweep reads the
    queue there)."""
    import jax

    from repro.serve import Request

    gen = loadgen.make_generator(traffic, seed, vocab, seconds)
    recs: Dict[int, Rec] = {}
    steps: List[StepRec] = []
    lateness: List[float] = []
    ann = jax.profiler.TraceAnnotation

    def submit(specs, t0, now):
        for sp in specs:
            req = Request(rid=sp.index, prompt=sp.prompt,
                          max_new_tokens=sp.max_new)
            recs[sp.index] = Rec(sp, t0 + sp.due, now, request=req)
            lateness.append(now - (t0 + sp.due))
            engine.submit(req)

    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    c0 = _counters(engine)
    with ann("bench.window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        carry = gen.start()
        while True:
            now = time.perf_counter()
            if now >= deadline:
                break
            with ann("bench.submit"):
                submit(carry + gen.due(now - t0), t0, now)
                carry = []
            if not engine.has_work():
                nd = gen.next_due()
                if nd is None:
                    raise RuntimeError("traffic ran dry inside the window")
                with ann("bench.wait"):
                    time.sleep(max(0.0, min(t0 + nd, deadline)
                                   - time.perf_counter()))
                continue
            before = _slots(engine)
            m0 = engine._st.mixed_steps if engine._st is not None else 0
            with ann("bench.step"):
                res = engine.step()
            t = time.perf_counter()
            with ann("bench.record"):
                for req, _tok in res.emitted:
                    recs[req.rid].stamps.append(t)
                for req in res.finished:
                    recs[req.rid].status = req.status
                steps.append(StepRec(
                    kind=("mixed" if engine._st.mixed_steps > m0
                          else "decode"),
                    rows=_rows(before, _slots(engine),
                               {id(r) for r in res.finished}),
                    emitted=len(res.emitted)))
                carry = gen.completed(t - t0, len(res.finished))
                if on_step is not None:
                    on_step(t)
        t_end = time.perf_counter()
    c1 = _counters(engine)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return {"t0": t0, "t_end": t_end, "seconds": seconds, "recs": recs,
            "steps": steps, "lateness": lateness,
            "counters": {k: c1[k] - c0[k] for k in c0}}


def end_to_end(win: dict, names: Sequence[str] = ()) -> dict:
    """The client-side metrics of one window. ``tokens_per_s``: output
    tokens over the whole window's seconds. ``ttft_p<q>_s``: the q-th
    percentile of time to first token over the requests due in the window,
    from the due time; a request still waiting enters at its age at the
    window's end. ``itl_p<q>_s``: the q-th percentile of the gaps between
    consecutive tokens of a request, over all gaps in the window."""
    t0, t_end = win["t0"], win["t_end"]
    recs = list(win["recs"].values())
    tokens = sum(len(r.stamps) for r in recs)
    ttft = [(r.stamps[0] if r.stamps else t_end) - r.due for r in recs]
    gaps = np.concatenate([np.diff(r.stamps) for r in recs
                           if len(r.stamps) > 1] or [np.zeros(0)])
    out = {"tokens_per_s": tokens / (t_end - t0)}
    for name in names:
        m = re.fullmatch(r"(ttft|itl)_p(\d+)_s", name)
        if m:
            vals = ttft if m.group(1) == "ttft" else gaps
            if len(vals):
                out[name] = float(np.percentile(vals, int(m.group(2))))
    out["_counts"] = {"requests": len(recs), "tokens": tokens,
                      "gaps": int(gaps.size)}
    return out


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader can read: the window's steps and
    counters (host clock), the reduced trace (or ``None``), the shapes
    (``work.Shape``), the chip's peaks and the engine's sizing."""
    shape: work.Shape
    steps: List[StepRec]
    counters: dict
    window_s: float
    trace: object
    peaks: dict
    chips: int
    num_slots: int
    chunk_width: int
    total_pages: int

    def flops(self) -> float:
        """Operations the window's processed tokens need (``work.py``)."""
        return sum(work.step_flops(self.shape, s.rows, s.emitted)
                   for s in self.steps)

    def mfu(self) -> Optional[float]:
        if not self.steps or self.window_s <= 0:
            return None
        return 100.0 * self.flops() / (
            self.window_s * self.chips * self.peaks["bf16_flops_per_s"])

    def idle_share(self) -> Optional[float]:
        if self.trace is None:
            return None
        v = self.trace.idle_share()
        return None if v is None else 100.0 * v

    def step_ms(self, kind: str) -> Optional[float]:
        if self.trace is None:
            return None
        runs = self.trace.module_runs(f"{kind}_fn")
        return 1e3 * float(np.mean(runs)) if runs else None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def _device_check(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (backend {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def start(chips: int, require_tpu: bool = True):
    """The chips a cell asks for, with JAX's persistent compilation cache
    on where they are TPUs. Returns the devices and the cache's
    directory."""
    import jax
    devs = _device_check(chips, require_tpu)
    cache = "off"
    if devs[0].platform == "tpu":
        from repro.launch.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devs, cache


def _peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


class CompileCounter:
    """Counts, while on, the programs traced, read from the persistent
    cache and compiled by the backend."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traced",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache reads",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        import jax
        self.on = False
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)

        def listener(event, duration, **kw):
            if self.on and event in self.EVENTS:
                self.counts[self.EVENTS[event]] += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


def build(config: dict, traffic: dict, seed: int, chips: int, devs):
    """The model, its seeded weights and the engine of one cell."""
    import jax
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import build_model
    from repro.serve import Engine, EngineConfig

    import weights as W

    prog = config["program"]
    model = build_model(prog["arch"], prog.get("variant", "full"),
                        **prog.get("overrides", {}))
    mesh = make_local_mesh(1, chips) if chips > 1 else None
    shardings = None
    if mesh is not None:
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        shardings = shd.named(shd.param_specs(shapes, mesh), mesh)
    params = W.make_weights(model, seed, shardings)
    jax.block_until_ready(params)
    fields = dict(prog.get("engine", {}))
    fields.update(traffic["engine"])
    engine = Engine(model, params, config=EngineConfig(**fields), mesh=mesh)
    return model, params, engine, mesh


def warm_up(engine, traffic: dict, seed: int, vocab: int) -> None:
    """Compile the two step programs this traffic drives (the mixed step
    and the decode step, greedy) with two short requests, then reset the
    engine's session."""
    from repro.serve import Request
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    n = min(int(traffic["prompt_tokens"]["low"]), engine.max_prompt_len)
    for i in range(2):
        engine.submit(Request(rid=-1 - i, prompt=rng.integers(
            0, vocab, n, dtype=np.int32), max_new_tokens=3))
    while engine.has_work():
        engine.step()
    engine.finish_run()


def sample_for_check(recs: Dict[int, Rec], seed: int, tokens: int,
                     max_requests: int) -> List[Rec]:
    """Finished requests to compare, drawn from the seed: the longest, then
    others at random until ``tokens`` served tokens or ``max_requests``."""
    done = [r for r in recs.values() if r.status == "ok"]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.spec.prompt) + len(r.request.output),
                             r.spec.index))
    picked = [done.pop()]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11])
    for i in rng.permutation(len(done)):
        if (len(picked) >= max_requests
                or sum(len(r.request.output) for r in picked) >= tokens):
            break
        picked.append(done[i])
    return picked


def compare(config: dict, traffic: dict, params, win: dict, seed: int, *,
            control: bool = False, ref=None) -> Tuple[dict, bool, dict]:
    """The comparison that decides ``correct``: a sample of the window's
    finished requests drawn from the seed (``sample_for_check``), each run
    once through the plain reference over its prompt and served tokens; the
    widest gap by which a served token's logit lies below the reference's
    best, the failed requests and the served tokens checked, each against
    its limit. With ``control`` the tokens judged are those the reference
    computed in float8 ranks first at the same positions (the control,
    which the limit must fail). Returns the checks, ``correct`` and the
    widest sound and control gaps."""
    import reference
    chk = {**traffic["check"], **config["check"]}
    eng = traffic["engine"]
    if ref is None:
        ref = reference.Reference(
            config, eng["max_prompt_len"] + eng["max_new_tokens"],
            eng["max_new_tokens"])
    t = time.perf_counter()
    picked = sample_for_check(win["recs"], seed, chk["tokens"],
                              chk["max_requests"])
    sound, ctl = [], []
    for r in picked:
        if control:
            g, c = ref.control_gaps(params, r.spec.prompt, r.request.output)
            ctl.append(c)
        else:
            g = ref.gaps(params, r.spec.prompt, r.request.output)
        sound.append(g)
    judged = ctl if control else sound
    scored = int(sum(g.size for g in judged))
    worst = max((float(g.max()) for g in judged if g.size), default=None)
    log(f"reference over {len(picked)} requests, {scored} served tokens, "
        f"in {time.perf_counter() - t:.1f} s"
        + (" (control: float8 reference in the program's place)"
           if control else ""))
    n_failed = sum(r.status in FAILED for r in win["recs"].values())
    checks = {"max_logit_gap": (worst, float(chk["logit_gap_limit"])),
              "failed_requests": (n_failed, 0),
              "checked_tokens": (scored, int(chk["min_tokens"]))}
    correct = (worst is not None and np.isfinite(worst)
               and worst <= checks["max_logit_gap"][1] and n_failed == 0
               and scored >= checks["checked_tokens"][1])

    def widest(gs):
        return max((float(g.max()) for g in gs if g.size), default=None)

    return checks, bool(correct), {"sound_max": widest(sound),
                                   "control_max": widest(ctl),
                                   "requests": len(picked)}


def free_engine(engine) -> None:
    """Delete the page pool now; the engine's jitted steps close over it,
    so the garbage collector alone would free it late."""
    import jax
    for x in jax.tree.leaves(engine.slots.caches):
        x.delete()


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: Optional[float] = None, require_tpu: bool = True,
        root: pathlib.Path = ROOT, config_override: Optional[dict] = None,
        traffic_override: Optional[dict] = None,
        patch: Optional[Callable] = None,
        control: bool = False) -> Tuple[dict, List[str]]:
    """One run of one cell. Returns the result line (a dict) and the lines
    naming each compared number beside its limit. ``config_override``,
    ``traffic_override``, ``patch`` (called with the engine before the
    window) and ``control`` (see ``compare``) serve the tests; a run off
    the TPU reports no metric."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_cell(cell_name, root)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    if config_override:
        config = {**config, **config_override}
    if traffic_override:
        traffic = {**traffic, **traffic_override}
    chips = int(cell["chips"])

    devs, cache = start(chips, require_tpu)
    on_tpu = devs[0].platform == "tpu"
    kind = devs[0].device_kind
    peaks = peaks_for(kind) if on_tpu else None
    log(f"devices: {len(devs)} x {kind} ({devs[0].platform}); compile "
        f"cache {cache}")

    t = time.perf_counter()
    model, params, engine, mesh = build(config, traffic, seed, chips, devs)
    vocab = model.cfg.vocab_size
    want = config.get("decode_attn")
    if on_tpu and want and engine.decode_attn != want:
        raise RuntimeError(f"engine decode_attn={engine.decode_attn}, "
                           f"want {want}")
    log(f"built model {model.cfg.name} ({model.cfg.n_layers} layers) and "
        f"engine: slots {engine.num_slots}, chunk {engine._chunk_width}, "
        f"cache_len {engine.cache_len}, pages {engine.slots.pool.total_pages}"
        f" of {engine.page_size}, decode_attn {engine.decode_attn} in "
        f"{time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    warm_up(engine, traffic, seed, vocab)
    log(f"warm-up {time.perf_counter() - t:.1f} s")
    if patch is not None:
        patch(engine)

    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    gc.collect()
    counter.on = True
    setup_s = time.perf_counter() - t_start
    try:
        win = serve_window(engine, traffic, seed, seconds, vocab,
                           trace_dir=trace_dir)
        counter.on = False
        e2e = end_to_end(win, [m["name"] for m in spec["end_to_end"]])
        late = np.asarray(win["lateness"])
        log(f"window {win['t_end'] - win['t0']:.3f} s: "
            f"{len(win['steps'])} steps "
            f"({win['counters']['mixed_steps']} mixed), "
            f"{e2e['_counts']['tokens']} tokens, "
            f"{e2e['_counts']['requests']} requests, "
            f"{e2e['_counts']['gaps']} token gaps, preemptions "
            f"{win['counters']['preemptions']}")
        log(f"compilations in the window: {counter.counts['compiled']} "
            f"({counter.counts})")
        if late.size:
            log(f"generator lateness (submit - due): mean "
                f"{late.mean() * 1e3:.3f} ms, max {late.max() * 1e3:.3f} ms "
                f"over {late.size} requests")
        mem = _peak_bytes(devs) if on_tpu else 0
        num_slots, chunk = engine.num_slots, engine._chunk_width
        total_pages = engine.slots.pool.total_pages
        free_engine(engine)
        del engine
        gc.collect()

        reduced = None
        if trace_dir is not None:
            import trace_reduce
            t = time.perf_counter()
            reduced = trace_reduce.Reduced(trace_reduce.read_xplane(
                trace_reduce.find_xplane(trace_dir)))
            log(f"trace reduced in {time.perf_counter() - t:.1f} s")
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    recs = list(win["recs"].values())
    checks, correct, _ = compare(config, traffic, params, win, seed,
                                 control=control)
    n_failed = checks["failed_requests"][0]

    metrics = {}
    if on_tpu:
        wanted = spec["per_layer"] if trace else spec["end_to_end"]
        if trace:
            ctx = Context(
                shape=work.Shape.from_config(config), steps=win["steps"],
                counters=win["counters"], window_s=win["t_end"] - win["t0"],
                trace=reduced, peaks=peaks, chips=chips,
                num_slots=num_slots, chunk_width=chunk,
                total_pages=total_pages)
            for m in wanted:
                v = metric_reader(m["name"], root)(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
        else:
            for m in wanted:
                v = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s
    result = {"correct": bool(correct), "attempted": len(recs),
              "failed": int(n_failed), "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    lines = [f"check {k}: {v} (limit {lim}, "
             f"{'at least' if k == 'checked_tokens' else 'at most'})"
             for k, (v, lim) in checks.items()]
    return result, lines

