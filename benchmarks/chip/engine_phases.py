#!/usr/bin/env python3
"""Split one cell's window by Engine.step's phases and the step programs'
name scopes, from a profiler trace (``engine_trace.py``).

  python3 benchmarks/chip/engine_phases.py --workload qwen1.5-4b.decode \
      --seed 5 --seconds 45 [--fixture decode_trace_engine.json.gz]

One TPU. Builds and warms the cell as ``run.py`` does, then traces one
window and prints one JSON object: the device's idle gaps named by engine
phase, host and phase milliseconds per decode and mixed step, device
milliseconds per step under the ``kv_write`` and ``attn_kernel`` scopes,
the scope paths of the longest operations, queue wait from the request
stamps, the mixed kernel's roofline share from the step records, and the
cost of one span with no trace running. ``--fixture`` then records a
short window (1.2 s, the seed plus one, after the first window's requests
are cancelled) for the CPU tests: the compact record of
``trace_reduce.read_xplane`` with the engine's spans and scopes, the step
records and the request stamps.
"""
import argparse
import dataclasses
import gc
import gzip
import json
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


# Enter/exit pairs timed for the cost of one span with no trace running.
SPAN_LOOPS = 100_000


def span_cost_us() -> float:
    """Microseconds of one span's enter and exit with no trace running."""
    from jax.profiler import TraceAnnotation
    t = time.perf_counter()
    for _ in range(SPAN_LOOPS):
        with TraceAnnotation("engine.step"):
            pass
    return (time.perf_counter() - t) / SPAN_LOOPS * 1e6


def _record(res) -> dict:
    return {"kind": res.kind,
            "starts": None if res.starts is None else res.starts.tolist(),
            "n_new": None if res.n_new is None else res.n_new.tolist(),
            "pages_in_use": res.pages_in_use}


def traced_window(engine, traffic, seed, seconds, vocab):
    """One traced window, as the harness runs it. Returns the window, the
    step records and the directory that holds its trace (the caller
    reduces it with ``reduce_trace`` and removes it)."""
    import harness

    results = []
    step = engine.step

    def keep(*a, **kw):
        res = step(*a, **kw)
        results.append(_record(res))
        return res

    engine.step = keep
    tmp = tempfile.mkdtemp(prefix="bench_phases_")
    gc.collect()
    try:
        win = harness.serve_window(engine, traffic, seed, seconds, vocab,
                                   trace_dir=tmp)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    finally:
        del engine.step
    return win, results, tmp


def reduce_trace(tmp, programs):
    """The compact record and the engine's spans and scopes of the trace
    in ``tmp``, which is then removed."""
    import engine_trace
    import trace_reduce

    try:
        path = trace_reduce.find_xplane(tmp)
        return (trace_reduce.read_xplane(path),
                engine_trace.read_engine(path, programs))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _stamps(win) -> dict:
    return {rid: [r.request.submitted_s, r.request.admitted_s,
                  r.request.first_token_s, r.request.finished_s]
            for rid, r in win["recs"].items()}


def summary(spec, win, rec, extra, results, peaks, engine) -> dict:
    import engine_trace
    import harness
    import trace_reduce
    import work

    reduced = trace_reduce.Reduced(rec)
    kinds = [r["kind"] for r in results]
    out = {"steps": {k: kinds.count(k) for k in ("decode", "mixed", None)},
           "window_s": reduced.window_s, "busy_s": reduced.busy_s(),
           "op_stats": extra["op_stats"]}
    gaps = engine_trace.phase_gaps(rec, extra)
    out["gaps_s"] = dict(sorted(gaps.items(), key=lambda kv: -kv[1]))
    out["step_gap_s"] = {
        "bench.step only": reduced.gap_seconds_by_span().get("bench.step", 0),
        "bench.step and engine.*": sum(
            v for k, v in gaps.items()
            if k == "bench.step" or k.startswith("engine."))}
    for kind in ("decode", "mixed"):
        ph = engine_trace.step_phase_ms(extra["engine_spans"], kinds, kind)
        if ph:
            out[f"phase_ms.{kind}"] = ph
            out[f"host_ms.{kind}"] = engine_trace.host_ms(
                extra["engine_spans"], kinds, kind)
        prog = f"{kind}_fn"
        for scope in ("kv_write", "attn_kernel"):
            out[f"{scope}_ms.{kind}"] = engine_trace.scope_ms(
                reduced, extra, scope, prog)
        runs = reduced.module_runs(prog)
        out[f"step_ms.{kind}"] = 1e3 * sum(runs) / len(runs) if runs \
            else None
    out["longest_ops"] = engine_trace.scope_of_ops(reduced, extra)
    st = _stamps(win)
    out["queue_wait_p50_ms"] = engine_trace.queue_wait_ms(
        [(v[0], v[1]) for v in st.values()], win["t0"], win["t_end"])
    # The mixed kernel's share through the harness's reader, over rows
    # taken from the step records in place of the rebuilt ones.
    ctx = harness.Context(
        shape=work.Shape.from_config(spec["config"]),
        steps=[harness.StepRec(r["kind"],
                               engine_trace.record_rows([r], r["kind"]),
                               s.emitted)
               for r, s in zip(results, win["steps"])],
        counters=win["counters"], window_s=win["t_end"] - win["t0"],
        trace=reduced, peaks=peaks, chips=int(spec["cell"]["chips"]),
        num_slots=engine.num_slots, chunk_width=engine._chunk_width,
        total_pages=engine.slots.pool.total_pages)
    out["tda_mixed_attention_roofline"] = harness.metric_reader(
        "tda_mixed_attention_roofline")(ctx)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args()

    import harness
    from repro.serve import spans

    cost = span_cost_us()
    spec = harness.load_cell(args.workload)
    chips = int(spec["cell"]["chips"])
    devs, _ = harness.start(chips)
    peaks = harness.peaks_for(devs[0].device_kind)
    model, params, engine, _ = harness.build(spec["config"], spec["traffic"],
                                             args.seed, chips, devs)
    vocab = model.cfg.vocab_size
    harness.warm_up(engine, spec["traffic"], args.seed, vocab)
    win, results, tmp = traced_window(engine, spec["traffic"], args.seed,
                                      args.seconds, vocab)
    if args.fixture:
        for r in win["recs"].values():
            if r.request.status is None:
                engine.cancel(r.request)
        engine.finish_run()
        fwin, fresults, ftmp = traced_window(
            engine, spec["traffic"], args.seed + 1, 1.2, vocab)
    # The scope map needs the step programs' HLO, compiled here only after
    # the windows: a window traced after that compile idled two to three
    # times longer inside engine.sync than the harness's traced run.
    programs = engine.compiled_steps()
    rec, extra = reduce_trace(tmp, programs)
    if args.fixture:
        frec, fextra = reduce_trace(ftmp, programs)
        fx = {"cell": args.workload, "device_kind": devs[0].device_kind,
              "trace": frec, "engine": fextra, "results": fresults,
              "requests": _stamps(fwin), "t0": fwin["t0"],
              "t_end": fwin["t_end"],
              "steps": [dataclasses.asdict(s) for s in fwin["steps"]],
              "counters": fwin["counters"],
              "window_s": fwin["t_end"] - fwin["t0"],
              "num_slots": engine.num_slots,
              "chunk_width": engine._chunk_width,
              "total_pages": engine.slots.pool.total_pages}
        with gzip.open(args.fixture, "wt") as f:
            json.dump(fx, f)
    out = {"workload": args.workload, "seed": args.seed,
           "device": devs[0].device_kind,
           "span_cost_us": cost, "spans_per_step": 1 + len(spans.PHASES)}
    out.update(summary(spec, win, rec, extra, results, peaks, engine))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
