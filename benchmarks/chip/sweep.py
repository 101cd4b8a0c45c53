#!/usr/bin/env python3
"""Find the knee of an open-loop cell once: its traffic at several fixed
rates, one window each, in one process.

  python3 benchmarks/chip/sweep.py --workload qwen1.5-4b.prefill \
      --rates 0.5,1,1.5,2 --seconds 40

For each rate: requests due and finished in the window, output tokens per
second, the 50th and 90th percentiles of time to first token, and the
waiting queue at half time and at the end. The knee is the highest rate at
which the queue does not grow over the window. The benchmark's own runs
never run this; the cell's rate in its traffic file was set from it.
"""
import argparse
import gc
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import numpy as np

    import harness

    spec = harness.load_cell(args.workload)
    config, traffic = spec["config"], spec["traffic"]
    chips = int(spec["cell"]["chips"])
    try:
        devs, _ = harness.start(chips)
    except harness.NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 3
    model, params, engine, _ = harness.build(config, traffic, args.seed,
                                             chips, devs)
    vocab = model.cfg.vocab_size
    harness.warm_up(engine, traffic, args.seed, vocab)
    for rate in [float(r) for r in args.rates.split(",")]:
        tr = {**traffic, "rate_per_s": rate}
        queue = []

        def probe(t, eng=engine):
            queue.append((t, eng.scheduler.pending()))

        t = time.perf_counter()
        win = harness.serve_window(engine, tr, args.seed, args.seconds,
                                   vocab, on_step=probe)
        e2e = harness.end_to_end(win)
        half = [q for t_, q in queue if t_ <= win["t0"] + args.seconds / 2]
        recs = list(win["recs"].values())
        ttft = [(r.stamps[0] if r.stamps else win["t_end"]) - r.due
                for r in recs]
        row = {"rate": rate, "due": len(recs),
               "finished": sum(r.status == "ok" for r in recs),
               "tokens_per_s": e2e["tokens_per_s"],
               "ttft_p50_s": float(np.percentile(ttft, 50)),
               "ttft_p90_s": float(np.percentile(ttft, 90)),
               "queue_half": half[-1] if half else 0,
               "queue_end": queue[-1][1] if queue else 0,
               "mixed_steps": win["counters"]["mixed_steps"],
               "steps": win["counters"]["steps"],
               "preemptions": win["counters"]["preemptions"],
               "seconds": time.perf_counter() - t}
        print(json.dumps(row), flush=True)
        # Withdraw what is left, so the next rate starts from an empty
        # engine.
        for r in recs:
            if r.status is None:
                engine.cancel(r.request)
        engine.finish_run()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
