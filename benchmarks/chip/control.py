#!/usr/bin/env python3
"""Readings behind the comparison's limit, many seeds in one process.

  python3 benchmarks/chip/control.py --workload qwen1.5-4b.decode \
      --seeds 1,2,3 --seconds 10 [--control 1]

For each seed: the cell's weights and engine, a window at the cell's own
load, then the harness's own comparison (``harness.compare``): the largest
teacher-forced gap of the served tokens (the sound reading) and, with
``--control 1``, the comparison judged on the tokens that the reference
computed in float8 e4m3 ranks first at the same positions (the control,
whose ``correct`` must come out false). One JSON line per seed, then a
summary. The benchmark's own runs never run this; it is how the limit in
the configuration file was read.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    import harness
    import reference

    spec = harness.load_cell(args.workload)
    config, traffic = spec["config"], spec["traffic"]
    chips = int(spec["cell"]["chips"])
    try:
        devs, _ = harness.start(chips)
    except harness.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 3
    eng = traffic["engine"]
    ref = reference.Reference(config,
                              eng["max_prompt_len"] + eng["max_new_tokens"],
                              eng["max_new_tokens"])
    rows = []
    for seed in seeds:
        t = time.perf_counter()
        model, params, engine, _ = harness.build(config, traffic, seed,
                                                 chips, devs)
        vocab = model.cfg.vocab_size
        harness.warm_up(engine, traffic, seed, vocab)
        win = harness.serve_window(engine, traffic, seed, args.seconds,
                                   vocab)
        harness.free_engine(engine)
        del engine
        gc.collect()
        checks, correct, read = harness.compare(
            config, traffic, params, win, seed, control=bool(args.control),
            ref=ref)
        row = {"seed": seed, **read, "correct": correct,
               "checks": {k: {"value": v, "limit": lim}
                          for k, (v, lim) in checks.items()},
               "seconds": time.perf_counter() - t}
        print(json.dumps(row), flush=True)
        rows.append(row)
        del params
        gc.collect()
    summary = {"workload": args.workload, "seeds": len(rows),
               "sound_max": max(r["sound_max"] or 0 for r in rows)}
    if args.control:
        summary["control_min"] = min(r["control_max"] or 0 for r in rows)
        summary["control_correct"] = sum(r["correct"] for r in rows)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
