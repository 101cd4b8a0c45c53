#!/usr/bin/env python3
"""Record the small chip trace the CPU tests reduce (``fixtures/``).

  python3 benchmarks/chip/record_fixture.py --out decode_trace.json.gz

One TPU: the ``qwen1.5-4b.decode`` cell's first ~1.2 s (the admitting
mixed step and a few decode steps), traced, reduced to the compact record
of ``trace_reduce.read_xplane`` and saved with the window's step records.
"""
import argparse
import dataclasses
import gzip
import json
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

CELL = "qwen1.5-4b.decode"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=1.2)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()

    import harness
    import trace_reduce

    spec = harness.load_cell(CELL)
    devs, _ = harness.start(1)
    model, params, engine, _ = harness.build(spec["config"], spec["traffic"],
                                             args.seed, 1, devs)
    vocab = model.cfg.vocab_size
    harness.warm_up(engine, spec["traffic"], args.seed, vocab)
    tmp = tempfile.mkdtemp(prefix="bench_fixture_")
    try:
        win = harness.serve_window(engine, spec["traffic"], args.seed,
                                   args.seconds, vocab, trace_dir=tmp)
        rec = trace_reduce.read_xplane(trace_reduce.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    out = {"cell": CELL, "device_kind": devs[0].device_kind,
           "trace": rec, "steps": [dataclasses.asdict(s)
                                   for s in win["steps"]],
           "counters": win["counters"],
           "window_s": win["t_end"] - win["t0"],
           "num_slots": engine.num_slots,
           "chunk_width": engine._chunk_width,
           "total_pages": engine.slots.pool.total_pages}
    with gzip.open(args.out, "wt") as f:
        json.dump(out, f)
    print(json.dumps({"steps": [s.kind for s in win["steps"]],
                      "ops": {d: len(v["ops"])
                              for d, v in rec["devices"].items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
