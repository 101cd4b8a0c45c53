#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

  python3 benchmarks/chip/run.py --workload qwen1.5-4b.decode --seed 1 \
      --seconds 30 --trace 0

From the root of a checkout. ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of the
window, with the device's busy and window seconds and a breakdown. The last
line of standard output is one JSON object; the last lines of standard
error name each number compared with the reference beside its limit.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    import harness
    try:
        result, lines = harness.run(args.workload, args.seed, args.seconds,
                                    bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"run.py: {e}; nothing was measured", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
