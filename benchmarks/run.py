"""Benchmark harness — one table per paper figure. Prints
``name,us_per_call,derived`` CSV (assignment format) and writes a
machine-readable ``BENCH_<table>.json`` sidecar per table (rows + any
structured metrics from ``tables.ARTIFACTS``) so the perf trajectory —
tokens/s, slot utilization, blocks-visited ratio — is tracked across PRs.

  PYTHONPATH=src python -m benchmarks.run [table ...]
Tables: params ema macs utilization latency_energy kernels decode
decode_attn accuracy roofline
"""
import json
import pathlib
import sys

from benchmarks import tables
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    names = sys.argv[1:] or ["params", "ema", "macs", "utilization",
                             "latency_energy", "kernels", "decode",
                             "decode_attn", "accuracy", "roofline"]
    print("name,us_per_call,derived")
    for n in names:
        rows = getattr(tables, f"bench_{n}")()
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        artifact = {"table": n,
                    "rows": [{"name": r[0], "us_per_call": r[1],
                              "derived": r[2]} for r in rows]}
        artifact.update(tables.ARTIFACTS.get(n, {}))
        pathlib.Path(f"BENCH_{n}.json").write_text(
            json.dumps(artifact, indent=1, default=float) + "\n")


if __name__ == "__main__":
    main()
