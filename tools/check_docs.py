"""Docs link/path checker: keeps README.md and docs/ from rotting.

Checks, with no network and no heavy imports:

1. Every repo path referenced in the markdown (backtick-quoted
   ``src/...``, ``tests/...``, ``examples/...``, ``benchmarks/...``,
   ``docs/...``, ``experiments/...``) exists; ``::test_name`` suffixes and
   glob-ish references are handled.
2. Every ``python`` entry point named in a bash code fence
   (``python -m <module>`` / ``python <script.py>``) resolves to a real
   module or file.
3. The tier-1 verify command documented in README is the one ROADMAP.md
   pins (``python -m pytest``).

CI pairs this with ``python -m pytest --collect-only -q`` so the
documented command is also *executed* against the tree.

  PYTHONPATH=src python tools/check_docs.py
"""
from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
DOC_FILES = [ROOT / "README.md", *sorted((ROOT / "docs").glob("*.md"))]

# Load-bearing doc anchors: each (file, substring) must stay present so the
# documented contracts (paged lane pool, sampling, prefix cache) cannot be
# silently dropped in a refactor. Extend when a new contract lands.
REQUIRED_ANCHORS = [
    ("README.md", "python -m pytest -x -q"),
    ("README.md", "serve/pages.py"),          # paged lane-pool column/row
    ("README.md", "kv_memory_ratio"),
    ("README.md", "prefix_hit_ratio"),        # prefix-sharing metric + row
    ("README.md", "| Shared |"),              # config-coverage shared column
    ("serving.md", "src/repro/serve/pages.py"),
    ("serving.md", "block table"),
    ("serving.md", "[lo, hi)"),
    ("serving.md", "kv_memory_ratio"),
    ("serving.md", "preempt"),
    ("serving.md", "src/repro/serve/sampling.py"),
    # prefix cache contract: hash granularity, CoW, eviction
    ("serving.md", "chained"),
    ("serving.md", "copy-on-write"),
    ("serving.md", "prefix_hit_ratio"),
    ("serving.md", "pages_shared"),
    ("serving.md", "LRU"),
    # compressed-weight serving contract: format switch, traffic metric
    ("README.md", "bytes_per_token"),
    ("serving.md", "weight_format"),
    ("serving.md", "bytes_per_token"),
    # serving failure model contract: terminal statuses, chaos harness,
    # audit mode, and the refused deployment
    ("serving.md", "Serving failure model"),
    ("serving.md", "FaultPlan"),
    ("serving.md", "timed_out"),
    ("serving.md", "REPRO_SERVE_AUDIT"),
    ("serving.md", "AuditError"),
    ("serving.md", "UnsupportedConfigError"),
    # tensor-parallel sharded decode contract: the section, the merge
    # kernel, the per-rank traffic metric, and the README coverage column
    ("serving.md", "Sharded decode"),
    ("serving.md", "kernels/tda/sharded.py"),
    ("serving.md", "tensor_parallel_size"),
    ("serving.md", "kv_bytes_per_token_per_rank"),
    ("README.md", "| Mesh |"),
    # mixed-step contract: the interleaved-chunked-prefill section, the
    # budget knob, and the README map row
    ("serving.md", "Interleaved chunked prefill"),
    ("serving.md", "prefill_budget"),
    ("README.md", "prefill_budget"),
    # async front-end & replica contract: the section, the new public
    # API names, and the README map row
    ("serving.md", "Async front-end & replicas"),
    ("serving.md", "EngineConfig"),
    ("serving.md", "SamplingParams"),
    ("serving.md", "StreamHandle"),
    ("serving.md", "FleetPrefixIndex"),
    ("serving.md", "cancelled"),
    ("README.md", "SamplingParams"),
]

PATH_RE = re.compile(
    r"[`(]((?:src|tests|examples|benchmarks|docs|experiments|tools)/"
    r"[A-Za-z0-9_./\-]*)")
PY_MODULE_RE = re.compile(r"python -m ([A-Za-z0-9_.]+)")
PY_SCRIPT_RE = re.compile(r"python ((?:[A-Za-z0-9_\-]+/)+[A-Za-z0-9_\-]+\.py)")


def check_paths(md: pathlib.Path, text: str, errors: list) -> None:
    for ref in PATH_RE.findall(text):
        ref = ref.split("::")[0].rstrip("./")
        if not ref or "*" in ref or "<" in ref:
            continue
        if not (ROOT / ref).exists():
            errors.append(f"{md.name}: referenced path does not exist: {ref}")


def check_commands(md: pathlib.Path, text: str, errors: list) -> None:
    fences = re.findall(r"```(?:bash|sh)?\n(.*?)```", text, re.S)
    for fence in fences:
        for mod in PY_MODULE_RE.findall(fence):
            top = mod.split(".")[0]
            if top in ("pytest",):
                continue
            cand = [ROOT / "src" / mod.replace(".", "/"),
                    ROOT / mod.replace(".", "/")]
            if not any(p.with_suffix(".py").exists() or
                       (p / "__init__.py").exists() for p in cand):
                errors.append(f"{md.name}: `python -m {mod}` does not "
                              "resolve under src/ or the repo root")
        for script in PY_SCRIPT_RE.findall(fence):
            if not (ROOT / script).exists():
                errors.append(f"{md.name}: `python {script}` missing")


def check_anchors(errors: list) -> None:
    texts = {md.name: md.read_text() for md in DOC_FILES if md.exists()}
    for fname, needle in REQUIRED_ANCHORS:
        if fname not in texts:
            errors.append(f"{fname} missing (required by anchors)")
        elif needle not in texts[fname]:
            errors.append(f"{fname}: required anchor not found: {needle!r}")


def main() -> int:
    errors: list = []
    readme = (ROOT / "README.md")
    if not readme.exists():
        errors.append("README.md missing")
    for md in DOC_FILES:
        text = md.read_text()
        check_paths(md, text, errors)
        check_commands(md, text, errors)
    check_anchors(errors)
    if errors:
        print("\n".join(errors))
        return 1
    print(f"docs check OK ({len(DOC_FILES)} files)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
