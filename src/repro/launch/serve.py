"""Serving launcher: dynamic-batched engine over synthetic request traffic.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b \
      [--variant full] [--requests 16] [--max-len 64] [--ckpt DIR]

The builders below are the one way a model, its parameters and synthetic
traffic are made for serving; this CLI and ``chip_smoke.py`` both call them
and hand the result to ``Engine``.
"""
from __future__ import annotations

import argparse
from typing import List, Sequence, Union

import jax
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint
from repro.configs import get_config
from repro.data import request_lengths
from repro.launch import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import Model
from repro.serve import Engine, EngineConfig, Request

__all__ = ["build_model", "init_params", "make_requests"]


def build_model(arch: str, variant: str = "full", **overrides) -> Model:
    """The registry config of ``arch`` with bfloat16 serving parameters;
    ``overrides`` replace config fields (e.g. ``n_layers`` cuts depth)."""
    return Model(get_config(arch, variant, param_dtype="bfloat16",
                            **overrides))


def init_params(model: Model, seed: int = 0, mesh=None):
    """Seeded random parameters, built on the device by one jitted init so
    the peak is one copy of the weights. With a ``mesh`` each leaf is
    created straight into its shards (``launch/sharding.param_specs``):
    no device ever holds the whole model."""
    key = jax.random.key(seed)
    if mesh is None:
        return jax.jit(model.init)(key)
    specs = shd.param_specs(jax.eval_shape(model.init, key), mesh)
    return jax.jit(model.init, out_shardings=shd.named(specs, mesh))(key)


def make_requests(lengths: Sequence[int], vocab_size: int, *,
                  max_new: Union[int, Sequence[int]], seed: int = 0,
                  shared_prefix: int = 0) -> List[Request]:
    """Seeded synthetic requests, one per prompt length. ``max_new`` is one
    output budget or one per request. With ``shared_prefix > 0`` the last
    request opens with the first request's first ``shared_prefix`` tokens,
    so it can be served from the prefix cache once the first has
    prefilled."""
    rng = np.random.default_rng(seed)
    budgets = ([int(max_new)] * len(lengths) if np.isscalar(max_new)
               else [int(b) for b in max_new])
    prompts = [rng.integers(0, vocab_size, size=int(n)).astype(np.int32)
               for n in lengths]
    if shared_prefix and len(prompts) > 1:
        k = min(shared_prefix, len(prompts[0]), len(prompts[-1]))
        prompts[-1][:k] = prompts[0][:k]
    return [Request(rid=rid, prompt=p, max_new_tokens=b)
            for rid, (p, b) in enumerate(zip(prompts, budgets))]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--variant", default="full")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    model = build_model(args.arch, args.variant)
    cfg = model.cfg
    params = init_params(model)
    if args.ckpt and latest_step(args.ckpt) is not None:
        try:
            restored, step = restore_checkpoint(args.ckpt,
                                                {"params": params})
            params = restored["params"]
            print(f"loaded checkpoint step {step}")
        except KeyError:
            # checkpoints written from a bare params tree have no
            # "params/" key prefix; retry against the bare structure
            params, step = restore_checkpoint(args.ckpt, params)
            print(f"partial restore: params only (step {step})")

    eng = Engine(model, params, config=EngineConfig(
        max_len=args.max_len, max_new_tokens=args.max_new))
    lengths = request_lengths(args.requests, args.max_len, "bert")
    for req in make_requests(lengths, cfg.vocab_size, max_new=args.max_new):
        eng.submit(req)
    done = eng.run()
    util = np.mean([s["utilization"] for s in eng.stats])
    packs = sum(s["n_requests"] for s in eng.stats) / max(
        sum(s["rows"] for s in eng.stats), 1)
    ds = eng.decode_stats
    print(f"served {len(done)} requests | {packs:.2f} requests/weight-sweep "
          f"| prefill fill {util:.2f} | decode slot utilization "
          f"{ds['slot_utilization']:.2f} over {ds['steps']} steps")


if __name__ == "__main__":
    main()
