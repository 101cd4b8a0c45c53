"""End-to-end dry-run machinery on a small host-device mesh: build_bundle ->
lower -> compile -> analyze, for one train and one decode cell (subprocess so
the main session keeps 1 device)."""
import json
import subprocess
import sys
import textwrap


def _run(body: str) -> dict:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.steps import build_bundle
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        {body}
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_train_bundle_lowers_compiles_analyzes():
    r = _run("""
        import repro.configs as C
        C.SHAPES["tiny_train"] = {"seq": 64, "batch": 8, "step": "train"}
        cfg = get_config("qwen2.5-32b", "smoke")
        b = build_bundle(cfg, "tiny_train", mesh)
        with mesh:
            comp = jax.jit(b.fn, in_shardings=b.in_shardings,
                           out_shardings=b.out_shardings,
                           donate_argnums=b.donate_argnums
                           ).lower(*b.args).compile()
        a = analyze_hlo(comp.as_text())
        mem = comp.memory_analysis()
        print(json.dumps({
            "flops": a.flops, "bytes": a.bytes,
            "coll": sorted(a.collective_bytes),
            "warn": len(a.warnings),
            "temp": mem.temp_size_in_bytes}))
    """)
    assert r["flops"] > 1e6
    assert r["bytes"] > 1e5
    assert r["warn"] == 0


def test_decode_bundle_unrolled_and_scanned_agree():
    r = _run("""
        import repro.configs as C
        C.SHAPES["tiny_decode"] = {"seq": 128, "batch": 8, "step": "decode"}
        res = {}
        for tag, unroll in (("scan", False), ("unroll", True)):
            cfg = get_config("qwen2.5-32b", "smoke", unroll_decode=unroll,
                             param_dtype="bfloat16")
            b = build_bundle(cfg, "tiny_decode", mesh)
            with mesh:
                comp = jax.jit(b.fn, in_shardings=b.in_shardings,
                               out_shardings=b.out_shardings,
                               donate_argnums=b.donate_argnums
                               ).lower(*b.args).compile()
            a = analyze_hlo(comp.as_text())
            res[tag] = {"flops": a.flops, "bytes": a.bytes}
        print(json.dumps(res))
    """)
    # same math -> comparable flops; unrolled must not read more bytes
    assert abs(r["scan"]["flops"] - r["unroll"]["flops"]) \
        / r["scan"]["flops"] < 0.2
    assert r["unroll"]["bytes"] <= r["scan"]["bytes"] * 1.1
