#!/usr/bin/env python3
"""Compile rehearsal: a configuration's serving executables compiled for a
described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/rehearse.py <config> \
        [--num-blocks N] [--max-slots N]

Compiles the three executables a cell's window drives (the donated
unified step, the non-donated chained unified step of the async
pipeline, the donated decode megastep) at the configuration's engine
sizes, with the Pallas kernels, and prints one JSON line per executable
with ``memory_analysis()``: what the compiler lays out for arguments,
outputs and temporaries.  It gives bytes and feasibility, never a time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("--num-blocks", type=int)
    ap.add_argument("--max-slots", type=int)
    ap.add_argument("--only", default="unified,chained,megastep")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.manifest import Manifest
    from repro.models import transformer as T

    man = Manifest()
    cfg = man.config(args.config)
    model = man.model(cfg)
    eng = dict(cfg["engine"])
    if args.num_blocks:
        eng["num_blocks"] = args.num_blocks
    if args.max_slots:
        eng["max_slots"] = args.max_slots
    pcfg = model.program_config(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def spec(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    params = spec(model.param_shapes(cfg))
    B, NB, MB, W = (eng["max_slots"], eng["num_blocks"],
                    eng["max_blocks_per_seq"], eng["max_num_batched_tokens"])
    state = spec(jax.eval_shape(lambda: T.make_decode_state(
        pcfg, B, NB, MB, dtype=jnp.float32,
        kv_cache_dtype=cfg.get("kv_cache_dtype", "bf16"))))
    rt = {"use_pallas": True, "interpret": False, "sampling_guard": True}

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    sp = {"keys": sd((B + 1, 2), jnp.uint32), "counts": sd((B + 1,), jnp.int32),
          "temps": sd((B + 1,), jnp.float32),
          "top_ks": sd((B + 1,), jnp.int32),
          "top_ps": sd((B + 1,), jnp.float32)}
    sp_dec = {k: sd((B,) + v.shape[1:], v.dtype) for k, v in sp.items()}
    i32 = sd((), jnp.int32)
    tok = sd((B,), jnp.int32)
    act = sd((B,), jnp.bool_)
    chunk = (sd((1, W), jnp.int32), sd((1, MB), jnp.int32), i32, i32)
    progs = {
        "unified": (jax.jit(lambda p, s, t, a, b, c, cbt, off, tl:
                            T.unified_step(pcfg, p, s, t, a, b, c, cbt, off,
                                           tl, None, rt), donate_argnums=(1,)),
                    (params, state, tok, sp, act) + chunk),
        "chained": (jax.jit(lambda p, s, pv, ci, up, t, a, b, c, cbt, off, tl:
                            T.unified_step_chained(pcfg, p, s, pv, ci, up, t,
                                                   a, b, c, cbt, off, tl,
                                                   None, rt)),
                    (params, state, sd((B + 1,), jnp.int32), tok,
                     sd((B,), jnp.bool_), tok, sp, act) + chunk),
        "megastep": (jax.jit(lambda p, s, t, a, b, n: T.decode_megastep(
            pcfg, p, s, t, a, b, n, max_horizon=8, ctx=None, rt=rt),
            donate_argnums=(1,)), (params, state, tok, sp_dec, act, i32)),
    }
    for name in args.only.split(","):
        fn, argv = progs[name]
        t0 = time.perf_counter()
        compiled = fn.lower(*argv).compile()
        ma = compiled.memory_analysis()
        print(json.dumps({
            "config": args.config, "executable": name, **eng,
            "compile_s": time.perf_counter() - t0,
            "pallas": "tpu_custom_call" in compiled.as_text(),
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
