#!/usr/bin/env python3
"""Bring-up smoke run: the serving engine's main path on one TPU v5e.

    python3 chip_smoke.py [--seed N]

Loads qwen1.5-0.5b at its published widths (24 layers, d_model 1024,
16 x 64 heads, d_ff 2816, vocab 151936) with random weights made from
``--seed``, through ``LLM.load``, and serves a handful of requests with
the default engine (chunked prefill + unified step + async pipeline).
Two phases:

* ``mha-bf16``: the published grouping (16 KV heads), dense KV pool;
* ``opt-gqa-int8-w4a16``: the Opt-GQA grouping (2 KV heads), int8 KV
  pool, RTN int4 weights, which puts the int8 decode / chunk kernels
  and the W4A16 matmul on the chip.

Each phase must show ``tpu_custom_call`` in the compiled unified step,
Pallas and XLA-reference logits that agree (prefill chunks + one decode
step, same params and state), and every request finishing ``length`` or
``stop`` with nothing quarantined or retried.  Lines before the last are
smoke readings, not benchmarks.  The last line is one JSON object,
``{"ok": true, "device": {...}}``.  Off a TPU v5e, or on any failure,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen1.5-0.5b"
SLOTS = 8                    # engine decode slots = requests in flight
REQUESTS = 8
PROMPT_LEN = (200, 400)      # seeded token ids: 1-2 chunks, 13-25 pages
MAX_TOKENS = 40
CHUNK = 256                  # max_num_batched_tokens: the chunk width
MB = 32                      # table entries: 512 tokens > 400 + 40
REF_LENS = (300, 200)        # reference check: 2 chunks and 1
# share of the chip's HBM that params + both live pool copies may take
# (the async path's chained step does not donate its state, so two
# pools are live at once)
HBM_BUDGET = 0.6
# Pallas vs XLA-reference logits, as a share of max |logit|.  Both paths
# run the same bf16 model but round differently: the kernels keep
# attention and the W4A16 dequant in f32, the reference dequantizes
# weights to bf16 (2^-9 relative per weight).  At reduced widths on the
# CPU (interpret mode) the gap was 0.2% (bf16 pool), 1.3% (int8 pool)
# and 2.4% (W4A16) of max |logit|; a kernel that reads the wrong KV head
# gave 94-124%.  0.1 sits between the two with room on both sides.
REL_TOL = 0.1

PHASES = (
    ("mha-bf16", {}, "bf16", None),
    ("opt-gqa-int8-w4a16", {"num_kv_heads": 2}, "int8", "rtn-int4"),
)


class SmokeFailure(RuntimeError):
    pass


def reading(name: str, **values) -> None:
    """One smoke reading (not a benchmark): a labelled JSON line."""
    print(json.dumps({"smoke_reading": name, **values}), flush=True)


def require_v5e():
    import jax
    dev = jax.devices()[0]
    kind = dev.device_kind
    if dev.platform != "tpu":
        raise SmokeFailure(f"needs a TPU v5e; JAX found platform "
                           f"{dev.platform!r} ({kind})")
    if not re.search(r"v5 ?lite|v5e", kind.lower()):
        raise SmokeFailure(f"needs a TPU v5e; found device kind {kind!r}")
    return dev


def prompts(seed: int, vocab: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, vocab, int(rng.integers(*PROMPT_LEN))))
            for _ in range(REQUESTS)]


def kernels_in_unified_step(runner):
    """Compile the runner's own unified-step jit on its live state;
    returns (compile seconds, sorted Pallas kernel names).  Fails if the
    compiled program holds no ``tpu_custom_call``."""
    import jax.numpy as jnp
    import numpy as np
    n = runner.max_slots + 1
    sp = {"keys": jnp.zeros((n, 2), jnp.uint32),
          "counts": jnp.zeros((n,), jnp.int32),
          "temps": jnp.zeros((n,), jnp.float32),
          "top_ks": jnp.zeros((n,), jnp.int32),
          "top_ps": jnp.ones((n,), jnp.float32)}
    t0 = time.perf_counter()
    lowered = runner._unified.lower(
        runner.params, runner.state,
        jnp.asarray(np.zeros(runner.max_slots, np.int32)), sp,
        jnp.asarray(np.zeros(runner.max_slots, bool)),
        jnp.zeros((1, runner.chunk_tokens), jnp.int32),
        jnp.zeros((1, runner.mb), jnp.int32), jnp.int32(0), jnp.int32(1))
    compiled = lowered.compile()
    secs = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise SmokeFailure("compiled unified step holds no tpu_custom_call: "
                           "the Pallas kernels are not on the chip")
    names = sorted(set(re.findall(r'kernel_name\s*=\s*"([^"]+)"',
                                  lowered.as_text())))
    return secs, names


def reference_gap(cfg, params, kv_cache_dtype: str, seed: int,
                  kernel_rt: dict):
    """Max |logit| gap and max |logit| of the XLA reference over the
    prefill chunks and one decode step of two sequences, run once with
    ``kernel_rt`` and once with ``{"use_pallas": False}`` on the same
    params and a fresh state each."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.kv_quant import cache_from_state, cache_to_state
    from repro.models import transformer as T
    rng = np.random.default_rng(seed + 1)
    lens = list(REF_LENS)
    toks = [rng.integers(1, cfg.vocab_size, n) for n in lens]
    nb = len(lens) * MB
    bt = rng.permutation(nb).reshape(len(lens), MB).astype(np.int32)
    nxt = jnp.asarray(rng.integers(1, cfg.vocab_size, len(lens)), jnp.int32)

    def run(rt):
        chunk = jax.jit(lambda p, c, t, b, off, tl: T.prefill_chunk(
            cfg, p, c, t, b, off, tl, None, rt))
        decode = jax.jit(lambda p, s, t: T.decode_step(cfg, p, s, t, None,
                                                       rt))
        state = T.make_decode_state(cfg, len(lens), nb, MB,
                                    dtype=jnp.float32,
                                    kv_cache_dtype=kv_cache_dtype)
        out = []
        for i, n in enumerate(lens):
            for start in range(0, n, CHUNK):
                w = min(CHUNK, n - start)
                t = np.zeros((1, CHUNK), np.int32)
                t[0, :w] = toks[i][start:start + w]
                lg, cache = chunk(params, cache_from_state(state),
                                  jnp.asarray(t), jnp.asarray(bt[i:i + 1]),
                                  jnp.int32(start), jnp.int32(start + w))
                state.update(cache_to_state(cache))
                out.append(lg)
        state["block_table"] = jnp.asarray(bt)
        state["seq_lens"] = jnp.asarray(np.asarray(lens, np.int32) + 1)
        lg, _ = decode(params, state, nxt)
        out.append(lg)
        return np.asarray(jnp.concatenate(out, 0), np.float32)

    got = run(kernel_rt)
    ref = run({"use_pallas": False})
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise SmokeFailure("non-finite logits in the reference comparison")
    return float(np.abs(got - ref).max()), float(np.abs(ref).max())


def run_phase(name, overrides, kv_cache_dtype, quant, seed, dev):
    import jax
    from repro.serving import LLM, SamplingParams

    t0 = time.perf_counter()
    llm = LLM.load(ARCH, reduced=False, overrides=overrides or None,
                   kv_cache_dtype=kv_cache_dtype, quant=quant, seed=seed,
                   max_slots=SLOTS, num_blocks=SLOTS * MB + SLOTS * MB // 4,
                   max_blocks_per_seq=MB, max_num_batched_tokens=CHUNK)
    load_s = time.perf_counter() - t0
    cfg, runner = llm.cfg, llm.engine.runner
    pool = {k: runner.state[k] for k in ("k_pool", "v_pool")}
    pool_bytes = runner.kv_pool_bytes()
    param_bytes = sum(int(a.size) * a.dtype.itemsize
                      for a in jax.tree.leaves(llm.params))
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    reading(f"{name}:load", arch=cfg.name, num_layers=cfg.num_layers,
            d_model=cfg.d_model, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, d_ff=cfg.d_ff,
            vocab_size=cfg.vocab_size, quant=quant,
            kv_cache_dtype=kv_cache_dtype,
            pool_dtype=str(pool["k_pool"].dtype),
            pool_shape=list(pool["k_pool"].shape),
            num_blocks=runner.num_blocks, kv_pool_bytes=pool_bytes,
            param_bytes=param_bytes, hbm_bytes_limit=limit,
            load_s=load_s)
    if limit and param_bytes + 2 * pool_bytes > HBM_BUDGET * limit:
        raise SmokeFailure(f"{name}: params + two pool copies "
                           f"({param_bytes + 2 * pool_bytes} B) exceed "
                           f"{HBM_BUDGET:.0%} of HBM ({limit} B)")

    compile_s, kernels = kernels_in_unified_step(runner)
    reading(f"{name}:unified_step", compile_s=compile_s, kernels=kernels)

    gap, scale = reference_gap(cfg, llm.params, kv_cache_dtype, seed,
                               dict(runner.rt, use_pallas=True,
                                    interpret=False))
    reading(f"{name}:pallas_vs_xla", max_abs_logit_diff=gap,
            max_abs_logit=scale, rel_tol=REL_TOL)
    if not gap <= REL_TOL * scale:
        raise SmokeFailure(f"{name}: Pallas vs XLA logits differ by {gap} "
                           f"(max |logit| {scale}, tolerance "
                           f"{REL_TOL} of it)")

    t0 = time.perf_counter()
    outs = llm.generate(prompts(seed, cfg.vocab_size),
                        SamplingParams(max_tokens=MAX_TOKENS))
    serve_s = time.perf_counter() - t0
    rep = llm.engine.report()
    llm.close()
    reasons = sorted({o.finish_reason for o in outs})
    gen = sum(len(o.token_ids) for o in outs)
    reading(f"{name}:serve", requests=len(outs), tokens_generated=gen,
            finish_reasons=reasons, serve_wall_s=serve_s,
            device_dispatches_per_step=rep["device_dispatches_per_step"],
            async_steps=rep["async_steps"],
            prefill_chunks=rep["prefill_chunks"],
            quarantined=rep["quarantined"],
            dispatch_retries=rep["dispatch_retries"],
            peak_bytes_in_use=(dev.memory_stats() or {}).get(
                "peak_bytes_in_use"))
    bad = [o.finish_reason for o in outs
           if o.finish_reason not in ("length", "stop")]
    if bad or rep["quarantined"] or rep["dispatch_retries"]:
        raise SmokeFailure(f"{name}: finish reasons {reasons}, quarantined "
                           f"{rep['quarantined']}, retries "
                           f"{rep['dispatch_retries']}")
    if gen != REQUESTS * MAX_TOKENS and "stop" not in reasons:
        raise SmokeFailure(f"{name}: {gen} tokens generated, expected "
                           f"{REQUESTS * MAX_TOKENS}")
    del llm, runner, pool
    gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        dev = require_v5e()
        from repro.runtime.compile_cache import enable_compile_cache
        reading("compile_cache", dir=enable_compile_cache())
        for name, overrides, kv_dtype, quant in PHASES:
            run_phase(name, overrides, kv_dtype, quant, args.seed, dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
