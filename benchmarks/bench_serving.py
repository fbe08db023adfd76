"""Paper Fig.2 + Fig.3: MHA vs Opt-GQA serving metrics, and run stability.

Small same-shape models on CPU: 'mha' (kv=H, contiguous-style oversized
blocks, no reuse) vs 'opt-gqa' (kv=H/4, paged, prefix reuse, ALiBi-ready).
Reported: latency, all-throughput (req/s, tok/s), generate throughput —
exactly the paper's three numbers (ratios are the transferable signal) —
plus streamed time-to-first-token (``ttft_ms``), measured at the moment
the engine emits a request's first ``RequestOutput`` delta.

``table_fastpath`` quantifies the fused decode megastep against the legacy
per-token loop on the same workload: per-engine-step decode latency,
host↔device syncs per decode step, TTFT and generate throughput.
``table_kv_memory`` records the quantized-KV trade: pool bytes and KV
bytes per cached token for the dense vs int8 pool (``kvmem_bf16`` /
``kvmem_int8`` rows), with the warm fused decode-step latency as the
cost axis. ``table_guards`` measures the robustness guards' warm-step
cost (``guards_on`` / ``guards_off`` rows; ``--assert-guard-overhead
1.02`` is the <2% acceptance gate). ``table_telemetry`` measures the
obs span tracer the same way (``telemetry_on`` / ``telemetry_off`` rows,
``--assert-telemetry-overhead 1.02``), and ``unified_*`` rows carry the
span-derived ``host_ms`` / ``device_ms`` per-step attribution (ROADMAP
item 1, measured). ``table_async`` compares the async pipelined step
(``async_on``: enqueue N+1 while N executes, readback deferred one
step) against the two-call synchronous path (``async_off``) on the
mixed workload; ``--assert-async-itl 1.0`` is the hard gate that the
pipelined ITL p50 stays at or under the two-call path's in the same
run.  Noisy latency tables (``fastpath``/``kvmem``/``guards``/
``telemetry``/``async``) share the interleaved paired-rep design
(``_paired_best``). Run as a module for smoke mode + JSON trajectory
tracking::

    PYTHONPATH=src python -m benchmarks.bench_serving --smoke \
        --json BENCH_serving.json \
        [--assert-baseline BENCH_serving.json --regress-factor 1.10]

``--assert-baseline`` fails the run if the fused warm decode-step latency
regressed past ``--regress-factor`` × the committed baseline row.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs.registry import get_reduced
from repro.models import transformer as T
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import SamplingParams, ServingEngine


def _run_engine(cfg, params, seed=0, *, n_requests=12, max_tokens=8,
                use_fused=True, max_horizon=8, kv_cache_dtype="bf16"):
    # enable_async_step=False everywhere except table_async: the legacy
    # tables measure sync-path dimensions (fused vs loop, pool dtype,
    # guard/tracer overhead) and their windows must not absorb the
    # chained async executable's one-time compile — the async dimension
    # has its own paired table and gate
    eng = ServingEngine(cfg, params, max_slots=4, num_blocks=256,
                        max_blocks_per_seq=16, prefill_bucket=32,
                        max_num_batched_tokens=64,
                        use_fused=use_fused, max_horizon=max_horizon,
                        kv_cache_dtype=kv_cache_dtype,
                        enable_async_step=False)
    rng = np.random.default_rng(seed)
    prefix = list(rng.integers(1, 200, 24))
    sp = SamplingParams(max_tokens=max_tokens)
    for _ in range(n_requests):
        eng.add(prefix + list(rng.integers(1, 200,
                                           int(rng.integers(4, 24)))), sp)
    return eng.run_until_done()


def _paired_best(reps, variants, key="decode_step_latency_us"):
    """Interleaved paired-rep de-noising (``table_guards``' design,
    factored out): each rep runs every variant back to back, so machine
    drift and load spikes hit all variants alike; the per-variant row
    keeps the BEST (minimum-``key``) rep — min, not mean, because
    scheduler noise only ever adds time.  For two-variant tables the
    returned ratio list holds each rep's second/first ``key`` ratio —
    overhead gates read its minimum (a busy runner inflates pairs, never
    deflates them, so the best pair is the honest intrinsic cost)."""
    best, ratios = {}, []
    for _ in range(reps):
        pair = []
        for name, fn in variants:
            r = fn()
            pair.append(r[key])
            if name not in best or r[key] < best[name][key]:
                best[name] = r
        if len(pair) == 2:
            ratios.append(pair[1] / pair[0])
    return best, ratios


def table_fig2(smoke: bool = False) -> None:
    key = jax.random.PRNGKey(0)
    for name, kv in (("mha", 8), ("opt-gqa", 2)):
        cfg = get_reduced("qwen1.5-0.5b", num_layers=4, num_heads=8,
                          num_kv_heads=kv)
        if name == "mha":
            cfg = cfg.replace(paging=cfg.paging.__class__(
                block_size=16, enable_prefix_reuse=False))
        params = T.init_params(cfg, key)
        r = _run_engine(cfg, params, n_requests=4 if smoke else 12)
        emit(f"fig2_{name}", r["latency_s"] * 1e6,
             f"req_s={r['throughput_req_s']:.3f};"
             f"tok_s={r['throughput_tok_s']:.1f};"
             f"gen_tok_s={r['generate_tok_s']:.1f};"
             f"ttft_ms={r['ttft_s'] * 1e3:.1f};"
             f"reused={r['blocks_reused']}")


def table_fig3(smoke: bool = False) -> None:
    key = jax.random.PRNGKey(0)
    cfg = get_reduced("qwen1.5-0.5b", num_layers=4, num_heads=8,
                      num_kv_heads=2)
    params = T.init_params(cfg, key)
    gen, lat = [], []
    for run_i in range(2 if smoke else 3):
        r = _run_engine(cfg, params, seed=run_i,
                        n_requests=4 if smoke else 12)
        gen.append(r["generate_tok_s"])
        lat.append(r["latency_s"] * 1e6)
        emit(f"fig3_run{run_i}", r["latency_s"] * 1e6,
             f"tok_s={r['throughput_tok_s']:.1f};"
             f"gen_tok_s={r['generate_tok_s']:.1f}")
    # the aggregate row's us_per_call is the mean per-request latency
    # across runs (it used to emit a literal 0.0 placeholder)
    emit("fig3_stability", float(np.mean(lat)),
         f"gen_mean={np.mean(gen):.1f};gen_cv={np.std(gen)/np.mean(gen):.3f}")


def table_fastpath(smoke: bool = False) -> None:
    """Decode fast path: legacy per-token loop vs fused megastep on the
    same workload. The win shows up as fewer host syncs per decode step
    (1.0 -> ~1/horizon) and lower per-step decode latency; ``ttft_ms`` is
    the streamed time-to-first-token (prefill wave -> first emitted
    RequestOutput), which the fused path leaves untouched.  Interleaved
    paired reps (``_paired_best``) de-noise both rows."""
    key = jax.random.PRNGKey(0)
    cfg = get_reduced("qwen1.5-0.5b", num_layers=4, num_heads=8,
                      num_kv_heads=2)
    params = T.init_params(cfg, key)
    # smoke keeps CI fast (horizon 4 still guarantees >= 2 fused dispatches,
    # so per-step latency is warm / post-compile); the full run is long
    # enough that the one-off megastep compile also amortizes in gen_tok_s.
    n_req = 4 if smoke else 12
    mnt = 12 if smoke else 64
    horizon = 4 if smoke else 8
    reps = 2 if smoke else 3

    def one(fused):
        return _run_engine(cfg, params, n_requests=n_req, max_tokens=mnt,
                           use_fused=fused, max_horizon=horizon)

    one(False)                       # warm both jit caches before timing
    one(True)
    best, ratios = _paired_best(reps, [("legacy", lambda: one(False)),
                                       ("fused", lambda: one(True))])
    for name, r in best.items():
        emit(f"fastpath_{name}", r["decode_step_latency_us"],
             f"gen_tok_s={r['generate_tok_s']:.1f};"
             f"ttft_ms={r['ttft_s'] * 1e3:.1f};"
             f"syncs_per_step={r['syncs_per_decode_step']:.3f};"
             f"decode_steps={r['decode_steps']};"
             f"dispatches={r['decode_dispatches']};"
             f"host_syncs={r['host_syncs']};"
             + (f"pair_ratio_min={min(ratios):.4f};" if name == "fused"
                else "")
             + f"reps={reps}")


def table_kv_memory(smoke: bool = False) -> None:
    """KV-cache memory: the same fused workload through the dense pool and
    the int8 quantized pool. ``us_per_call`` is the warm fused decode-step
    latency (the int8 path must stay close to the dense one); the derived
    columns record the memory win — ``kv_pool_bytes`` / ``kv_bytes_per_tok``
    drop ~2x vs bf16 pools and ~4x vs these f32 CPU pools, which is the
    admissible-batch/context headroom the quantization buys.
    Interleaved paired reps (``_paired_best``) de-noise the latency
    axis; the memory columns are deterministic."""
    key = jax.random.PRNGKey(0)
    cfg = get_reduced("qwen1.5-0.5b", num_layers=4, num_heads=8,
                      num_kv_heads=2)
    params = T.init_params(cfg, key)
    n_req = 4 if smoke else 12
    mnt = 12 if smoke else 64
    reps = 2 if smoke else 3

    def one(name):
        return _run_engine(cfg, params, n_requests=n_req, max_tokens=mnt,
                           kv_cache_dtype=name)

    one("bf16")                      # warm both jit caches before timing
    one("int8")
    best, ratios = _paired_best(reps, [("bf16", lambda: one("bf16")),
                                       ("int8", lambda: one("int8"))])
    for name, r in best.items():
        emit(f"kvmem_{name}", r["decode_step_latency_us"],
             f"kv_pool_bytes={int(r['kv_pool_bytes'])};"
             f"kv_bytes_per_tok={r['kv_bytes_per_token']:.1f};"
             f"gen_tok_s={r['generate_tok_s']:.1f};"
             f"ttft_ms={r['ttft_s'] * 1e3:.1f};"
             + (f"pair_ratio_min={min(ratios):.4f};" if name == "int8"
                else "")
             + f"reps={reps}")


def table_guards(smoke: bool = False) -> None:
    """Robustness-guard overhead: the same fused decode workload with the
    non-finite sampling guard compiled in (``enable_guards=True``, the
    default) vs compiled out.  The guard is a trace-static flag — guards
    off re-traces to the exact pre-guard program — so the warm fused
    decode-step latency must be indistinguishable; each row is the min
    over ``reps`` runs (min, not mean: scheduler noise only ever adds
    time)."""
    key = jax.random.PRNGKey(0)
    cfg = get_reduced("qwen1.5-0.5b", num_layers=4, num_heads=8,
                      num_kv_heads=2)
    params = T.init_params(cfg, key)
    n_req = 4 if smoke else 12
    mnt = 12 if smoke else 64
    reps = 3 if smoke else 5

    def one(guards):
        eng = ServingEngine(cfg, params, max_slots=4, num_blocks=256,
                            max_blocks_per_seq=16,
                            max_num_batched_tokens=64, max_horizon=4,
                            enable_guards=guards, enable_async_step=False)
        rng = np.random.default_rng(0)
        prefix = list(rng.integers(1, 200, 24))
        sp = SamplingParams(max_tokens=mnt)
        for _ in range(n_req):
            eng.add(prefix + list(rng.integers(
                1, 200, int(rng.integers(4, 24)))), sp)
        return eng.run_until_done()

    one(True)                        # warm both jit caches before timing
    one(False)
    # paired design: each rep times off then on back-to-back, and the
    # gate reads the BEST pair's ratio — load spikes only ever inflate a
    # pair, so one clean pair suffices to show the guard costs nothing
    best, ratios = _paired_best(reps, [("off", lambda: one(False)),
                                       ("on", lambda: one(True))])
    for name, r in best.items():
        emit(f"guards_{name}", r["decode_step_latency_us"],
             f"gen_tok_s={r['generate_tok_s']:.1f};"
             f"dispatches_per_step={r['device_dispatches_per_step']:.2f};"
             + (f"pair_ratio_min={min(ratios):.4f};" if name == "on" else "")
             + f"reps={reps}")


def table_chunked_prefill(smoke: bool = False) -> None:
    """Mixed workload: one long prompt arrives over a warm decoding
    batch.  Stop-the-world prefill (``chunked_prefill_off``) stalls every
    running request for the whole-prompt duration — the stall lands in
    ``itl_p99`` (the ``us_per_call`` column) — and pays a fresh prefill
    compile per (wave, bucket) shape.  The token-budget planner
    (``chunked_prefill_on``) interleaves the prompt's chunks between
    decode steps: ITL p99 drops to O(chunk), TTFT of the long request is
    reported as ``ttft_long_ms``, and the chunk executable compiles
    exactly once (asserted here — the recompile-explosion acceptance
    gate)."""
    import time as _time
    key = jax.random.PRNGKey(0)
    cfg = get_reduced("qwen1.5-0.5b", num_layers=4, num_heads=8,
                      num_kv_heads=2)
    params = T.init_params(cfg, key)
    long_len = 256 if smoke else 1024
    bs = cfg.paging.block_size
    mb = long_len // bs + 4
    itl = {}
    for name, chunked in (("off", False), ("on", True)):
        eng = ServingEngine(cfg, params, max_slots=4, num_blocks=mb + 32,
                            max_blocks_per_seq=mb, prefill_bucket=64,
                            enable_chunked_prefill=chunked,
                            max_num_batched_tokens=128, max_horizon=4,
                            enable_async_step=False)
        rng = np.random.default_rng(0)
        sp = SamplingParams(max_tokens=32 if smoke else 64)
        for _ in range(3):
            eng.add(list(rng.integers(1, 200, int(rng.integers(8, 24)))), sp)
        for _ in range(4):
            eng.step()                      # the short batch is decoding
        eng.reset_itl_window()              # ITL window: steady state only
        rid = eng.add(list(rng.integers(1, 200, long_len)),
                      SamplingParams(max_tokens=8))
        t_arr = _time.perf_counter()
        eng.run_until_done()
        rep = eng.report()
        rec = next(r for r in eng.finished if r.rid == rid)
        ttft_long = (rec.first_token_t - t_arr) * 1e3
        itl[name] = rep["itl_p99_ms"]
        # budget_util only exists in chunked mode, and prefill_compiles
        # is NaN if the private jax cache API drifted; never emit NaN
        # (it would make the committed BENCH_serving.json invalid JSON)
        util = (f"budget_util={rep['budget_utilization']:.2f};"
                if np.isfinite(rep["budget_utilization"]) else "")
        compiles = rep["prefill_compiles"]
        emit(f"chunked_prefill_{name}", rep["itl_p99_ms"] * 1e3,
             f"itl_p50_ms={rep['itl_p50_ms']:.2f};"
             f"ttft_long_ms={ttft_long:.1f};"
             f"prefill_chunks={int(rep['prefill_chunks'])};"
             + (f"prefill_compiles={int(compiles)};"
                if np.isfinite(compiles) else "")
             + f"{util}"
             f"gen_tok_s={rep['generate_tok_s']:.1f}")
        if chunked:
            if not np.isfinite(compiles):
                print("skipping compile-count gate: jax jit _cache_size "
                      "API unavailable (drift, not a regression)")
            else:
                assert compiles == 1, \
                    f"chunk executable compiled {compiles:.0f}x"
    assert itl["on"] < itl["off"], \
        f"chunked ITL p99 {itl['on']:.1f}ms not under " \
        f"stop-the-world {itl['off']:.1f}ms"


def table_unified(smoke: bool = False) -> None:
    """Unified single-dispatch step vs the two-call mixed execute on the
    PR 4 mixed workload (one long prompt chunking over a warm decoding
    batch).  ``unified_on`` must show EXACTLY 1.0 device dispatches per
    engine iteration across the steady mixed window (the two-call path
    pays a decode dispatch + a chunk dispatch + a first-token sample
    dispatch, ~2-3), with mixed-workload ITL p99 at or under the
    two-call baseline and the unified executable compiled once."""
    import time as _time
    key = jax.random.PRNGKey(0)
    cfg = get_reduced("qwen1.5-0.5b", num_layers=4, num_heads=8,
                      num_kv_heads=2)
    params = T.init_params(cfg, key)
    long_len = 256 if smoke else 1024
    bs = cfg.paging.block_size
    mb = long_len // bs + 4
    itl = {}
    disp = {}
    for name, unified in (("off", False), ("on", True)):
        eng = ServingEngine(cfg, params, max_slots=4, num_blocks=mb + 32,
                            max_blocks_per_seq=mb,
                            enable_unified_step=unified,
                            max_num_batched_tokens=128, max_horizon=4,
                            enable_async_step=False)
        rng = np.random.default_rng(0)
        sp = SamplingParams(max_tokens=32 if smoke else 64)
        for _ in range(3):
            eng.add(list(rng.integers(1, 200, int(rng.integers(8, 24)))), sp)
        # warm-up prompt longer than the budget: compiles every mixed-
        # phase executable (chunk / unified / sample) BEFORE the measured
        # window, so the ITL comparison is steady-state on both paths
        eng.add(list(rng.integers(1, 200, 160)), SamplingParams(max_tokens=2))
        while any(s.prefilling for s in eng.running.values()) or \
                len(eng.finished) < 1:
            eng.step()                      # warm-up prompt in and out
        for _ in range(4):
            eng.step()                      # the short batch is decoding
        eng.reset_itl_window()              # steady state only: compiles
        eng.reset_dispatch_window()         # and warm-up CoW excluded
        rid = eng.add(list(rng.integers(1, 200, long_len)),
                      SamplingParams(max_tokens=8))
        t_arr = _time.perf_counter()
        # measure the dispatch window over the mixed phase only (the
        # all-decode drain after the prompt lands is megastep territory
        # on both paths)
        mixed_steps = 0
        while any(s.prefilling for s in eng.running.values()) or \
                any(r.rid == rid for r in eng.waiting):
            eng.step()
            mixed_steps += 1
        rep_mixed = eng.report()
        disp[name] = rep_mixed["device_dispatches_per_step"]
        # ROADMAP item 1, measured: host-vs-device wall-time split per
        # mixed-phase step (obs span attribution) — the host share is
        # the serialization the async engine direction would overlap
        attr = eng.attribution(window=mixed_steps)
        eng.run_until_done()
        rep = eng.report()
        rec = next(r for r in eng.finished if r.rid == rid)
        ttft_long = (rec.first_token_t - t_arr) * 1e3
        itl[name] = rep["itl_p99_ms"]
        compiles = rep["prefill_compiles"]
        emit(f"unified_{name}", rep["itl_p99_ms"] * 1e3,
             f"itl_p50_ms={rep['itl_p50_ms']:.2f};"
             f"dispatches_per_step={disp[name]:.2f};"
             f"ttft_long_ms={ttft_long:.1f};"
             + (f"host_ms={attr['host_ms']:.3f};"
                f"device_ms={attr['device_ms']:.3f};"
                if np.isfinite(attr["host_ms"]) else "")
             + (f"prefill_compiles={int(compiles)};"
                if np.isfinite(compiles) else "")
             + f"gen_tok_s={rep['generate_tok_s']:.1f}")
        if unified:
            assert disp["on"] == 1.0, \
                f"unified mixed step dispatched {disp['on']:.2f}x/step"
            if np.isfinite(compiles):
                assert compiles == 1, \
                    f"unified executable compiled {compiles:.0f}x"
    assert disp["off"] >= 1.5, \
        f"two-call path reads {disp['off']:.2f} dispatches/step — the " \
        "comparison lost its baseline"
    # acceptance: unified ITL p99 at or under the two-call baseline
    # (1.05 slack absorbs CI timer noise; the dispatch assert above is
    # the deterministic gate)
    assert itl["on"] <= itl["off"] * 1.05, \
        f"unified ITL p99 {itl['on']:.2f}ms above two-call " \
        f"{itl['off']:.2f}ms"


def table_async(smoke: bool = False) -> None:
    """Async pipelined step vs the synchronous two-call mixed execute on
    a SUSTAINED mixed workload: a queue of long prompts chunks over a
    warm decoding batch for the whole measured window, so the steady
    state being timed is the mixed phase the pipeline optimizes (a
    single long prompt's 2-3 chunk steps drown in the all-decode drain).
    ``async_on`` plans and enqueues dispatch N+1 while N executes on
    device — token readback deferred exactly one step
    (``enable_async_step=True``, the default); ``async_off`` is the
    two-call path (``enable_unified_step=False``) that reads back every
    step.  Interleaved paired reps; the ``--assert-async-itl`` gate
    reads the best back-to-back pair's ITL p50 ratio.  The async row
    must keep EXACTLY 1.0 device dispatches per mixed step, actually
    pipeline (``async_steps > 0``), and compile the chained unified
    executable exactly once (zero steady-state recompiles)."""
    import time as _time
    key = jax.random.PRNGKey(0)
    cfg = get_reduced("qwen1.5-0.5b", num_layers=4, num_heads=8,
                      num_kv_heads=2)
    params = T.init_params(cfg, key)
    long_len = 256 if smoke else 512
    bs = cfg.paging.block_size
    mb = long_len // bs + 4
    n_long = 3 if smoke else 5
    reps = 2 if smoke else 3

    def one(name):
        kw = dict(enable_async_step=True) if name == "on" else \
            dict(enable_unified_step=False, enable_async_step=False)
        eng = ServingEngine(cfg, params, max_slots=4,
                            num_blocks=4 * mb + 32, max_blocks_per_seq=mb,
                            max_num_batched_tokens=128, max_horizon=4,
                            **kw)
        rng = np.random.default_rng(0)
        # the short batch must keep decoding through the whole mixed
        # window (finished slots would thin the decode rows both paths
        # share and admit longs in bursts, adding admission noise)
        sp = SamplingParams(max_tokens=64)
        for _ in range(3):
            eng.add(list(rng.integers(1, 200, int(rng.integers(8, 24)))),
                    sp)
        # warm-up prompt longer than the budget compiles every mixed-
        # phase executable before the measured window (see table_unified)
        eng.add(list(rng.integers(1, 200, 160)),
                SamplingParams(max_tokens=2))
        while any(s.prefilling for s in eng.running.values()) or \
                len(eng.finished) < 1:
            eng.step()
        for _ in range(4):
            eng.step()                      # the short batch is decoding
        eng.reset_itl_window()              # steady state only
        eng.reset_dispatch_window()
        longs = {eng.add(list(rng.integers(1, 200, long_len)),
                         SamplingParams(max_tokens=8))
                 for _ in range(n_long)}
        t_arr = _time.perf_counter()
        mixed_steps = 0
        while sum(1 for r in eng.finished if r.rid in longs) < n_long:
            eng.step()
            mixed_steps += 1
        # percentiles read HERE cover exactly the mixed window (the
        # all-decode drain that follows is identical megastep territory
        # on both paths and would only dilute the comparison)
        rep_mixed = eng.report()
        attr = eng.attribution(window=mixed_steps)
        eng.run_until_done()
        rep = eng.report()
        rec = next(r for r in eng.finished if r.rid == min(longs))
        eng.close()
        return {"itl_p50_ms": rep_mixed["itl_p50_ms"],
                "itl_p99_ms": rep_mixed["itl_p99_ms"],
                "dispatches": rep_mixed["device_dispatches_per_step"],
                "async_steps": rep["async_steps"],
                "compiles": rep["prefill_compiles"],
                "host_ms": attr["host_ms"], "device_ms": attr["device_ms"],
                "ttft_long_ms": (rec.first_token_t - t_arr) * 1e3,
                "gen_tok_s": rep["generate_tok_s"]}

    one("off")                       # warm both jit caches before timing
    one("on")
    best, ratios = _paired_best(reps, [("off", lambda: one("off")),
                                       ("on", lambda: one("on"))],
                                key="itl_p50_ms")
    for name, r in best.items():
        emit(f"async_{name}", r["itl_p50_ms"] * 1e3,
             f"itl_p99_ms={r['itl_p99_ms']:.2f};"
             f"dispatches_per_step={r['dispatches']:.2f};"
             f"async_steps={int(r['async_steps'])};"
             f"ttft_long_ms={r['ttft_long_ms']:.1f};"
             + (f"host_ms={r['host_ms']:.3f};"
                f"device_ms={r['device_ms']:.3f};"
                if np.isfinite(r["host_ms"]) else "")
             + (f"prefill_compiles={int(r['compiles'])};"
                if np.isfinite(r["compiles"]) else "")
             + (f"pair_ratio_min={min(ratios):.4f};" if name == "on"
                else "")
             + f"gen_tok_s={r['gen_tok_s']:.1f}")
    on, off = best["on"], best["off"]
    assert on["dispatches"] == 1.0, \
        f"async mixed step dispatched {on['dispatches']:.2f}x/step"
    assert on["async_steps"] > 0, "the pipeline never engaged"
    assert off["async_steps"] == 0, "the sync oracle speculated"
    if np.isfinite(on["compiles"]):
        assert on["compiles"] == 1, \
            f"chained unified executable compiled {on['compiles']:.0f}x"


def assert_async_itl(rows, max_ratio: float) -> None:
    """Acceptance gate (hard): the async pipelined step's steady-state
    ITL p50 must not exceed ``max_ratio`` x the two-call synchronous
    path's in the same run (1.0 = at or under it).  Reads the best
    back-to-back (off, on) pair ratio from ``table_async`` — load
    spikes inflate pairs, never deflate them, so the minimum pair ratio
    is the honest estimate."""
    ratio = None
    for row in rows:
        name, _, derived = row.split(",", 2)
        if name == "async_on":
            for field in derived.split(";"):
                if field.startswith("pair_ratio_min="):
                    ratio = float(field.split("=", 1)[1])
    assert ratio is not None, "async_on row (pair_ratio_min) missing"
    if ratio > max_ratio:
        print(f"REGRESSION: async/two-call ITL p50 pair ratio "
              f"{ratio:.4f} > {max_ratio:.2f}", file=sys.stderr)
        sys.exit(1)
    print(f"async/two-call ITL p50 pair ratio {ratio:.4f} "
          f"(allowed {max_ratio:.2f}): OK")


def table_telemetry(smoke: bool = False) -> None:
    """Span-tracer overhead: the same fused decode workload with the obs
    tracer recording every step (``enable_telemetry=True``, the default)
    vs handing out the no-op singleton.  The hot-path cost is two
    ``perf_counter_ns`` calls and a deque append per span, so the warm
    fused decode step must be indistinguishable; same paired design as
    ``table_guards`` (best back-to-back pair ratio, min over reps)."""
    key = jax.random.PRNGKey(0)
    cfg = get_reduced("qwen1.5-0.5b", num_layers=4, num_heads=8,
                      num_kv_heads=2)
    params = T.init_params(cfg, key)
    n_req = 4 if smoke else 12
    mnt = 12 if smoke else 64
    reps = 3 if smoke else 5

    def one(telemetry):
        eng = ServingEngine(cfg, params, max_slots=4, num_blocks=256,
                            max_blocks_per_seq=16,
                            max_num_batched_tokens=64, max_horizon=4,
                            enable_telemetry=telemetry,
                            enable_async_step=False)
        rng = np.random.default_rng(0)
        prefix = list(rng.integers(1, 200, 24))
        sp = SamplingParams(max_tokens=mnt)
        for _ in range(n_req):
            eng.add(prefix + list(rng.integers(
                1, 200, int(rng.integers(4, 24)))), sp)
        return eng.run_until_done()

    one(True)                        # warm both jit caches before timing
    one(False)
    best, ratios = _paired_best(reps, [("off", lambda: one(False)),
                                       ("on", lambda: one(True))])
    for name, r in best.items():
        emit(f"telemetry_{name}", r["decode_step_latency_us"],
             f"gen_tok_s={r['generate_tok_s']:.1f};"
             f"itl_p50_ms={r['itl_p50_ms']:.2f};"
             + (f"pair_ratio_min={min(ratios):.4f};" if name == "on" else "")
             + f"reps={reps}")


def assert_telemetry_overhead(rows, max_ratio: float) -> None:
    """Acceptance gate: recording spans must not change the warm fused
    decode step by more than ``max_ratio`` (1.02 = 2%).  Reads the best
    back-to-back (off, on) pair ratio from ``table_telemetry`` — load
    spikes inflate pairs, never deflate them, so the minimum pair ratio
    is the honest estimate of the tracer's intrinsic cost."""
    ratio = None
    for row in rows:
        name, _, derived = row.split(",", 2)
        if name == "telemetry_on":
            for field in derived.split(";"):
                if field.startswith("pair_ratio_min="):
                    ratio = float(field.split("=", 1)[1])
    assert ratio is not None, "telemetry_on row (pair_ratio_min) missing"
    if ratio > max_ratio:
        print(f"REGRESSION: telemetry-on/off warm-step pair ratio "
              f"{ratio:.4f} > {max_ratio:.2f}", file=sys.stderr)
        sys.exit(1)
    print(f"telemetry-on/off warm-step pair ratio {ratio:.4f} "
          f"(allowed {max_ratio:.2f}): OK")


def assert_no_regression(rows, baseline_path: str, factor: float,
                         smoke: bool = False) -> None:
    """Warm fused decode-step latency must stay within ``factor`` x the
    committed baseline (acceptance: no warm-decode-step regression).
    Only like-for-like comparisons are meaningful: if the baseline was
    recorded in a different mode (smoke vs full workload), the gate is
    skipped with a notice instead of comparing incomparable numbers."""
    with open(baseline_path) as f:
        doc = json.load(f)
    base_smoke = bool(doc.get("meta", {}).get("smoke"))
    if base_smoke != smoke:
        print(f"skipping regression gate: baseline {baseline_path} was "
              f"recorded with smoke={base_smoke}, this run is "
              f"smoke={smoke} (different workloads)")
        return
    base_rows = {r["name"]: r for r in doc["rows"]}
    if "fastpath_fused" not in base_rows:
        print(f"skipping regression gate: {baseline_path} has no "
              f"fastpath_fused row")
        return
    base = base_rows["fastpath_fused"]["us_per_call"]
    cur = None
    for row in rows:
        name, us, _ = row.split(",", 2)
        if name == "fastpath_fused":
            cur = float(us)
    assert cur is not None, "fastpath_fused row missing from this run"
    if cur > base * factor:
        print(f"REGRESSION: fused warm decode step {cur:.1f}us > "
              f"{factor:.2f} x baseline {base:.1f}us", file=sys.stderr)
        sys.exit(1)
    print(f"fused warm decode step {cur:.1f}us vs baseline {base:.1f}us "
          f"(allowed {factor:.2f}x): OK")


def assert_fastpath_ratio(rows, max_ratio: float) -> None:
    """Machine-independent gate: within THIS run, the fused megastep's
    warm decode step must stay under ``max_ratio`` x the legacy loop's.
    Catches the fast path breaking (ratio -> ~1.0) regardless of how
    slow the host is, so it is safe on shared CI runners."""
    us = {}
    for row in rows:
        name, v, _ = row.split(",", 2)
        if name in ("fastpath_legacy", "fastpath_fused"):
            us[name] = float(v)
    ratio = us["fastpath_fused"] / us["fastpath_legacy"]
    if ratio > max_ratio:
        print(f"REGRESSION: fused/legacy warm-step ratio {ratio:.3f} > "
              f"{max_ratio:.2f} ({us['fastpath_fused']:.1f}us vs "
              f"{us['fastpath_legacy']:.1f}us)", file=sys.stderr)
        sys.exit(1)
    print(f"fused/legacy warm-step ratio {ratio:.3f} "
          f"(allowed {max_ratio:.2f}): OK")


def assert_guard_overhead(rows, max_ratio: float) -> None:
    """Acceptance gate: the compiled-in non-finite guard must not change
    the warm fused decode step by more than ``max_ratio`` (e.g. 1.02 =
    2%).  Uses the best back-to-back (off, on) pair's ratio from
    ``table_guards`` — machine-independent AND load-spike-tolerant: a
    busy runner inflates pairs, never deflates them, so the minimum pair
    ratio is the honest estimate of the guard's intrinsic cost."""
    ratio = None
    for row in rows:
        name, _, derived = row.split(",", 2)
        if name == "guards_on":
            for field in derived.split(";"):
                if field.startswith("pair_ratio_min="):
                    ratio = float(field.split("=", 1)[1])
    assert ratio is not None, "guards_on row (pair_ratio_min) missing"
    if ratio > max_ratio:
        print(f"REGRESSION: guards-on/guards-off warm-step pair ratio "
              f"{ratio:.4f} > {max_ratio:.2f}", file=sys.stderr)
        sys.exit(1)
    print(f"guards-on/guards-off warm-step pair ratio {ratio:.4f} "
          f"(allowed {max_ratio:.2f}): OK")


def run(smoke: bool = False) -> None:
    table_fig2(smoke)
    table_fig3(smoke)
    table_fastpath(smoke)
    table_kv_memory(smoke)
    table_guards(smoke)
    table_telemetry(smoke)
    table_chunked_prefill(smoke)
    table_unified(smoke)
    table_async(smoke)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced request counts (CI)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (e.g. BENCH_serving.json)")
    ap.add_argument("--assert-baseline", default=None, metavar="PATH",
                    help="fail if fused warm decode-step latency regressed "
                         "vs this BENCH_serving.json")
    ap.add_argument("--regress-factor", type=float, default=1.10,
                    help="allowed slowdown factor for --assert-baseline")
    ap.add_argument("--assert-fastpath-ratio", type=float, default=None,
                    metavar="R", help="fail if fused/legacy warm-step "
                    "ratio within this run exceeds R (machine-independent)")
    ap.add_argument("--assert-guard-overhead", type=float, default=None,
                    metavar="R", help="fail if guards_on/guards_off warm-"
                    "step ratio exceeds R (acceptance: 1.02)")
    ap.add_argument("--assert-telemetry-overhead", type=float, default=None,
                    metavar="R", help="fail if telemetry_on/telemetry_off "
                    "warm-step ratio exceeds R (acceptance: 1.02)")
    ap.add_argument("--assert-async-itl", type=float, default=None,
                    metavar="R", help="fail if async_on/async_off ITL p50 "
                    "pair ratio exceeds R (acceptance: 1.0 — the pipelined "
                    "step must be at or under the two-call path)")
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    run(smoke=args.smoke)
    from benchmarks.common import ROWS
    if args.json:
        from benchmarks.report import write_bench_json
        write_bench_json(ROWS, args.json, smoke=args.smoke)
        print(f"wrote {args.json}")
    if args.assert_baseline:
        assert_no_regression(ROWS, args.assert_baseline,
                             args.regress_factor, smoke=args.smoke)
    if args.assert_fastpath_ratio is not None:
        assert_fastpath_ratio(ROWS, args.assert_fastpath_ratio)
    if args.assert_guard_overhead is not None:
        assert_guard_overhead(ROWS, args.assert_guard_overhead)
    if args.assert_telemetry_overhead is not None:
        assert_telemetry_overhead(ROWS, args.assert_telemetry_overhead)
    if args.assert_async_itl is not None:
        assert_async_itl(ROWS, args.assert_async_itl)


if __name__ == "__main__":
    main()
