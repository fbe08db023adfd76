"""Serving driver: continuous batching over the paged engine via ``LLM``.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --requests 16 [--quant gptq-int4] [--stream] [--top-k 40] \
        [--top-p 0.95] [--temperature 0.8] [--stop 13 198] [--mha-baseline]

``--mha-baseline`` serves the same arch with kv_heads == num_heads and
prefix reuse off — the paper's comparison point (Fig. 2). ``--stream``
prints each ``RequestOutput`` delta as horizons complete instead of
waiting for the batch to drain.

Robustness knobs (see docs/API.md "Fault tolerance"): ``--max-waiting N``
bounds the intake queue with ``--shed-policy {reject,shed-oldest}``
deciding what happens when it is full (``reject`` raises
``EngineOverloadedError`` at submit — with this driver's submit-all-
upfront pattern that aborts the run, which is the point of the policy;
``shed-oldest`` finishes the oldest waiting request with
``finish_reason='shed'``), and ``--deadline-ms`` attaches an end-to-end
deadline to every request (``finish_reason='deadline'`` on expiry).

Observability knobs (see docs/OBSERVABILITY.md): ``--metrics-port N``
serves ``/metrics`` (Prometheus), ``/health`` (JSON) and ``/trace``
(Chrome trace JSON) on localhost while the run executes;
``--trace-out f.json`` writes the span timeline at exit (open in
Perfetto); ``--metrics-out f.json`` dumps the registry snapshot;
``--profile-dir d/`` wraps the run in a ``jax.profiler`` capture with
every engine span mirrored as a TraceAnnotation (this keeps the span
tracer on); ``--no-enable-telemetry`` turns the span tracer off (the
metrics registry is always on).
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.configs.base import PagingConfig
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import LLM, SamplingParams


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the tiny same-family CPU config "
                         "(--no-reduced loads the full-size one)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-tokens", "--max-new", dest="max_tokens",
                    type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--blocks", type=int, default=256)
    ap.add_argument("--quant", default=None,
                    choices=["rtn-int4", "gptq-int4"],
                    help="serve int4 weights (Opt-GPTQ configuration): "
                         "RTN or Hessian-based GPTQ")
    ap.add_argument("--kv-cache-dtype", default="bf16",
                    choices=["bf16", "int8"],
                    help="paged KV pool format: int8 quantizes K/V on "
                         "write (per-block-per-head scales, ~2x lower KV "
                         "bytes/token vs bf16)")
    ap.add_argument("--checkpoint", default=None,
                    help="Checkpointer directory to restore params from")
    ap.add_argument("--max-num-batched-tokens", type=int, default=256,
                    help="per-step token budget: running decodes are "
                         "packed first, prefill chunks fill the rest "
                         "(bounds inter-token latency at O(chunk))")
    ap.add_argument("--enable-chunked-prefill",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="--no-enable-chunked-prefill restores the "
                         "stop-the-world whole-prompt prefill (the "
                         "parity oracle; also the path non-full-"
                         "attention archs always use)")
    ap.add_argument("--enable-unified-step",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="--no-enable-unified-step restores the two-call "
                         "mixed step (separate decode / prefill-chunk / "
                         "sample dispatches) — the unified single-"
                         "dispatch step's parity oracle")
    ap.add_argument("--enable-async-step",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="--no-enable-async-step restores the read-back-"
                         "every-step loop — the async pipelined step "
                         "(plan/enqueue N+1 while N executes, tokens "
                         "read back one step late) is on by default in "
                         "unified mode")
    ap.add_argument("--max-waiting", type=int, default=None,
                    help="bound the waiting queue; arrivals past the "
                         "bound are handled per --shed-policy")
    ap.add_argument("--shed-policy", default="reject",
                    choices=["reject", "shed-oldest"],
                    help="full-queue policy: 'reject' refuses the new "
                         "request (EngineOverloadedError), 'shed-oldest' "
                         "finishes the oldest waiting request with "
                         "finish_reason='shed' to make room")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request end-to-end deadline from arrival; "
                         "expired requests finish with "
                         "finish_reason='deadline'")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--stop", type=int, nargs="*", default=[],
                    help="stop token ids (finish_reason='stop')")
    ap.add_argument("--stream", action="store_true",
                    help="print RequestOutput deltas as they arrive")
    ap.add_argument("--mha-baseline", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--enable-telemetry",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="--no-enable-telemetry disables the span tracer "
                         "(zero-work no-op); counters/histograms stay on")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics (Prometheus), /health (JSON) and "
                         "/trace (Chrome JSON) on 127.0.0.1:PORT for the "
                         "duration of the run")
    ap.add_argument("--trace-out", default=None,
                    help="write the span timeline as Chrome-trace JSON "
                         "at exit (load in Perfetto / about:tracing)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics-registry JSON snapshot at exit")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the run into "
                         "this directory (mirrors every engine span as a "
                         "TraceAnnotation)")
    args = ap.parse_args()
    enable_compile_cache()

    overrides = {}
    if args.mha_baseline:
        from repro.configs.registry import get_config, get_reduced
        base = get_reduced(args.arch) if args.reduced else \
            get_config(args.arch)
        overrides = dict(num_kv_heads=base.num_heads,
                         paging=PagingConfig(enable_prefix_reuse=False))
    llm = LLM.load(args.arch, quant=args.quant,
                   kv_cache_dtype=args.kv_cache_dtype,
                   checkpoint=args.checkpoint,
                   reduced=args.reduced, overrides=overrides,
                   seed=args.seed, max_slots=args.slots,
                   num_blocks=args.blocks, max_blocks_per_seq=16,
                   max_num_batched_tokens=args.max_num_batched_tokens,
                   enable_chunked_prefill=args.enable_chunked_prefill,
                   enable_unified_step=args.enable_unified_step,
                   enable_async_step=args.enable_async_step,
                   max_waiting=args.max_waiting,
                   shed_policy=args.shed_policy,
                   prefill_bucket=32,
                   enable_telemetry=args.enable_telemetry,
                   profile_labels=bool(args.profile_dir))

    server = None
    if args.metrics_port is not None:
        from repro.obs.http import start_obs_server
        server = start_obs_server(args.metrics_port,
                                  registry=llm.engine.obs,
                                  health_fn=llm.engine.health,
                                  tracer=llm.engine.tracer)
        print(f"# obs endpoint on http://127.0.0.1:"
              f"{server.server_address[1]} (/metrics /health /trace)")
    if args.profile_dir:
        import jax
        jax.profiler.start_trace(args.profile_dir)

    rng = np.random.default_rng(args.seed)
    prefix = list(rng.integers(1, 200, 24))
    prompts = [prefix + list(rng.integers(1, 200, int(rng.integers(4, 32))))
               for _ in range(args.requests)]
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p, stop=list(args.stop),
                        max_tokens=args.max_tokens,
                        deadline_ms=args.deadline_ms)

    try:
        if args.stream:
            for out in llm.stream(prompts, sp):
                print(json.dumps({
                    "rid": out.request_id, "new": out.new_token_ids,
                    "n_total": len(out.token_ids),
                    "finish_reason": out.finish_reason}))
        else:
            outs = llm.generate(prompts, sp)
            for out in outs:
                print(json.dumps({"rid": out.request_id,
                                  "tokens": out.token_ids,
                                  "finish_reason": out.finish_reason}))
        if args.profile_dir:
            import jax
            jax.profiler.stop_trace()
        if args.trace_out:
            llm.engine.tracer.save(args.trace_out)
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                json.dump(llm.engine.obs.snapshot(), f, indent=1)
        attr = llm.engine.attribution()
        if attr["steps"]:
            print(json.dumps({"attribution": {k: round(float(v), 4)
                                              for k, v in attr.items()}}))
    finally:
        # flush the async pipeline + detok worker, stop the obs server
        # thread — even when the run aborts (EngineOverloadedError under
        # --shed-policy reject, Ctrl-C, a poisoned run), nothing leaks
        llm.close()
        if server is not None:
            server.shutdown()
    rep = llm.engine.report()
    mode = ("mha" if args.mha_baseline else "opt-gqa") + \
        (f"+{args.quant}" if args.quant else "") + \
        (f"+kv-{args.kv_cache_dtype}" if args.kv_cache_dtype != "bf16"
         else "")
    print(json.dumps({"mode": mode, **{k: round(float(v), 4)
                                       for k, v in rep.items()}}, indent=1))


if __name__ == "__main__":
    main()
