"""Continuous-batching serving engine facade (the vLLM role in the paper).

The engine is a thin conductor over two halves:

* ``serving.scheduler.Scheduler`` — pure host policy: admission
  (watermark + prompt clamping), slot/block accounting, recompute-style
  preemption, capacity force-finishing, and the per-iteration token
  budget plan (``plan_step``): running decodes packed first
  (decode-priority, so inter-token latency stays bounded at O(chunk)
  instead of O(longest prompt)), then prefill *chunks* of
  partially-admitted prompts into the remaining
  ``max_num_batched_tokens``, with KV blocks allocated incrementally
  per chunk;
* ``serving.model_runner.ModelRunner`` — the device: paged KV pools,
  the fixed-shape unified step (compiled ONCE regardless of prompt
  length or wave composition), the fused decode megastep, CoW block
  copies, on-device per-slot sampling.

One executable serves each situation the engine can observe.  A plan
with prefill work runs as unified dispatches: the first fuses the
step's decodes, a prefill chunk (through the dynamic-offset chunk-flash
path) and every row's sampling under one donated jit, with one
``[max_slots + 1]`` token readback; further chunks of an admission
burst dispatch chunk-only.  A decode-only plan runs the megastep, one
donated dispatch per multi-token horizon (``max_horizon=1`` gives one
dispatch per token through the same decode step and sampling kernel).
An arch whose prefill state cannot yet re-enter mid-prompt (SSM /
recurrent / sliding-ring stacks), or ``enable_chunked_prefill=False``,
takes the stop-the-world whole-prompt wave instead of chunks.

``enable_async_step=True`` (default; rides the unified executable)
pipelines the loop one step deep: an iteration plans and ENQUEUES its
unified dispatch chained on the previous, still in-flight one — the
decode feed tokens are gathered on device from that dispatch's output
buffer — and only then reads the previous step's tokens back, so every
host millisecond (plan, absorb, detokenize via the background worker,
bookkeeping) overlaps device execution.  The scheduler plans
speculatively (``Sequence.speculated``: in-flight tokens counted into
``seq_len`` but not ``req.output``) and reconciles at readback;
finish/abort/preemption during the flight discards the speculated
token, which recompute replay regenerates token-exactly.  All donating
dispatches (megastep, CoW, chunk bursts) flush the pipeline first.
``enable_async_step=False`` keeps the read-back-every-step engine as
the pipeline's parity oracle.

Requests enter with a ``SamplingParams`` (temperature / top_k / top_p /
seed / stop token ids / max_tokens) that is lowered to padded per-slot
device arrays, so one batch freely mixes greedy, temperature and
top-k/top-p requests, with one host↔device round trip per dispatch.

Results stream back as ``RequestOutput`` deltas: ``step()`` returns the
events produced by that iteration and ``stream()`` yields them as
horizons complete, so callers see tokens long before the batch drains —
and ``add_request`` / ``add`` may be called while streaming (continuous
intake). ``run_until_done`` is retained as the drain-everything driver.

The pre-redesign surface — ``ServingEngine(cfg, params)`` plus the bare
``Request(prompt, max_new_tokens, temperature)`` — keeps working as a
deprecation shim for one release; new code should construct via
``serving.llm.LLM`` and speak ``SamplingParams`` / ``RequestOutput``.
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence as SeqT

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.paged_cache import BlockAllocator
from repro.models import transformer as T
from repro.obs.metrics import MetricsDict, MetricsRegistry
from repro.obs.trace import SpanTracer, attribute_steps
from repro.runtime.fault import StragglerDetector
from repro.serving.detok import DetokWorker
from repro.serving.faults import (FaultInjector, PoisonedDispatchError,
                                  TransientDeviceError)
from repro.serving.model_runner import ModelRunner
from repro.serving.params import (FINISH_ABORT, FINISH_ERROR, FINISH_LENGTH,
                                  FINISH_SHED, FINISH_STOP, RequestOutput,
                                  SamplingParams)
from repro.serving.scheduler import (RequestState, Scheduler, Sequence,
                                     StepPlan, UnifiedDispatch)


class EngineOverloadedError(RuntimeError):
    """``add`` refused a request: the waiting queue is at ``max_waiting``
    and the engine's shed policy is "reject"."""


@dataclass
class Request:
    """Deprecated pre-``SamplingParams`` request record (one-release shim).

    Use ``engine.add(prompt, SamplingParams(...))`` instead; this maps
    onto it via ``add_request`` and keeps filling ``output`` in place.
    """
    rid: int
    prompt: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    arrival: float = 0.0
    output: List[int] = field(default_factory=list)
    first_token_t: Optional[float] = None
    done_t: Optional[float] = None


@dataclass
class _Flight:
    """One in-flight (enqueued, not yet read back) unified dispatch.

    ``out`` is the dispatch's device-side ``[max_slots + 1]`` token
    buffer — the NEXT dispatch gathers its feed tokens from it on
    device, and the host reads it back one step later.  ``decode_rows``
    / ``chunk_seq`` name the sequences whose sampled token the buffer
    carries; ``source_row`` maps ``id(Sequence)`` to its row so the
    successor dispatch can chain on it (row ``max_slots`` is the chunk
    sample).  Holding the Sequence *objects* (not slots) lets the
    collect path detect finish/abort/preemption-and-readmission during
    the flight by identity."""
    out: object
    decode_rows: List[tuple] = field(default_factory=list)
    chunk_seq: Optional[Sequence] = None
    source_row: Dict[int, int] = field(default_factory=dict)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 8,
                 num_blocks: int = 512, max_blocks_per_seq: int = 64,
                 prefill_bucket: int = 64, rt: Optional[dict] = None,
                 seed: int = 0, max_horizon: int = 8, detokenizer=None,
                 kv_cache_dtype: str = "bf16",
                 max_num_batched_tokens: int = 256,
                 enable_chunked_prefill: bool = True,
                 enable_async_step: bool = True,
                 max_waiting: Optional[int] = None,
                 shed_policy: str = "reject",
                 enable_guards: bool = True,
                 fault_injector: Optional[FaultInjector] = None,
                 max_dispatch_retries: int = 2,
                 retry_backoff_s: float = 0.0,
                 enable_telemetry: bool = True,
                 trace_capacity: int = 65536,
                 profile_labels: bool = False):
        if shed_policy not in ("reject", "shed-oldest"):
            raise ValueError(f"shed_policy {shed_policy!r}: expected "
                             "'reject' or 'shed-oldest'")
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.mb = max_blocks_per_seq
        self.prefill_bucket = prefill_bucket
        self.max_horizon = max(1, max_horizon)
        self.detokenizer = detokenizer
        self.seed = seed
        # ---- observability (tentpole: see docs/OBSERVABILITY.md) ----
        # the registry is the single source of truth for every number
        # report()/health() expose; the historical ``self.metrics`` dict
        # survives as a MutableMapping facade over registry counters, so
        # engine and scheduler call sites are unchanged.  The span
        # tracer is the only piece ``enable_telemetry`` gates: metrics
        # are core accounting (report()'s contract) and stay on.
        # ``profile_labels`` mirrors every span into the profiler's
        # trace (jax.profiler.TraceAnnotation), so it keeps the tracer on.
        self.obs = MetricsRegistry()
        self.tracer = SpanTracer(
            capacity=trace_capacity,
            enabled=enable_telemetry or profile_labels,
            annotate=jax.profiler.TraceAnnotation if profile_labels
            else None)
        self.metrics: Dict[str, float] = MetricsDict(self.obs, initial={
            "prompt_tokens": 0, "gen_tokens": 0, "preemptions": 0,
            "host_syncs": 0, "decode_dispatches": 0, "decode_steps": 0,
            "truncated_prompts": 0, "prefill_chunks": 0, "plan_steps": 0,
            "budget_tokens_used": 0,
            # device calls per engine iteration (the unified-dispatch
            # figure): work_steps counts iterations that dispatched at all
            "device_dispatches": 0, "work_steps": 0,
            # robustness counters (see docs/API.md "Fault tolerance")
            "dispatch_retries": 0, "quarantined": 0, "shed": 0,
            "aborted": 0, "deadline_expired": 0, "slow_steps": 0,
            # iterations that ran pipelined: enqueued their dispatch
            # chained on an in-flight one instead of blocking on it
            "async_steps": 0})
        # per-request latency decompositions, derived from lifecycle
        # events (arrival -> admitted -> first token -> finish)
        self._h_queue_wait = self.obs.histogram(
            "repro_request_queue_wait_ms",
            help="arrival to first admission (slot assigned)")
        self._h_ttft = self.obs.histogram(
            "repro_request_ttft_ms",
            help="arrival to first sampled token")
        # bounded percentile window: a long-lived streaming engine must
        # not grow a sample per token forever; 64k recent gaps is plenty
        # for p99 (the cumulative buckets keep the full history)
        self._h_itl = self.obs.histogram(
            "repro_itl_ms", sample_maxlen=65536,
            help="inter-token latency (per-event gaps, TTFT excluded)")
        self._g_waiting = self.obs.gauge(
            "repro_waiting", help="requests queued for admission")
        self._g_running = self.obs.gauge(
            "repro_running", help="requests holding a decode slot")
        self._g_free_blocks = self.obs.gauge(
            "repro_free_blocks", help="free KV pool blocks")
        self._g_step_ema = self.obs.gauge(
            "repro_step_time_ema_ms",
            help="straggler watchdog's EMA of work-step wall time")
        # sliding-window-only archs use a fixed ring cache: no block growth
        ring_only = bool(cfg.sliding_window) and not any(
            cfg.layer_kind(i) == "full" for i in range(cfg.num_layers))
        # chunked prefill needs every layer's prefill state to live in the
        # paged pool; SSM / recurrent / ring archs keep the whole-prompt
        # wave.  A chunked engine runs every prefill through the unified
        # step.
        self.chunked = bool(enable_chunked_prefill) \
            and T.supports_chunked_prefill(cfg)
        alloc = BlockAllocator(
            num_blocks, cfg.paging.block_size,
            enable_prefix_reuse=cfg.paging.enable_prefix_reuse,
            watermark_frac=cfg.paging.watermark_frac)
        self.scheduler = Scheduler(alloc, max_slots=max_slots,
                                   max_blocks_per_seq=max_blocks_per_seq,
                                   ring_only=ring_only, metrics=self.metrics)
        self.max_num_batched_tokens = int(max_num_batched_tokens)
        if self.chunked and self.max_num_batched_tokens <= max_slots:
            raise ValueError(
                f"max_num_batched_tokens={max_num_batched_tokens} must "
                f"exceed max_slots={max_slots}: a step of all-decode slots "
                "would otherwise leave prefill no budget (starvation)")
        # the chunk executable's fixed token width: a chunk can never be
        # longer than the budget, nor than a sequence's KV capacity
        chunk_tokens = min(self.max_num_batched_tokens,
                           self.scheduler.cap_tokens) if self.chunked \
            else None
        # async pipelined step (default; needs the unified executable):
        # a mixed iteration ENQUEUES its unified dispatch chained on the
        # previous (still in-flight) one and reads tokens back exactly
        # one step late, so the whole host side of a step — plan,
        # absorb, detokenize, bookkeeping — overlaps device execution.
        # ``enable_async_step=False`` keeps the read-back-every-step
        # engine as the pipeline's token-exactness oracle.
        self.async_step = bool(enable_async_step) and self.chunked
        # the per-step non-finite logit guard is a *static* flag baked
        # into the jitted executables at trace time: guards-off builds
        # trace byte-identical programs to a build that never heard of
        # guards (zero overhead when disabled), guards-on adds one
        # isfinite-reduce + select per sampled row
        self.guards = bool(enable_guards)
        rt = dict(rt or {})
        if self.guards:
            rt["sampling_guard"] = True
        self.runner = ModelRunner(cfg, params, max_slots=max_slots,
                                  num_blocks=num_blocks,
                                  max_blocks_per_seq=max_blocks_per_seq,
                                  rt=rt, max_horizon=self.max_horizon,
                                  kv_cache_dtype=kv_cache_dtype,
                                  chunk_tokens=chunk_tokens,
                                  tracer=self.tracer,
                                  metrics=self.metrics)
        self.kv_cache_dtype = self.runner.kv_cache_dtype
        self._t0: Optional[float] = None
        self._next_rid = 0
        # ---- robustness state (tentpole: see docs/API.md) ----
        self.max_waiting = None if max_waiting is None else int(max_waiting)
        self.shed_policy = shed_policy
        self.faults = fault_injector
        self.max_dispatch_retries = int(max_dispatch_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        # serving watchdog: EMA step-time monitor over work steps (the
        # training stack's detector, reused verbatim)
        self._straggler = StragglerDetector()
        # poisoned-dispatch bisection: rid groups awaiting probation, and
        # the group currently admitted in isolation (allowed_rids)
        self._suspects: deque = deque()
        self._probing: Optional[List[int]] = None
        # events produced outside step() (abort / shed): drained first
        # by the next step so stream()/run_until_done surface them
        self._pending: List[RequestOutput] = []
        # ---- async pipeline state (see docs/PERF.md "Async pipeline") ----
        # the un-collected in-flight dispatch, and the background worker
        # every async-mode emission (tokens, aborts, sheds) routes
        # through so detokenization overlaps the next device dispatch
        # while per-request event order is preserved (FIFO worker)
        self._flight: Optional[_Flight] = None
        self._detok: Optional[DetokWorker] = \
            DetokWorker(detokenizer, self.tracer) if self.async_step \
            else None

    # ---------------------------------------------------- facade views
    @property
    def alloc(self) -> BlockAllocator:
        return self.scheduler.alloc

    @property
    def waiting(self) -> List[RequestState]:
        return self.scheduler.waiting

    @property
    def running(self) -> Dict[int, Sequence]:
        return self.scheduler.running

    @property
    def finished(self) -> List[RequestState]:
        return self.scheduler.finished

    @property
    def state(self):
        return self.runner.state

    @property
    def rt(self) -> dict:
        return self.runner.rt

    # ------------------------------------------------------------ intake
    def _base_key(self, rid: int, sp: SamplingParams) -> np.ndarray:
        """Per-request PRNG stream root: explicit seed wins; otherwise a
        stream derived from (engine seed, request id)."""
        if sp.seed is not None:
            k = jax.random.PRNGKey(sp.seed)
        else:
            k = jax.random.fold_in(jax.random.PRNGKey(self.seed), rid)
        return np.asarray(k, np.uint32)

    def add(self, prompt: SeqT[int],
            sampling_params: Optional[SamplingParams] = None,
            request_id: Optional[int] = None) -> int:
        """Queue a request (allowed while running / streaming). Returns
        the request id used in its ``RequestOutput`` events.

        With ``max_waiting`` set the waiting queue is bounded: a full
        queue either raises ``EngineOverloadedError`` (shed_policy
        "reject" — the caller backs off) or finishes the OLDEST waiting
        request with finish_reason "shed" to make room ("shed-oldest" —
        staleness-bounded queues; running requests are never shed)."""
        if self.max_waiting is not None \
                and len(self.scheduler.waiting) >= self.max_waiting:
            self.metrics["shed"] += 1
            if self.shed_policy == "reject":
                raise EngineOverloadedError(
                    f"waiting queue at max_waiting={self.max_waiting}")
            victim = self.scheduler.waiting[0]
            self.scheduler.abort(victim.rid, FINISH_SHED)
            self._emit(victim, self._pending)
        sp = sampling_params or SamplingParams()
        rid = self._next_rid if request_id is None else request_id
        self._next_rid = max(self._next_rid, rid) + 1
        rec = RequestState(rid=rid, prompt=list(prompt), sampling=sp,
                           base_key=self._base_key(rid, sp))
        self.scheduler.add(rec)
        self.tracer.instant("req.arrival", cat="request",
                            args={"rid": rid, "prompt_len": len(rec.prompt)})
        return rid

    def add_request(self, req: Request) -> None:
        """Deprecated: wrap a legacy ``Request``; its ``output`` list is
        shared with the engine so old call sites keep reading results."""
        warnings.warn(
            "ServingEngine.add_request(Request(...)) is deprecated; use "
            "engine.add(prompt, SamplingParams(...)) or serving.llm.LLM",
            DeprecationWarning, stacklevel=2)
        sp = SamplingParams(temperature=req.temperature,
                            max_tokens=req.max_new_tokens)
        rec = RequestState(rid=req.rid, prompt=req.prompt, sampling=sp,
                           output=req.output, shim=req,
                           base_key=self._base_key(req.rid, sp))
        self._next_rid = max(self._next_rid, req.rid + 1)
        self.scheduler.add(rec)
        req.arrival = rec.arrival

    # ------------------------------------------------------------ lifecycle
    def abort(self, request_id: int) -> bool:
        """Cancel a request wherever it is — waiting, mid-prefill-chunk,
        or decoding.  Its KV blocks, hash registrations and slot are
        released the same call (refcount-audited: ``alloc.audit()``
        stays clean).  The finish event (finish_reason "aborted",
        partial output kept) surfaces with the next ``step()``.  Returns
        False if the id is unknown or already finished."""
        req = self.scheduler.abort(request_id, FINISH_ABORT)
        if req is None:
            return False
        self.metrics["aborted"] += 1
        self.tracer.instant("req.abort", cat="request",
                            args={"rid": request_id})
        self._emit(req, self._pending)
        return True

    def _mark_admitted(self, reqs: SeqT[RequestState], now: float) -> None:
        """First-admission lifecycle mark: the queue-wait histogram
        sample (arrival -> slot assigned) plus a trace instant.
        Re-admissions after preemption keep the original mark — queue
        wait measures a request's first trip through the queue."""
        for req in reqs:
            if req.admitted_t is None:
                req.admitted_t = now
                self._h_queue_wait.observe((now - req.arrival) * 1e3)
                self.tracer.instant("req.admitted", cat="request",
                                    args={"rid": req.rid})

    # ------------------------------------------------------------ outputs
    def _emit(self, req: RequestState, outs: List[RequestOutput]) -> None:
        new = list(req.output[req.emitted:])
        finished = req.finish_reason is not None
        if not new and not finished:
            return
        if self._detok is not None:
            # async mode: EVERY emission (tokens, abort, shed, deadline)
            # routes through the FIFO worker, so per-request event order
            # is preserved while detokenization overlaps the in-flight
            # dispatch.  The job snapshots its data here, on the engine
            # thread; ``step()`` surfaces the built outputs one step of
            # slack later.
            if finished:
                self.tracer.instant("req.finish", cat="request",
                                    args={"rid": req.rid,
                                          "reason": req.finish_reason,
                                          "tokens": len(req.output)})
            self._detok.submit(req, new, finished, req.finish_reason)
            req.emitted = len(req.output)
            return
        if req.shim is not None:     # legacy Request: mirror timestamps
            req.shim.first_token_t = req.first_token_t
            req.shim.done_t = req.done_t
        text = new_text = ""
        if self.detokenizer is not None:
            # incremental: only the delta is detokenized per event, the
            # cumulative text accumulates on the request record
            with self.tracer.span("detokenize", cat="host"):
                new_text = self.detokenizer(new) if new else ""
            req.text += new_text
            text = req.text
        if finished:
            self.tracer.instant("req.finish", cat="request",
                                args={"rid": req.rid,
                                      "reason": req.finish_reason,
                                      "tokens": len(req.output)})
        outs.append(RequestOutput(
            request_id=req.rid, prompt_token_ids=req.prompt_token_ids,
            token_ids=list(req.output), new_token_ids=new,
            finished=finished, finish_reason=req.finish_reason,
            text=text, new_text=new_text))
        req.emitted = len(req.output)

    def _absorb(self, s: Sequence, toks, now: float,
                outs: List[RequestOutput]) -> None:
        """Fold sampled tokens into a sequence, honouring stop token ids
        and the max_tokens budget; finishing frees KV blocks immediately
        (tokens past a stop are discarded). Emits the delta event."""
        req = s.req
        if toks:
            # inter-token latency sample: gap between this token-bearing
            # event and the request's previous one (TTFT excluded)
            if req.last_event_t is not None:
                self._h_itl.observe((now - req.last_event_t) * 1e3)
            req.last_event_t = now
        for tok in toks:
            if int(tok) < 0:
                # the on-device non-finite guard sampled -1: this ROW's
                # logits went NaN/inf.  Quarantine just this request —
                # everything sampled before the -1 is kept, everything
                # after it (fused horizons feed a clamped placeholder
                # forward) is garbage and discarded with the sequence.
                self.metrics["quarantined"] += 1
                self.tracer.instant("req.quarantine", cat="request",
                                    args={"rid": req.rid, "site": "nan_row"})
                if self.faults is not None:
                    self.faults.forgive(req.rid)
                self.scheduler.finish(s, FINISH_ERROR)
                break
            req.output.append(int(tok))
            s.last_token = int(tok)
            s.seq_len += 1
            self.metrics["gen_tokens"] += 1
            if req.first_token_t is None:
                req.first_token_t = now
                self._h_ttft.observe((now - req.arrival) * 1e3)
                self.tracer.instant("req.first_token", cat="request",
                                    args={"rid": req.rid})
            if int(tok) in req.sampling.stop:
                self.scheduler.finish(s, FINISH_STOP)
                break
            if req.tokens_remaining() <= 0:
                self.scheduler.finish(s, FINISH_LENGTH)
                break
        self._emit(req, outs)

    # ------------------------------------------------------------ recovery
    def _protected(self, rids: List[int], fn):
        """Run one device-dispatch thunk under the transient-fault guard:
        consult the injector BEFORE issuing the dispatch (donated buffers
        are never left half-dead, so a retry is always safe), retry with
        bounded exponential backoff, then escalate to
        ``PoisonedDispatchError`` carrying the batch's request ids for
        the bisection path.  One ``is None`` check when no injector is
        attached."""
        attempt = 0
        while True:
            try:
                if self.faults is not None:
                    self.faults.check_dispatch(rids)
                return fn()
            except TransientDeviceError as e:
                attempt += 1
                self.metrics["dispatch_retries"] += 1
                if attempt > self.max_dispatch_retries:
                    raise PoisonedDispatchError(rids, str(e)) from e
                if self.retry_backoff_s:
                    time.sleep(self.retry_backoff_s * (2 ** (attempt - 1)))

    def _quarantine(self, rid: int, outs: List[RequestOutput]) -> None:
        self.metrics["quarantined"] += 1
        self.tracer.instant("req.quarantine", cat="request",
                            args={"rid": rid, "site": "dispatch"})
        if self.faults is not None:
            self.faults.forgive(rid)
        req = self.scheduler.abort(rid, FINISH_ERROR)
        if req is not None:
            self._emit(req, outs)

    def _advance_probe(self) -> None:
        """Move the bisection forward: pop the next suspect group into
        probation (the scheduler admits ONLY its rids until it clears),
        or lift the allow-set entirely once no suspects remain."""
        if self._probing is None and self._suspects:
            self._probing = list(self._suspects.popleft())
            self.scheduler.allowed_rids = set(self._probing)
        elif self._probing is None:
            self.scheduler.allowed_rids = None

    def _recover(self, e: PoisonedDispatchError,
                 outs: List[RequestOutput]) -> None:
        """Poisoned-dispatch recovery.  Every request in the failing
        batch is requeued recompute-style (the same fold-and-replay that
        preemption uses, so survivors stay token-exact); a single-request
        batch has found its offender and is quarantined with
        finish_reason "error"; a larger batch is bisected into two
        probation groups the scheduler will re-admit in isolation —
        log2(batch) failing dispatches later the offender is cornered
        while every innocent request has cleared and kept decoding."""
        live = [rid for rid in e.rids
                if self.scheduler.preempt_request(rid) is not None]
        if len(live) == 1:
            self._quarantine(live[0], outs)
        elif len(live) > 1:
            mid = len(live) // 2
            self._suspects.append(live[:mid])
            self._suspects.append(live[mid:])
        self._probing = None
        self._advance_probe()

    # ------------------------------------------------------------ prefill
    def _sampling_rows(self, recs: List[RequestState],
                       live: Optional[set] = None) -> Dict[str, np.ndarray]:
        """Stack per-request SamplingParams into padded device-ready rows.

        ``live`` — rids whose sampled token this dispatch actually
        consumes (decode rows absorb every row they compute, but a mixed
        dispatch also computes throwaway samples for mid-prefill slots
        and non-final chunk rows).  The nan fault site is consulted only
        for live rows, so a scheduled fault cannot burn itself on a
        sample nobody reads.  None = every non-pad row is live."""
        with self.tracer.span("inputs", cat="host"):
            B = len(recs)
            arr = {"keys": np.zeros((B, 2), np.uint32),
                   "counts": np.zeros((B,), np.int32),
                   "temps": np.zeros((B,), np.float32),
                   "top_ks": np.zeros((B,), np.int32),
                   "top_ps": np.ones((B,), np.float32)}
            for i, r in enumerate(recs):
                if r is None:
                    continue
                arr["keys"][i] = r.base_key
                arr["counts"][i] = len(r.output)
                arr["temps"][i] = r.sampling.temperature
                arr["top_ks"][i] = r.sampling.top_k
                arr["top_ps"][i] = r.sampling.top_p
            # nan-site fault injection: a NaN bias row added to the
            # chosen requests' logits ON DEVICE, so the non-finite guard
            # is exercised end to end.  The "poison" key is present only
            # when a spec fires (its presence is static per trace, so
            # fault-free serving never traces a poisoned executable).
            eligible = [r.rid for r in recs if r is not None
                        and (live is None or r.rid in live)]
            nan = self.faults.nan_rids(eligible) \
                if self.faults is not None else ()
            if nan:
                rows = [i for i, r in enumerate(recs)
                        if r is not None and r.rid in nan]
                if rows:
                    p = np.zeros((B,), np.float32)
                    p[rows] = np.nan
                    arr["poison"] = p
            return arr

    def _slot_sampling(self, live: Optional[set] = None
                       ) -> Dict[str, np.ndarray]:
        with self.tracer.span("inputs", cat="host"):
            recs: List[Optional[RequestState]] = [None] * self.max_slots
            for slot, s in self.scheduler.running.items():
                recs[slot] = s.req
            return self._sampling_rows(recs, live=live)

    def _sync_tables(self, slots) -> None:
        """Device tables carrying exactly ``slots`` of the running set
        (every other slot gets seq_len 0, so its KV writes drop)."""
        with self.tracer.span("sync_tables", cat="host"):
            self.runner.sync_tables({slot: self.scheduler.running[slot]
                                     for slot in slots})

    def _run_prefill_oracle(self, seqs: List[Sequence],
                            outs: List[RequestOutput]) -> None:
        """Stop-the-world wave prefill — the only path for archs the
        unified step cannot chunk, and the parity oracle behind
        ``enable_chunked_prefill=False``: pads the whole wave to a
        ``prefill_bucket`` multiple, so it recompiles per (wave size,
        bucket) pair and stalls every running sequence for the duration
        of the longest prompt."""
        b = self.prefill_bucket
        maxlen = max(s.seq_len for s in seqs)
        maxlen = min(((maxlen + b - 1) // b) * b, self.scheduler.cap_tokens)
        rids = [s.req.rid for s in seqs]
        logits = self._protected(rids,
                                 lambda: self.runner.prefill(seqs, maxlen))
        # register-on-write: the wave's device write is now confirmed, so
        # its full prompt blocks become content-addressable
        for s in seqs:
            self.scheduler.register_written(s)
        self.metrics["prompt_tokens"] += sum(s.seq_len for s in seqs)
        # first sampled token, per-request sampling streams
        nxt = self._protected(rids, lambda: self.runner.sample(
            logits, self._sampling_rows([s.req for s in seqs])))
        self.metrics["host_syncs"] += 1
        now = time.perf_counter()
        with self.tracer.span("absorb", cat="host"):
            for i, s in enumerate(seqs):
                self._absorb(s, [int(nxt[i])], now, outs)
        # leave device tables consistent with the host bookkeeping
        # (slots just prefilled or freed) instead of relying on the next
        # decode's sync.
        self._sync_tables(self.scheduler.running)

    # ------------------------------------------------------------ readback
    def _readback(self, out) -> np.ndarray:
        """The host<->device sync boundary: one bulk transfer of a
        dispatch's token buffer.  The span is cat="device" — the host is
        blocked on the device stream, not doing host work — and lands in
        the step that COLLECTS the tokens: under async pipelining that
        is one step after the dispatch was enqueued, so its duration is
        whatever device time the overlapped host work failed to hide
        (near-zero in the steady state; see docs/OBSERVABILITY.md).
        The single shared np.asarray sink for the sync unified dispatch
        and the async collect (one justified R1 baseline entry)."""
        with self.tracer.span("readback", cat="device"):
            return np.asarray(out)

    # ------------------------------------------------------------ decode
    def _prepare_dispatch(self, horizon: int) -> StepPlan:
        """Whole-prompt-mode planning: horizon + block growth for all
        running (= all decodable) sequences, as one degenerate StepPlan."""
        h = self.scheduler.plan_horizon(horizon)
        cow = self.scheduler.grow_for_horizon(h) if h else []
        return StepPlan(decode_slots=sorted(self.scheduler.decodable())
                        if h else [], horizon=h, cow_pairs=cow,
                        prefill=[], budget=0)

    def _dispatch_decode(self, plan: StepPlan,
                         outs: List[RequestOutput]) -> None:
        """Execute a plan's decodes as one fused megastep over the
        planned horizon.  Only the plan's decodable slots are active:
        mid-prefill slots get device seq_len 0, so the decode KV scatter
        drops their writes."""
        # device tables carry EXACTLY the planned slots: everything else
        # (mid-prefill, or decodables a degenerate budget left out) gets
        # seq_len 0, so the decode KV scatter drops their writes
        self._sync_tables(plan.decode_slots)
        with self.tracer.span("inputs", cat="host"):
            toks = np.zeros((self.max_slots,), np.int32)
            for slot in plan.decode_slots:
                toks[slot] = self.scheduler.running[slot].last_token
            rids = [self.scheduler.running[sl].req.rid
                    for sl in plan.decode_slots]
            active = np.zeros((self.max_slots,), bool)
            active[plan.decode_slots] = True
        out_np = self._protected(rids, lambda: self.runner.megastep(
            toks, self._slot_sampling(live=set(rids)), active,
            plan.horizon))
        with self.tracer.span("absorb", cat="host"):
            self.metrics["host_syncs"] += 1
            self.metrics["decode_dispatches"] += 1
            self.metrics["decode_steps"] += plan.horizon
            now = time.perf_counter()
            for slot in plan.decode_slots:
                self._absorb(self.scheduler.running[slot],
                             out_np[:, slot].tolist(), now, outs)

    def _dispatch_unified(self, plan: StepPlan,
                          outs: List[RequestOutput]) -> None:
        """Execute a plan's prefill chunks as unified dispatches (the
        layout is ``StepPlan.unified_dispatches``): the first fuses the
        decode step (at horizon 1), the step's first prefill chunk AND
        all sampling into ONE donated device call with a single
        ``[max_slots + 1]`` token readback; further chunks (fresh-
        admission bursts) each dispatch alone.  In the steady mixed
        workload (one prompt chunking over a decoding batch) that is
        exactly one device dispatch per engine iteration."""
        done: List[tuple] = []
        try:
            for d in plan.unified_dispatches():
                # device tables carry EXACTLY this dispatch's decode slots:
                # everything else gets seq_len 0, so the decode KV scatter
                # drops its writes (chunk-only dispatches decode nothing)
                self._sync_tables(d.decode_slots)
                with self.tracer.span("inputs", cat="host"):
                    toks = np.zeros((self.max_slots,), np.int32)
                    active = np.zeros((self.max_slots,), bool)
                    recs: List[Optional[RequestState]] = \
                        [None] * self.max_slots
                    rids = []
                    for slot in d.decode_slots:
                        toks[slot] = self.scheduler.running[slot].last_token
                        active[slot] = True
                        recs[slot] = self.scheduler.running[slot].req
                        rids.append(recs[slot].rid)
                    c = d.chunk
                    recs.append(c.seq.req)      # row max_slots: the chunk
                    live = set(rids) | ({c.seq.req.rid} if d.sample_chunk
                                        else set())
                out = self._protected(
                    rids + [c.seq.req.rid],
                    lambda: self.runner.unified_step(
                        toks, self._sampling_rows(recs, live=live), active,
                        c.seq.req.prompt, c.seq.block_ids, c.start,
                        c.length))
                done.append((d, out))
                with self.tracer.span("plan", cat="host"):
                    self.scheduler.complete_chunk(c)
                    self.metrics["prefill_chunks"] += 1
                    self.metrics["prompt_tokens"] += c.length
                    if d.decode_slots:
                        self.metrics["decode_dispatches"] += 1
                        self.metrics["decode_steps"] += 1
        finally:
            # the step's ONE blocking point: token buffers are absorbed
            # after every dispatch is in flight (an admission burst of
            # several chunks pipelines; the steady mixed state is a
            # single dispatch).  On a poisoned later dispatch this still
            # runs before recovery, so completed dispatches' tokens are
            # banked and survive the fold-and-requeue token-exactly.
            if done:
                self.metrics["host_syncs"] += 1
                now = time.perf_counter()
                for d, out in done:
                    out_np = self._readback(out)
                    with self.tracer.span("absorb", cat="host"):
                        for slot in d.decode_slots:
                            self._absorb(self.scheduler.running[slot],
                                         [int(out_np[slot])], now, outs)
                        if d.sample_chunk:
                            self._absorb(d.chunk.seq,
                                         [int(out_np[self.max_slots])],
                                         now, outs)

    # ------------------------------------------------------------ pipeline
    def _enqueue_unified(self, d: UnifiedDispatch,
                         outs: List[RequestOutput]) -> _Flight:
        """Enqueue one unified dispatch WITHOUT reading it back, chained
        on the in-flight dispatch's output buffer (the tentpole's device
        half).  A decode row whose feed token is still in flight is fed
        by a device-side gather (``use_prev``/``chain_idx`` into the
        previous ``[max_slots + 1]`` buffer); rows whose token the host
        already holds (pipeline restart after a flush) feed the host
        value.  Host bookkeeping — tables, PRNG counts, chunk
        completion, the speculative seq_len bumps — is identical to what
        the synchronous engine would have done AFTER absorbing the
        in-flight tokens, so planning and device state never diverge
        from the oracle."""
        sched = self.scheduler
        prev = self._flight
        # device tables: each slot's seq_len already counts its
        # speculated token (the one this dispatch feeds and whose KV it
        # writes at seq_len - 1) — exactly the sync post-absorb state
        self._sync_tables(d.decode_slots)
        with self.tracer.span("inputs", cat="host"):
            toks = np.zeros((self.max_slots,), np.int32)
            chain_idx = np.zeros((self.max_slots,), np.int32)
            use_prev = np.zeros((self.max_slots,), bool)
            active = np.zeros((self.max_slots,), bool)
            recs: List[Optional[RequestState]] = [None] * self.max_slots
            rids = []
            for slot in d.decode_slots:
                s = sched.running[slot]
                active[slot] = True
                recs[slot] = s.req
                rids.append(s.req.rid)
                row = prev.source_row.get(id(s)) if prev is not None \
                    else None
                if row is None:
                    toks[slot] = s.last_token     # host-known feed
                else:
                    use_prev[slot] = True         # gather from in-flight
                    chain_idx[slot] = row
            c = d.chunk
            recs.append(c.seq.req)                # row max_slots: the chunk
            live = set(rids) | ({c.seq.req.rid} if d.sample_chunk
                                else set())
            sp = self._sampling_rows(recs, live=live)
            for slot in d.decode_slots:
                # the PRNG stream position counts every token SAMPLED so
                # far — including the in-flight one this dispatch feeds,
                # which req.output does not hold yet
                sp["counts"][slot] += sched.running[slot].speculated
        try:
            out = self._protected(
                rids + [c.seq.req.rid],
                lambda: self.runner.unified_step_chained(
                    prev.out if prev is not None else None,
                    chain_idx, use_prev, toks, sp, active,
                    c.seq.req.prompt, c.seq.block_ids, c.start, c.length))
        except PoisonedDispatchError:
            # bank the PREVIOUS dispatch's (completed, valid) tokens
            # before recovery requeues this batch — survivors keep them
            # and the fold-and-replay stays token-exact
            self._collect_flight(outs)
            raise
        with self.tracer.span("plan", cat="host"):
            sched.complete_chunk(c)
            self.metrics["prefill_chunks"] += 1
            self.metrics["prompt_tokens"] += c.length
            if d.decode_slots:
                self.metrics["decode_dispatches"] += 1
                self.metrics["decode_steps"] += 1
            # speculation bumps AFTER the successful enqueue: every row
            # whose sample this dispatch's buffer carries
            flight = _Flight(out=out)
            for slot in d.decode_slots:
                s = sched.running[slot]
                sched.speculate(s)
                flight.decode_rows.append((slot, s))
                flight.source_row[id(s)] = slot
            if d.sample_chunk:
                sched.speculate(c.seq)
                flight.chunk_seq = c.seq
                flight.source_row[id(c.seq)] = self.max_slots
        return flight

    def _collect_flight(self, outs: List[RequestOutput]) -> None:
        """Read back the in-flight dispatch — the step's one blocking
        point, deferred exactly one step — then reconcile and absorb its
        tokens.  A row whose Sequence finished, aborted, expired, or was
        preempted (even re-admitted into the same slot as a NEW record:
        object identity catches it) while in flight is discarded with
        the dead record; recompute replay regenerates the token
        token-exactly via the counts-indexed sampling stream if the
        request ever runs again.  No-op when nothing is in flight, so it
        doubles as the pipeline flush every donating fallback dispatch
        (megastep, CoW, chunk bursts) requires."""
        fl = self._flight
        if fl is None:
            return
        self._flight = None
        out_np = self._readback(fl.out)
        with self.tracer.span("absorb", cat="host"):
            self.metrics["host_syncs"] += 1
            now = time.perf_counter()
            rows = list(fl.decode_rows)
            if fl.chunk_seq is not None:
                rows.append((self.max_slots, fl.chunk_seq))
            for row, s in rows:
                if s.req.finish_reason is not None \
                        or self.scheduler.running.get(s.slot) is not s:
                    continue
                self.scheduler.reconcile(s)
                self._absorb(s, [int(out_np[row])], now, outs)

    def _prune_plan(self, plan: StepPlan) -> None:
        """Drop plan rows a pipeline flush invalidated: absorbing the
        in-flight tokens can finish a planned decode slot (stop token,
        quarantined NaN row) whose Sequence the dispatch path would then
        look up.  Chunks never die here — mid-prefill slots have no
        in-flight sample — and a freed slot's pending CoW copy lands in
        a free block nothing reads before it is rewritten."""
        plan.decode_slots = [sl for sl in plan.decode_slots
                             if sl in self.scheduler.running]

    def _dispatch_fallback(self, plan: StepPlan,
                           outs: List[RequestOutput]) -> None:
        """The synchronous dispatch selection (also the async engine's
        non-pipelined fallback, after a flush): a plan's CoW copies,
        then its decodes as a megastep unless they ride the first
        unified dispatch (horizon 1 beside prefill), then its chunks as
        unified dispatches."""
        if plan.cow_pairs:
            self.runner.copy_cow(plan.cow_pairs)
        if plan.decode_slots and (plan.horizon > 1 or not plan.prefill):
            self._dispatch_decode(plan, outs)
        if plan.prefill:
            self._dispatch_unified(plan, outs)

    # ------------------------------------------------------------ drive
    def step(self) -> List[RequestOutput]:
        """One engine iteration under the token budget: the scheduler
        plans decodes first (fused horizon when no prefill is pending,
        one interleaved token otherwise), then packs prefill chunks into
        the remaining budget; the runner executes both halves.  With
        ``enable_chunked_prefill=False`` (or an arch the unified step
        cannot chunk) the stop-the-world whole-prompt wave runs
        instead.  Returns the
        ``RequestOutput`` deltas produced by this iteration.

        Robustness rides the same loop: deadlines expire before
        planning, fault-injection sites are consulted at their natural
        points (dispatch wrappers, sampling rows, admission headroom,
        the step wall-clock), a poisoned dispatch lands in the recovery
        path instead of crashing the engine, and the straggler watchdog
        observes every work step's wall time.

        Telemetry rides it too (``enable_telemetry``, default on): the
        whole iteration is an ``engine.step`` span with plan / inputs /
        sync_tables / dispatch / readback / absorb / detokenize children
        on ``self.tracer``, which is what ``attribution()`` decomposes
        into per-step host vs device milliseconds — see
        docs/OBSERVABILITY.md.

        With ``enable_async_step`` (default, unified mode) the step is
        PIPELINED: it plans and enqueues its dispatch chained on the
        previous (still in-flight) one, then reads the previous step's
        tokens back — so the returned events run one step behind the
        device, and an extra ``step()`` or two after the scheduler
        drains surfaces the tail (``stream`` / ``run_until_done`` /
        ``close`` handle that)."""
        with self.tracer.span("engine.step", cat="step"):
            if self._detok is not None:
                # async: this step's emissions land on the worker; what
                # surfaces NOW is everything submitted before this step
                # began — one step of slack hides detokenize latency
                # under the in-flight dispatch
                n0 = self._detok.submitted
                tail = self._step_impl()
                with self.tracer.span("absorb", cat="host"):
                    outs = self._detok.collect_upto(n0) + tail
            else:
                outs = self._step_impl()
        self._update_gauges()
        return outs

    def _update_gauges(self) -> None:
        """Refresh the point-in-time gauges the ``/metrics`` endpoint
        exposes (plain host floats; never dispatches)."""
        self._g_waiting.set(len(self.scheduler.waiting))
        self._g_running.set(len(self.scheduler.running))
        self._g_free_blocks.set(self.alloc.num_free)
        if self._straggler.ema is not None:
            self._g_step_ema.set(self._straggler.ema * 1e3)

    def _step_impl(self) -> List[RequestOutput]:
        if self._t0 is None:
            self._t0 = time.perf_counter()
        outs: List[RequestOutput] = self._pending  # abort/shed events first
        self._pending = []
        alloc_blocked = False
        with self.tracer.span("plan", cat="host"):
            if self.faults is not None:
                self.faults.step_begin()
                alloc_blocked = self.faults.alloc_blocked()
            for req in self.scheduler.expire_deadlines():
                self.metrics["deadline_expired"] += 1
                self._emit(req, outs)
            self._advance_probe()
        d0 = self.runner.dispatches
        t_work = time.perf_counter()
        if self.faults is not None:
            stall = self.faults.stall_seconds()
            if stall:           # inside the timed window: the watchdog
                time.sleep(stall)  # must see the stall, like a real one
        try:
            with self.tracer.span("plan", cat="host"):
                for req in self.scheduler.finish_at_capacity():
                    self._emit(req, outs)  # free slots/blocks first
            if not self.chunked:
                admitted = self.scheduler.try_admit(alloc_blocked)
                self._mark_admitted([s.req for s in admitted],
                                    time.perf_counter())
                if admitted:
                    self._run_prefill_oracle(admitted, outs)
                for req in self.scheduler.finish_at_capacity():
                    self._emit(req, outs)  # a fresh exactly-cap prefill
                if not self.scheduler.running:  # may be at the boundary
                    return outs
                with self.tracer.span("plan", cat="host"):
                    plan = self._prepare_dispatch(self.max_horizon)
                self._dispatch_fallback(plan, outs)
                return outs
            with self.tracer.span("plan", cat="host"):
                plan = self.scheduler.plan_step(
                    self.max_num_batched_tokens,
                    max_horizon=self.max_horizon,
                    alloc_blocked=alloc_blocked)
                self._mark_admitted([c.seq.req for c in plan.prefill],
                                    time.perf_counter())
                ds = plan.unified_dispatches() if self.async_step else None
            if self.async_step:
                if len(ds) == 1 and plan.horizon <= 1 \
                        and not plan.cow_pairs:
                    # the tentpole fast path (the steady mixed state):
                    # enqueue this step's single unified dispatch chained
                    # on the in-flight one, THEN read the previous step's
                    # tokens back — the new dispatch executes on device
                    # while the host absorbs, plans and detokenizes
                    flight = self._enqueue_unified(ds[0], outs)
                    self._collect_flight(outs)
                    self._flight = flight
                    self.metrics["async_steps"] += 1
                else:
                    # leaving the pipelined regime (pure-decode megastep,
                    # a multi-chunk admission burst, decodes at a
                    # multi-token horizon beside prefill, CoW copies, or
                    # no schedulable work): every fallback dispatch donates
                    # its inputs, so the in-flight dispatch is collected
                    # first — and absorbing its tokens may finish
                    # sequences the plan still references, so the plan is
                    # pruned to the survivors
                    if self._flight is not None:
                        self._collect_flight(outs)
                        self._prune_plan(plan)
                    self._dispatch_fallback(plan, outs)
            else:
                self._dispatch_fallback(plan, outs)
            if plan.used:
                self.metrics["plan_steps"] += 1
                self.metrics["budget_tokens_used"] += plan.used
            return outs
        except PoisonedDispatchError as e:
            self._recover(e, outs)
            return outs
        finally:
            used = self.runner.dispatches - d0
            if used:
                self.metrics["device_dispatches"] += used
                self.metrics["work_steps"] += 1
                # the first work step is the jit-compile step: feeding it
                # to the watchdog would seed the EMA ~100x too high and
                # mask every real stall for dozens of steps (the same
                # warm-vs-cold split the decode timers make)
                if self.metrics["work_steps"] > 1:
                    verdict = self._straggler.observe(
                        int(self.metrics["work_steps"]),
                        time.perf_counter() - t_work)
                    if verdict != "ok":
                        self.metrics["slow_steps"] += 1
            # probation clears once every probed rid has made it out of
            # the waiting queue through a CLEAN dispatch (a rid-targeted
            # fault would have failed that dispatch): move to the next
            # suspect group, or lift the allow-set
            if self._probing is not None:
                probe = set(self._probing)
                if not any(r.rid in probe for r in self.scheduler.waiting):
                    self._probing = None
                    self._advance_probe()

    def _work_pending(self) -> bool:
        """Drain condition for ``stream``/``run_until_done``: scheduler
        work, an un-collected in-flight dispatch, or detokenize-worker
        events not yet surfaced through ``step()`` — the async pipeline
        runs the event stream one step behind the device, so the last
        couple of steps exist purely to flush it."""
        return self.scheduler.has_work() or self._flight is not None \
            or bool(self._detok is not None and self._detok.pending())

    def stream(self, max_steps: int = 100000) -> Iterator[RequestOutput]:
        """Yield ``RequestOutput`` deltas as horizons complete — callers
        see first tokens while the batch is still running, and may keep
        calling ``add`` / ``add_request`` between events."""
        steps = 0
        while self._work_pending() and steps < max_steps:
            yield from self.step()
            steps += 1

    def run_until_done(self, max_steps: int = 10000) -> Dict[str, float]:
        steps = 0
        while self._work_pending() and steps < max_steps:
            self.step()
            steps += 1
        return self.report()

    # ------------------------------------------------------------ shutdown
    def close(self) -> List[RequestOutput]:
        """Shut the pipeline down cleanly: read back any in-flight
        dispatch (banking its tokens), drain and join the detokenize
        worker, and return every event not yet surfaced through
        ``step()`` (empty for a drained or synchronous engine).
        Idempotent.  The engine is a context manager — ``with`` calls
        this on exit — and ``launch/serve.py`` calls it on shutdown so
        the worker thread and the in-flight dispatch never outlive the
        server loop."""
        outs: List[RequestOutput] = []
        try:
            self._collect_flight(outs)
        finally:
            if self._detok is not None:
                worker, self._detok = self._detok, None
                outs.extend(worker.close())
        if self._pending:
            outs = self._pending + outs
            self._pending = []
        return outs

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def reset_dispatch_window(self) -> None:
        """Zero the device-dispatch counters so ``report()``'s
        ``device_dispatches_per_step`` covers only what follows — e.g.
        the steady mixed-workload window after warm-up (compile steps
        and one-off CoW copies land in the warm-up bucket)."""
        self.metrics["device_dispatches"] = 0
        self.metrics["work_steps"] = 0

    def reset_itl_window(self) -> None:
        """Drop accumulated inter-token-latency samples so ``report()``'s
        ITL percentiles cover only what follows — e.g. a steady-state
        window after warm-up/compile steps.  Live requests keep their
        last-event timestamps: a stall in progress still lands in the
        first post-reset sample.  Only the percentile window resets; the
        cumulative ``repro_itl_ms`` histogram buckets on ``/metrics``
        keep the full history."""
        self._h_itl.clear_samples()

    def attribution(self, window: int = 50) -> Dict[str, float]:
        """Steady-state host-vs-device wall-time split per engine step.

        Decomposes the last ``window`` *work* steps (steps that issued
        at least one device dispatch) from the span ring: ``device_ms``
        is dispatch issue + the token-readback sync boundary,
        ``host_ms`` is everything else the step did (plan, absorb,
        detokenize, bookkeeping).  This is the measured form of the
        ROADMAP item 1 diagnosis — the serialized host share the async
        engine has to overlap away.  All-NaN (``steps == 0``) when
        telemetry is disabled or nothing dispatched yet."""
        return attribute_steps(self.tracer.spans(), window=window)

    def _shared_snapshot(self) -> Dict[str, float]:
        """The fields ``report()`` and ``health()`` both expose, computed
        ONCE from the obs registry (the single source of truth) so the
        two views can never drift apart.  Key names are the historical
        ones — both public dicts splat this in unchanged."""
        m = self.metrics
        ema = self._straggler.ema
        return {
            "step_time_ema_ms": ema * 1e3 if ema is not None
            else float("nan"),
            "slow_steps": float(m["slow_steps"]),
            "dispatch_retries": float(m["dispatch_retries"]),
            "quarantined": float(m["quarantined"]),
            "shed": float(m["shed"]),
            "aborted": float(m["aborted"]),
            "deadline_expired": float(m["deadline_expired"]),
            "block_utilization": self.alloc.utilization(),
        }

    def health(self) -> Dict[str, float]:
        """O(1) liveness snapshot for load balancers / operators: queue
        depth, pool pressure, and the robustness counters.  Never
        dispatches, never blocks — safe to poll every step (and what
        the ``/health`` endpoint in ``launch/serve.py`` serves)."""
        return {
            "waiting": float(len(self.scheduler.waiting)),
            "running": float(len(self.scheduler.running)),
            "max_waiting": float(self.max_waiting)
            if self.max_waiting is not None else float("inf"),
            "free_blocks": float(self.alloc.num_free),
            "watermark_blocks": float(self.alloc.watermark),
            **self._shared_snapshot(),
            # rids still under poisoned-dispatch probation (0 = healthy)
            "probing_rids": float(len(self._probing or [])
                                  + sum(len(g) for g in self._suspects)),
        }

    def report(self) -> Dict[str, float]:
        """The paper's three numbers (+ fast-path and streaming counters)."""
        t1 = time.perf_counter()
        wall = max(t1 - (self._t0 or t1), 1e-9)
        fin = self.scheduler.finished
        n = len(fin)
        lat = float(np.mean([r.done_t - r.arrival for r in fin])) \
            if n else float("nan")
        ttft = float(np.mean([r.first_token_t - r.arrival for r in fin
                              if r.first_token_t is not None])) \
            if n else float("nan")
        total_toks = self.metrics["prompt_tokens"] + self.metrics["gen_tokens"]
        d_steps = max(self.metrics["decode_steps"], 1)
        # inter-token latency percentiles over per-event gaps: under
        # stop-the-world prefill the p99 carries the "one long prompt
        # stalls everyone" spikes the chunked planner bounds at O(chunk)
        itl = np.asarray(self._h_itl.samples(), np.float64)   # already ms
        itl_p50 = float(np.percentile(itl, 50)) if itl.size else float("nan")
        itl_p99 = float(np.percentile(itl, 99)) if itl.size else float("nan")
        plan_steps = self.metrics["plan_steps"]
        budget_util = (self.metrics["budget_tokens_used"]
                       / (plan_steps * self.max_num_batched_tokens)) \
            if plan_steps else float("nan")
        return {
            "latency_s": lat,
            "ttft_s": ttft,
            "itl_p50_ms": itl_p50,
            "itl_p99_ms": itl_p99,
            "queue_wait_p50_ms": self._h_queue_wait.percentile(50),
            "prefill_chunks": self.metrics["prefill_chunks"],
            "prefill_compiles": self.runner.prefill_compiles(),
            # device calls per engine iteration (1.0 in the unified
            # steady mixed state)
            "device_dispatches_per_step":
                (self.metrics["device_dispatches"]
                 / self.metrics["work_steps"])
                if self.metrics["work_steps"] else float("nan"),
            "budget_utilization": budget_util,
            "throughput_req_s": n / wall,
            "throughput_tok_s": total_toks / wall,
            "generate_tok_s": self.metrics["gen_tokens"] / wall,
            "preemptions": self.metrics["preemptions"],
            # robustness: the same registry-backed block health() serves
            **self._shared_snapshot(),
            "blocks_reused": self.alloc.stats["reused"],
            # pool memory: the figure kv_cache_dtype="int8" halves vs bf16
            "kv_pool_bytes": self.runner.kv_pool_bytes(),
            "kv_bytes_per_token": self.runner.kv_bytes_per_token(),
            "wall_s": wall,
            # iterations that ran pipelined (enqueue-then-collect): > 0
            # proves the async path actually engaged in a bench window
            "async_steps": self.metrics["async_steps"],
            "host_syncs": self.metrics["host_syncs"],
            "decode_dispatches": self.metrics["decode_dispatches"],
            "decode_steps": self.metrics["decode_steps"],
            # decode-path syncs only (one per dispatch): prefill-wave syncs
            # are excluded, so max_horizon=1 reads exactly 1.0 and a
            # fused horizon 1/horizon
            "syncs_per_decode_step":
                self.metrics["decode_dispatches"] / d_steps,
        }
