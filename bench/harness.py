"""One run of one cell: build, warm up, measure, check, report.

The serving program is driven only through its public entry:
``LLM(cfg, params, **engine sizes)`` and ``llm.engine``'s ``add``,
``step``, ``report``, ``state``, ``tracer`` and ``obs``.  Requests enter
through ``engine.add`` when they are due; every latency is taken on this
module's clock, from the due time to the return of the ``step()`` call
that handed the token back.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from bench import manifest as mf
from bench import trace_reduce
from bench.traffic import Request, Traffic

clock = time.perf_counter
TRACE_MAX_S = 6.0          # longest traced window
TAIL_MAX_S = 60.0          # longest wait past the close for late answers
CHECK_TOKENS = 256         # served tokens the check wants at least
CHECK_MAX_SEQS = 4         # sequences the reference runs at most


class NoChip(RuntimeError):
    """The run needs an accelerator this machine does not have."""


# --------------------------------------------------------------------------
# the device
# --------------------------------------------------------------------------

def require_device(peaks: dict, chips: int):
    """The chips a cell needs, or ``NoChip`` naming what JAX found."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {dev.platform!r} "
                     f"({dev.device_kind})")
    if dev.device_kind not in peaks:
        raise NoChip(f"device kind {dev.device_kind!r} has no entry in "
                     f"bench/peaks.json")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return dev


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` points."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


class CompileCounter:
    """Counts compilations (and persistent-cache loads) as JAX reports
    them, with the time of each."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.times.append(clock())

    def since(self, t: float) -> int:
        return sum(1 for x in self.times if x >= t)


def warm_readback_slices(engine, dev) -> None:
    """A megastep hands back ``out[:n]`` of its [horizon, slots] token
    buffer, a small program for each ``n``; compile each here, for a
    buffer committed to the device and for one that is not, so that no
    horizon the window meets first compiles inside it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    shape = (engine.max_horizon, engine.max_slots)
    for buf in (jax.jit(lambda: jnp.zeros(shape, jnp.int32))(),
                jax.device_put(np.zeros(shape, np.int32), dev)):
        for n in range(1, shape[0] + 1):
            np.asarray(buf[:n])


# --------------------------------------------------------------------------
# driving the engine
# --------------------------------------------------------------------------

@dataclass
class Client:
    engine: object
    annotate: bool = False
    by_rid: Dict[int, Request] = field(default_factory=dict)
    outstanding: int = 0
    steps: int = 0

    def _label(self, name):
        if self.annotate:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def add(self, r: Request, due_t: float) -> None:
        from repro.serving import SamplingParams
        sp = SamplingParams(temperature=r.temperature, top_p=r.top_p,
                            seed=r.seed, max_tokens=r.max_tokens)
        with self._label("bench.add"):
            r.rid = self.engine.add(r.prompt, sp)
        r.due_t = due_t
        self.by_rid[r.rid] = r
        self.outstanding += 1

    def step(self) -> None:
        with self._label("bench.step"):
            outs = self.engine.step()
        t = clock()
        self.steps += 1
        for o in outs:
            r = self.by_rid.get(o.request_id)
            if r is None:
                continue
            new = o.new_token_ids
            if new:
                r.events.append((t, r.n_out, len(new)))
                if r.first_t is None:
                    r.first_t = t
                r.last_t = t
                r.n_out += len(new)
            if o.finished and r.finish_t is None:
                r.finish_t, r.reason = t, o.finish_reason
                r.tokens = list(o.token_ids)
                self.outstanding -= 1

    def idle(self, until: float) -> None:
        with self._label("bench.idle"):
            time.sleep(max(0.0, min(until - clock(), 0.05)))

    def run_until(self, done) -> None:
        while not done():
            self.step()


class OpenLoop:
    """Requests enter at their due times; the engine steps while any is
    outstanding."""

    def __init__(self, client: Client, schedule: List[Request],
                 t_base: float):
        self.d = client
        self.sched = schedule
        self.t_base = t_base
        self.i = 0

    def pump(self, until) -> None:
        while not until():
            now = clock()
            while (self.i < len(self.sched)
                   and self.t_base + self.sched[self.i].due <= now):
                r = self.sched[self.i]
                self.d.add(r, self.t_base + r.due)
                self.i += 1
            if self.d.outstanding:
                self.d.step()
            elif self.i < len(self.sched):
                self.d.idle(self.t_base + self.sched[self.i].due)
            else:
                return


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

@dataclass
class RunData:
    """What the metric readers read (see ``bench/metrics``)."""
    cfg: dict
    sizes: dict
    peaks: dict
    requests: List[Request]
    t0: float
    t1: float
    setup_s: float
    engine_spans: list
    queue_wait_ms: List[float]
    pool: dict
    trace: Optional[list] = None
    trace_t0: float = 0.0          # trace clock (ns) at host time t0
    t_end: float = 0.0             # when the run stopped waiting
    kernels: dict = field(default_factory=dict)

    def to_trace_ns(self, t: float) -> float:
        return self.trace_t0 + (t - self.t0) * 1e9

    def window_requests(self) -> List[Request]:
        return [r for r in self.requests if self.t0 <= r.due_t < self.t1]

    def finished_in_window(self) -> List[Request]:
        return [r for r in self.requests if r.finish_t is not None
                and self.t0 <= r.finish_t < self.t1]


def _check_sample(reqs: List[Request], seed: int) -> List[Request]:
    """Finished greedy requests to compare with the reference: the
    longest, then others drawn from the seed until the sample holds
    ``CHECK_TOKENS`` served tokens."""
    import numpy as np
    done = [r for r in reqs if r.greedy and r.reason == "length"]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.tokens), r.index))
    pick = [done[0]]
    rest = done[1:]
    order = np.random.default_rng([seed % (1 << 64), 7]).permutation(len(rest))
    for j in order:
        if (sum(len(r.tokens) for r in pick) >= CHECK_TOKENS
                or len(pick) >= CHECK_MAX_SEQS):
            break
        pick.append(rest[j])
    return pick


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, man: Optional[mf.Manifest] = None,
        require_chip: bool = True, control: Optional[dict] = None,
        log=None) -> dict:
    """One run; returns the result line as a dict (``correct`` first)."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    man = man or mf.Manifest()
    cell = man.cell(workload)
    cfg = man.config(cell.config)
    mix = man.traffic(cell.traffic)
    cellp = man.cell_params(cell.name)
    peaks_all = man.peaks()
    dev = None
    if require_chip:
        dev = require_device(peaks_all, cell.chips)
    import jax
    if dev is None:
        dev = jax.devices()[0]
    peaks = peaks_all.get(dev.device_kind, {})
    cache_dir = enable_compile_cache(man.root)
    compiles = CompileCounter()
    model = man.model(cfg)
    sizes = model.sizes(cfg)

    from repro.serving import LLM
    t_w = clock()
    phases0 = {"start_to_weights": t_w - t_start}
    params = model.make_params(cfg, seed)
    eng = cfg["engine"]
    llm = LLM(model.program_config(cfg), params,
              seed=int(seed % (2 ** 31 - 1)),
              kv_cache_dtype=cfg.get("kv_cache_dtype", "bf16"),
              max_slots=eng["max_slots"], num_blocks=eng["num_blocks"],
              max_blocks_per_seq=eng["max_blocks_per_seq"],
              max_num_batched_tokens=eng["max_num_batched_tokens"],
              profile_labels=trace)
    engine = llm.engine
    d = Client(engine, annotate=trace)
    traffic = Traffic(mix, cellp, seed, sizes["V"])
    win_s = min(seconds, TRACE_MAX_S) if trace else float(seconds)
    warm = mix["warmup"]

    # ---- warm-up, part 1: every executable of the cell compiles here.
    # Two short and two long prompts arrive together (several chunks in
    # one step: the donated unified step), then chunk alone beside the
    # decodes (the chained step), then decode alone (the megastep).
    phases = {"weights_engine": clock() - t_w}
    t_c = clock()
    warm_readback_slices(engine, dev)
    blk = traffic.block("compile", warm["compile_pool"])
    blk.sort(key=lambda r: len(r.prompt))
    for r in blk[:2] + blk[-2:]:
        r.max_tokens = min(r.max_tokens, warm["compile_max_tokens"])
        d.add(r, clock())
    d.run_until(lambda: d.outstanding == 0)
    setup_compiles = compiles.since(t_c)
    phases["compile_burst"] = clock() - t_c

    # ---- warm-up, part 2: the cell's own traffic for the mix's warm-up
    # seconds (open loop: the schedule's warm-up block), then the window
    t_w2 = clock()
    if mix["loop"] == "open":
        schedule = traffic.open_loop(warm["seconds"], win_s,
                                     mix.get("tail_seconds", TAIL_MAX_S))
        loop = OpenLoop(d, schedule, clock())
        t0 = loop.t_base + warm["seconds"]
        loop.pump(lambda: clock() >= t0)
    else:
        backlog = traffic.backlog(mix["block"])
        keep = eng["max_slots"] + mix["backlog_extra"]

        def top_up():
            while d.outstanding < keep:
                d.add(next(backlog), clock())

        top_up()
        first_wave = list(d.by_rid.values())[-keep:][:eng["max_slots"]]
        t_ready = [None]

        def warmed():
            # the first wave admitted and answered, then the mix's seconds
            top_up()
            if t_ready[0] is None and all(r.first_t is not None
                                          for r in first_wave):
                t_ready[0] = clock()
            return t_ready[0] is not None and \
                clock() >= t_ready[0] + warm["seconds"]
        d.run_until(warmed)
        t0 = clock()
    phases["traffic_warmup"] = clock() - t_w2
    setup_compiles += compiles.since(t_w2)
    setup_s = t0 - t_start
    report0 = engine.report()
    qw = engine.obs.get("repro_request_queue_wait_ms")
    qw.clear_samples()
    engine.tracer.clear()
    if trace:
        tr_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tr_dir)
        win_label = jax.profiler.TraceAnnotation("bench.window")
        win_label.__enter__()
    t0 = clock()
    t1 = t0 + win_s
    if mix["loop"] == "open":
        loop.pump(lambda: clock() >= t1)
    else:
        def window_done():
            top_up()
            return clock() >= t1
        d.run_until(window_done)
    t1 = clock()
    if trace:
        win_label.__exit__(None, None, None)
    report1 = engine.report()
    window_compiles = compiles.since(t0)
    spans = [s for s in engine.tracer.spans()
             if s.dur is not None and t0 * 1e9 <= s.ts < t1 * 1e9]
    queue_wait = list(qw.samples())
    if trace:
        jax.profiler.stop_trace()

    # ---- past the close: late first tokens and the check's sample
    reqs = list(d.by_rid.values())
    if mix["loop"] == "open":
        due_in = [r for r in reqs if t0 <= r.due_t < t1]
        t_close = clock()

        def settled():
            if clock() - t_close > TAIL_MAX_S:
                return True
            if any(r.first_t is None for r in due_in):
                return False
            done = [r for r in d.by_rid.values()
                    if r.greedy and r.reason == "length"]
            return sum(len(r.tokens) for r in done) >= CHECK_TOKENS
        loop.pump(settled)
    reqs = list(d.by_rid.values())
    t_end = clock()
    mem = (dev.memory_stats() or {})
    pool = {"itemsize": engine.state["k_pool"].dtype.itemsize,
            "quantized": "k_scales" in engine.state}
    tail_steps = d.steps
    llm.close()
    del llm, engine, d, params
    gc.collect()

    # ---- the check: served greedy tokens against the reference
    late_done = [r for r in reqs if r.finish_t is not None and r.finish_t >= t0]
    sample = _check_sample(late_done or reqs, seed)
    limits = man.limits(cell.config)
    pad_to = eng["max_blocks_per_seq"] * cfg["engine"]["block_size"]
    t_ref = clock()
    rows = model.reference_gaps(cfg, seed, [(r.prompt, r.tokens)
                                            for r in sample], pad_to,
                                control=control)
    ref_s = clock() - t_ref
    # the control's top tokens take the served tokens' place, so it is
    # judged by the same comparison
    judged = "control" if control else "served"
    gap = max((row[judged] for row in rows), default=float("inf"))
    checked = sum(row["tokens"] for row in rows)
    errors = sum(1 for r in reqs if r.reason == "error")
    checks = {"max_logit_gap": {"value": gap,
                                "limit": limits["max_logit_gap"]},
              "tokens_checked": {"value": checked, "limit": 1},
              "quarantined": {"value": errors, "limit": 0}}
    correct = (gap <= limits["max_logit_gap"] and checked >= 1
               and errors == 0)

    # ---- metrics
    data = RunData(cfg=cfg, sizes=sizes, peaks=peaks, requests=reqs,
                   t0=t0, t1=t1, setup_s=setup_s, engine_spans=spans,
                   queue_wait_ms=queue_wait, pool=pool,
                   kernels=man.kernels(), t_end=t_end)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": mem.get("peak_bytes_in_use")}
    breakdown = None
    if trace:
        events = trace_reduce.load(tr_dir)
        shutil.rmtree(tr_dir, ignore_errors=True)
        win = trace_reduce.host_window(events, "bench.window")
        if win is None:
            raise RuntimeError("the trace holds no bench.window annotation")
        data.trace, data.trace_t0 = events, win[0]
        busy = trace_reduce.busy(events, *win)
        device["busy_s"] = trace_reduce.total(busy) / 1e9
        device["window_s"] = (win[1] - win[0]) / 1e9
        breakdown = {
            "device_ops": trace_reduce.top_ops(events, *win),
            "idle_gaps": trace_reduce.idle_by_host(
                events, busy, *win, labels=("bench.", "unified", "megastep",
                                            "prefill", "decode", "sample",
                                            "copy_cow"))}
    kind = "per_layer" if trace else "end_to_end"
    specs = (man.per_layer(cell.name) if trace
             else man.end_to_end(cell.name))
    metrics = {}
    for m in specs:
        v = man.metric_reader(m["name"])(data)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    window_due = data.window_requests()
    if mix["loop"] == "open":
        attempted = len(window_due)
        failed = sum(1 for r in window_due
                     if r.reason not in (None, "length"))
    else:
        active = [r for r in reqs if any(t0 <= t < t1 for t, _, _ in r.events)]
        attempted = len(active)
        failed = sum(1 for r in active if r.reason not in (None, "length"))
    late = [r for r in window_due if r.first_t is None]
    info = {"cache_dir": cache_dir, "setup_compiles": setup_compiles,
            "setup_phases": {**phases0, **phases},
            "window_compiles": window_compiles,
            "window_s": t1 - t0, "steps": tail_steps,
            "unanswered_at_end": len(late),
            "preemptions": report1["preemptions"] - report0["preemptions"],
            "async_steps": report1["async_steps"] - report0["async_steps"],
            "checked_seqs": len(rows), "reference_s": ref_s,
            "rows": rows, "kind": kind}
    log("bench: " + json.dumps(info))
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
