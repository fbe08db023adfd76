"""repro.obs: span tracer, metrics registry, HTTP exposition, and the
engine's lifecycle-derived latency histograms + host/device attribution."""
import json
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs.registry import get_reduced
from repro.models import transformer as T
from repro.obs import (Gauge, Histogram, MetricsDict, MetricsRegistry,
                       SpanTracer, attribute_steps, validate_chrome_trace)
from repro.obs.http import start_obs_server
from repro.runtime.fault import StragglerDetector
from repro.serving import SamplingParams, ServingEngine

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small():
    cfg = get_reduced("qwen2-1.5b", num_layers=2)
    params = T.init_params(cfg, KEY)
    return cfg, params


@pytest.fixture(scope="module")
def served(small):
    """One engine run shared by the derivation/attribution/export tests."""
    cfg, params = small
    eng = ServingEngine(cfg, params, max_slots=4, num_blocks=128,
                        max_blocks_per_seq=8, prefill_bucket=16,
                        detokenizer=lambda ids: "".join(
                            chr(97 + i % 26) for i in ids))
    rng = np.random.default_rng(0)
    sp = SamplingParams(max_tokens=4)
    for _ in range(6):
        eng.add(list(rng.integers(1, 200, int(rng.integers(3, 15)))), sp)
    eng.run_until_done()
    return eng


# ------------------------------------------------------------------ tracer
def test_span_nesting_records_depth():
    tr = SpanTracer()
    with tr.span("outer"):
        with tr.span("inner", cat="device"):
            pass
    inner, outer = tr.spans()          # completion order: inner exits first
    assert (inner.name, inner.depth) == ("inner", 1)
    assert (outer.name, outer.depth) == ("outer", 0)
    assert inner.cat == "device"
    # containment: the inner span's window sits inside the outer's
    assert outer.ts <= inner.ts
    assert inner.ts + inner.dur <= outer.ts + outer.dur


def test_ring_truncation_counts_dropped():
    tr = SpanTracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr.spans()) == 4
    assert [s.name for s in tr.spans()] == ["e6", "e7", "e8", "e9"]
    assert tr.dropped == 6
    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0


def test_disabled_tracer_is_zero_work():
    tr = SpanTracer(enabled=False)
    # the disabled path hands out ONE shared no-op object — no per-span
    # allocation on a telemetry-off hot loop
    assert tr.span("a") is tr.span("b")
    with tr.span("a", cat="device", args={"x": 1}) as sp:
        sp.set(y=2)                    # no-op, chains fine
    tr.instant("mark")
    assert tr.spans() == [] and tr.dropped == 0
    tr.enable()
    with tr.span("now-recorded"):
        pass
    assert [s.name for s in tr.spans()] == ["now-recorded"]


def test_chrome_trace_schema_valid():
    tr = SpanTracer()
    with tr.span("step", cat="step", args={"k": 1}):
        tr.instant("mark", cat="request")
    doc = tr.to_chrome_trace()
    assert validate_chrome_trace(doc) == []
    phs = {e["name"]: e["ph"] for e in doc["traceEvents"]}
    assert phs == {"mark": "i", "step": "X"}
    step = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert step["dur"] >= 0 and step["args"] == {"k": 1}
    # validator actually catches malformed docs
    assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]})


def test_attribution_host_plus_device_is_step():
    tr = SpanTracer()
    for _ in range(3):
        with tr.span("engine.step", cat="step"):
            with tr.span("plan", cat="host"):
                pass
            with tr.span("dispatch:unified", cat="device"):
                pass
            with tr.span("readback", cat="device"):
                pass
    attr = attribute_steps(tr.spans(), window=2)
    assert attr["steps"] == 2.0
    assert attr["host_ms"] + attr["device_ms"] == \
        pytest.approx(attr["step_ms"])
    assert 0.0 < attr["device_frac"] < 1.0
    assert attr["host_frac"] + attr["device_frac"] == pytest.approx(1.0)
    # no work steps (e.g. tracer disabled) -> NaN columns, not garbage
    empty = attribute_steps([])
    assert empty["steps"] == 0.0 and empty["host_ms"] != empty["host_ms"]


class _Annotations:
    """A fake annotation factory: records (event, label) in order."""

    def __init__(self):
        self.log = []

    def __call__(self, label):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", label))

            def __exit__(self, *exc):
                log.append(("exit", label))
        return _Ann()


@pytest.mark.parametrize("with_factory", [True, False],
                         ids=["factory", "no-factory"])
def test_spans_mirror_into_annotation_factory(with_factory):
    ann = _Annotations()
    tr = SpanTracer(annotate=ann if with_factory else None)
    with tr.span("engine.step", cat="step"):
        with tr.span("dispatch:megastep", cat="device", label="megastep"):
            with tr.span("readback", cat="device"):
                pass
        tr.instant("compile", cat="compile", args={"executable": "x"})
    # the ring is the same either way
    assert [s.name for s in tr.spans()] == [
        "readback", "dispatch:megastep", "compile", "engine.step"]
    if not with_factory:
        assert ann.log == []
        return
    # each span is one annotation of the same nesting, under its label
    assert ann.log == [("enter", "engine.step"), ("enter", "megastep"),
                       ("enter", "readback"), ("exit", "readback"),
                       ("exit", "megastep"), ("enter", "compile"),
                       ("exit", "compile"), ("exit", "engine.step")]


def test_disabled_tracer_with_factory_is_zero_work():
    ann = _Annotations()
    tr = SpanTracer(enabled=False, annotate=ann)
    assert tr.span("a") is tr.span("b", label="c")
    with tr.span("a", cat="device"):
        tr.instant("mark")
    assert ann.log == [] and tr.spans() == []


def _attribute_steps_quadratic(spans, window=None):
    """The pre-sweep ``attribute_steps``: a device span counts when no
    other device span contains it (quadratic), summed per step."""
    spans = list(spans)
    steps = [s for s in spans if s.name == "engine.step"
             and s.dur is not None]
    device = [s for s in spans if s.cat == "device" and s.dur is not None]
    top = [d for d in device
           if not any(o is not d and o.ts <= d.ts
                      and d.ts + d.dur <= o.ts + o.dur for o in device)]
    rows = []
    for st in steps:
        end = st.ts + st.dur
        dev = sum(d.dur for d in top
                  if st.ts <= d.ts and d.ts + d.dur <= end)
        if dev > 0:
            rows.append((st.dur, dev))
    if window is not None:
        rows = rows[-window:]
    n = len(rows)
    return (n, sum(r[0] for r in rows) / n / 1e6,
            sum(r[1] for r in rows) / n / 1e6)


def test_attribute_steps_sweep_equals_quadratic_on_nested_spans():
    from repro.obs import Span
    rng = np.random.default_rng(1)
    spans, t = [], 0
    for i in range(40):
        t0 = t
        t += int(rng.integers(5, 50))
        kids = []
        for _ in range(int(rng.integers(0, 4))):
            a = t
            t += int(rng.integers(1, 30))
            kids.append(Span("plan", "host", a, t - a, 1, None))
            a = t
            t += int(rng.integers(10, 300))
            disp = Span("dispatch:megastep", "device", a, t - a, 1, None)
            # a readback nested in the dispatch, as the megastep has
            rb = Span("readback", "device", a + (t - a) // 2,
                      (t - a) // 4, 2, None)
            kids += [rb, disp]
        if i % 7 == 3:
            a = t
            t += 20
            kids.append(Span("readback", "device", a, 20, 1, None))
        t += int(rng.integers(1, 9))
        spans += kids + [Span("engine.step", "step", t0, t - t0, 0, None)]
        spans.append(Span("req.arrival", "request", t, None, 0, None))
    for window in (None, 5):
        new = attribute_steps(spans, window=window)
        n, step_ms, device_ms = _attribute_steps_quadratic(spans, window)
        assert new["steps"] == n > 0
        assert new["step_ms"] == pytest.approx(step_ms)
        assert new["device_ms"] == pytest.approx(device_ms)


# ----------------------------------------------------------------- metrics
def test_histogram_bucket_edges_le_semantics():
    h = Histogram("h_ms", buckets=(1.0, 5.0, 10.0))
    for v in (0.5, 1.0, 1.001, 5.0, 99.0):   # 1.0 and 5.0 land ON an edge
        h.observe(v)
    assert h.counts == [2, 2, 0, 1]          # le=1: {0.5, 1.0}; +Inf: {99}
    assert h.cumulative() == [("1", 2), ("5", 4), ("10", 4), ("+Inf", 5)]
    assert h.count == 5 and h.sum == pytest.approx(106.501)
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(5.0, 1.0))


def test_histogram_percentile_matches_numpy():
    h = Histogram("h", buckets=(1e9,), sample_maxlen=64)
    rng = np.random.default_rng(0)
    xs = rng.uniform(0, 100, 50)
    for v in xs:
        h.observe(v)
    for p in (0, 50, 99, 100):
        assert h.percentile(p) == pytest.approx(np.percentile(xs, p))
    h.clear_samples()
    assert h.percentile(50) != h.percentile(50)   # NaN on empty window
    assert h.count == 50                          # cumulative untouched


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("repro_gen_tokens", help="tokens").inc(7)
    reg.gauge("repro_waiting").set(3)
    h = reg.histogram("repro_itl_ms", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(4.0)
    text = reg.to_prometheus()
    assert "# TYPE repro_gen_tokens counter" in text
    assert "# HELP repro_gen_tokens tokens" in text
    assert "repro_gen_tokens 7" in text
    assert "# TYPE repro_waiting gauge" in text
    assert 'repro_itl_ms_bucket{le="1"} 1' in text
    assert 'repro_itl_ms_bucket{le="10"} 2' in text
    assert 'repro_itl_ms_bucket{le="+Inf"} 2' in text
    assert "repro_itl_ms_sum 4.5" in text
    assert "repro_itl_ms_count 2" in text
    with pytest.raises(ValueError):
        reg.counter("0bad name")


def test_registry_snapshot_json_and_type_guard():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.gauge("g").set(float("nan"))
    reg.histogram("h", buckets=(1.0,)).observe(2.0)
    snap = reg.snapshot()
    json.dumps(snap, allow_nan=False)            # NaN-free by contract
    assert snap["gauges"]["g"] is None
    assert snap["histograms"]["h"]["buckets"] == {"1": 0, "+Inf": 1}
    with pytest.raises(TypeError):
        reg.gauge("c")                           # name already a counter
    assert reg.counter("c").get() == 1.0         # get-or-create idempotent


def test_metrics_dict_facade_backed_by_registry():
    reg = MetricsRegistry()
    m = MetricsDict(reg, initial={"gen_tokens": 0})
    m["gen_tokens"] += 2                         # the engine's idiom
    m.setdefault("preemptions", 0)               # the scheduler's idiom
    m["preemptions"] += 1
    assert m["gen_tokens"] == 2.0
    assert reg.get("repro_gen_tokens").get() == 2.0
    assert dict(m) == {"gen_tokens": 2.0, "preemptions": 1.0}
    with pytest.raises(KeyError):
        m["never_created"]


# -------------------------------------------------------------------- http
def test_http_metrics_health_trace_smoke():
    reg = MetricsRegistry()
    reg.counter("repro_gen_tokens").inc(5)
    tr = SpanTracer()
    tr.instant("mark")
    srv = start_obs_server(0, registry=reg, tracer=tr,
                           health_fn=lambda: {"waiting": 1.0,
                                              "max_waiting": float("inf")})
    port = srv.server_address[1]
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}") as r:
                return r.status, r.read().decode()
        code, text = get("/metrics")
        assert code == 200 and "repro_gen_tokens 5" in text
        code, text = get("/health")
        assert code == 200
        assert json.loads(text) == {"waiting": 1.0, "max_waiting": None}
        code, text = get("/trace")
        assert code == 200
        assert validate_chrome_trace(json.loads(text)) == []
        with pytest.raises(urllib.error.HTTPError):
            get("/nope")
    finally:
        srv.shutdown()


# ------------------------------------------------------------------ engine
def test_engine_latency_histograms_match_lifecycle(served):
    eng = served
    fin = eng.finished
    assert fin
    want_ttft = sorted((r.first_token_t - r.arrival) * 1e3 for r in fin)
    assert sorted(eng._h_ttft.samples()) == pytest.approx(want_ttft)
    want_wait = sorted((r.admitted_t - r.arrival) * 1e3 for r in fin)
    assert sorted(eng._h_queue_wait.samples()) == pytest.approx(want_wait)
    assert all(w >= 0 for w in want_wait)
    # ITL window feeds report() in ms, no double unit conversion
    rep = eng.report()
    assert rep["itl_p50_ms"] == pytest.approx(
        float(np.percentile(eng._h_itl.samples(), 50)))
    assert rep["queue_wait_p50_ms"] == pytest.approx(
        float(np.percentile(want_wait, 50)))


def test_engine_attribution_and_trace_export(served, tmp_path):
    eng = served
    attr = eng.attribution()
    assert attr["steps"] > 0
    assert attr["host_ms"] + attr["device_ms"] == \
        pytest.approx(attr["step_ms"])
    assert 0.0 <= attr["host_frac"] <= 1.0
    names = {s.name for s in eng.tracer.spans()}
    assert {"engine.step", "plan", "detokenize", "req.arrival",
            "req.admitted", "req.first_token", "req.finish"} <= names
    assert any(n.startswith("dispatch:") for n in names)
    out = tmp_path / "trace.json"
    eng.tracer.save(str(out))
    doc = json.loads(out.read_text())
    assert validate_chrome_trace(doc) == []
    assert len(doc["traceEvents"]) == len(eng.tracer.spans())


def test_report_health_served_from_registry(served):
    eng = served
    rep, health = eng.report(), eng.health()
    # the deduped robustness block: one source, both views, same names
    for k in ("step_time_ema_ms", "slow_steps", "dispatch_retries",
              "quarantined", "shed", "aborted", "deadline_expired",
              "block_utilization"):
        assert rep[k] == health[k]
    for k in ("waiting", "running", "free_blocks", "watermark_blocks",
              "probing_rids", "max_waiting"):
        assert k in health
    # counters flow through to the Prometheus exposition
    text = eng.obs.to_prometheus()
    assert f'repro_gen_tokens {eng.metrics["gen_tokens"]:g}' in text
    assert "repro_request_ttft_ms_bucket" in text
    json.dumps(eng.obs.snapshot(), allow_nan=False)


def test_telemetry_off_engine_still_serves(small):
    cfg, params = small
    eng = ServingEngine(cfg, params, max_slots=2, num_blocks=64,
                        max_blocks_per_seq=8, prefill_bucket=16,
                        enable_telemetry=False)
    eng.add([5, 9, 13, 2, 7], SamplingParams(max_tokens=3))
    rep = eng.run_until_done()
    assert len(eng.finished) == 1
    assert eng.tracer.spans() == []              # traced nothing
    attr = eng.attribution()
    assert attr["steps"] == 0.0                  # NaN columns, no crash
    assert rep["itl_p50_ms"] == rep["itl_p50_ms"]  # histograms still on
    assert eng.metrics["gen_tokens"] == 3


# the runner's forward-pass dispatch spans and how many passes each makes
FORWARD = ("dispatch:megastep", "dispatch:unified",
           "dispatch:unified_chained", "dispatch:decode", "dispatch:chunk",
           "dispatch:prefill")


@pytest.mark.parametrize("mode", [
    {}, {"enable_async_step": False}, {"enable_unified_step": False},
    {"use_fused": False}, {"enable_chunked_prefill": False}],
    ids=["async", "unified", "two-call", "legacy-decode", "oracle-prefill"])
def test_forward_pass_spans_carry_rows(small, mode):
    cfg, params = small
    eng = ServingEngine(cfg, params, max_slots=4, num_blocks=128,
                        max_blocks_per_seq=8, prefill_bucket=16,
                        max_num_batched_tokens=16, **mode)
    rng = np.random.default_rng(2)
    for _ in range(6):
        eng.add(list(rng.integers(1, 200, int(rng.integers(3, 30)))),
                SamplingParams(max_tokens=int(rng.integers(2, 12))))
    eng.run_until_done()
    passes = rows = 0
    for s in eng.tracer.spans():
        if s.name not in FORWARD:
            continue
        assert 0 <= s.args["rows"] <= 4, s
        n = s.args["n_steps"] if s.name == "dispatch:megastep" else 1
        passes += n
        rows += s.args["rows"] * n
    assert passes > 0 and rows > 0
    # the registry counts the same passes and rows at the same boundary
    assert eng.obs.get("repro_forward_passes").get() == passes
    assert eng.obs.get("repro_decode_rows").get() == rows
    text = eng.obs.to_prometheus()
    assert "repro_forward_passes" in text and "repro_decode_rows" in text


@pytest.mark.parametrize("mode", [
    {}, {"enable_async_step": False}, {"use_fused": False}],
    ids=["async", "unified", "legacy-decode"])
def test_forward_pass_spans_carry_pages(small, mode):
    """Each forward-pass span's ``pages`` arg, and ``repro_decode_pages``,
    is the live KV pages its decode rows read per layer: the sum of
    ceil(seq_len / BS) over the device lengths the dispatch ran with."""
    cfg, params = small
    eng = ServingEngine(cfg, params, max_slots=4, num_blocks=128,
                        max_blocks_per_seq=8, prefill_bucket=16,
                        max_num_batched_tokens=16, **mode)
    runner, bs = eng.runner, cfg.paging.block_size
    seen = []

    def watch(name):
        run = getattr(runner, name)

        def wrapped(*a, **kw):
            sl = np.asarray(runner.state["seq_lens"])
            seen.append(int(np.sum(-(-sl // bs))))
            return run(*a, **kw)
        setattr(runner, name, wrapped)
    for name in ("megastep", "unified_step", "unified_step_chained",
                 "decode"):
        watch(name)
    rng = np.random.default_rng(5)
    for _ in range(6):
        eng.add(list(rng.integers(1, 200, int(rng.integers(3, 40)))),
                SamplingParams(max_tokens=int(rng.integers(4, 24))))
    eng.run_until_done()
    pages, total = [], 0
    for s in eng.tracer.spans():
        if s.name in FORWARD[:4]:
            n = s.args["n_steps"] if s.name == "dispatch:megastep" else 1
            pages.append(s.args["pages"] if s.args["rows"] else 0)
            total += s.args["pages"] * n
        elif s.name in FORWARD:
            assert s.args["pages"] == 0, s
    # chunk-only unified dispatches decode nothing: their tables are empty
    assert pages == seen and max(pages) > 1
    assert eng.obs.get("repro_decode_pages").get() == total
    assert "repro_decode_pages" in eng.obs.to_prometheus()


def test_compile_fires_once_per_executable(small):
    cfg, params = small
    eng = ServingEngine(cfg, params, max_slots=4, num_blocks=128,
                        max_blocks_per_seq=8, max_num_batched_tokens=16)

    def serve(seed):
        rng = np.random.default_rng(seed)
        for _ in range(5):
            eng.add(list(rng.integers(1, 200, int(rng.integers(3, 40)))),
                    SamplingParams(max_tokens=6))
        eng.run_until_done()

    def compiles():
        return [s.args["executable"] for s in eng.tracer.spans()
                if s.name == "compile"]
    serve(0)
    first = compiles()
    assert first and len(first) == len(set(first))     # once each
    assert {"unified_step_chained", "megastep"} <= set(first)
    assert eng.obs.get("repro_compiles").get() == len(first)
    eng.tracer.clear()
    serve(1)                                            # a warm loop
    assert compiles() == []
    assert eng.obs.get("repro_compiles").get() == len(first)


def test_straggler_events_bounded():
    det = StragglerDetector(threshold=1.5, patience=10**9)
    det.observe(0, 1.0)                          # seeds the EMA
    for i in range(1, 1002):
        det.observe(i, 10.0)                     # every step flagged
    assert len(det.events) == 256                # bounded, not a leak
    assert det.events[-1]["step"] == 1001
