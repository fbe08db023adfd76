"""The readers of the program's own spans and counters, on hand-worked
span lists and trace events; and the six readers that came before them,
which read the same values as before on the recorded trace."""
from types import SimpleNamespace as NS

import pytest

import benchtest
from bench.harness import RunData
from bench.traffic import Request

DEV, HOST = "/device:TPU:0", "/host:CPU"
MAN = benchtest.mf.Manifest()
CFG = MAN.config("qwen2-1.5b-w4a16")
S = MAN.model(CFG).sizes(CFG)
PEAKS = MAN.peaks()["TPU v5 lite"]


def read(name, run):
    return MAN.metric_reader(name)(run)


def span(name, ts=0, dur=1, cat="device", **args):
    return NS(name=name, cat=cat, ts=ts, dur=dur, args=args or None)


def ev(plane, name, ts, dur):
    return {"plane": plane, "line": "XLA Ops" if plane == DEV else "python3",
            "name": name, "ts": float(ts), "dur": float(dur)}


def run_of(spans=(), trace=None, window=(0.0, 1e-6), requests=()):
    return RunData(cfg=CFG, sizes=S, peaks=PEAKS, requests=list(requests),
                   t0=window[0], t1=window[1], setup_s=1.0,
                   engine_spans=list(spans), queue_wait_ms=[],
                   pool={"itemsize": 1, "quantized": True}, trace=trace,
                   trace_t0=0.0, kernels=MAN.kernels())


# ------------------------------------------------ sched.decode_rows_per_pass
def test_decode_rows_per_pass_by_hand():
    spans = [span("dispatch:megastep", n_steps=8, rows=64),
             span("readback"),
             span("dispatch:unified_chained", start=0, length=512, rows=60),
             span("dispatch:chunk", start=512, length=100, rows=0),
             span("dispatch:cow", pairs=2), span("plan", cat="host")]
    # (8 x 64 + 60 + 0) rows over 8 + 1 + 1 passes
    assert read("sched.decode_rows_per_pass", run_of(spans)) == \
        pytest.approx((8 * 64 + 60) / 10)


@pytest.mark.parametrize("spans", [
    [], [span("dispatch:cow", pairs=1), span("readback")],
    [span("dispatch:megastep", n_steps=8), span("dispatch:decode")]],
    ids=["no-spans", "no-pass", "no-rows"])
def test_decode_rows_per_pass_none(spans):
    assert read("sched.decode_rows_per_pass", run_of(spans)) is None


# --------------------------------------------------- device.host_idle_share
HOST_TRACE = [
    ev(DEV, "%fusion.1 = f32[8] fusion(%a)", 0, 10),
    ev(DEV, "%paged_attention_quant.2 = bf16[1] custom-call()", 30, 40),
    ev(DEV, "%gptq_matmul.3 = bf16[1] custom-call()", 80, 10),
    ev(HOST, "bench.step", 0, 100),
    ev(HOST, "engine.step", 5, 90),
    ev(HOST, "plan", 10, 10),
    ev(HOST, "megastep", 22, 50),
    ev(HOST, "readback", 60, 14),
    ev(HOST, "engine.step", 200, 10),        # after the window
]


def test_host_idle_share_by_hand():
    # window (0, 100) ns; idle gaps (10, 30), (70, 80), (90, 100) with
    # midpoints 20 (engine.step, outside every wait: host), 75 (the
    # engine.step only: host) and 95 (bench.step only: not the program)
    run = run_of(trace=HOST_TRACE, window=(0.0, 100e-9))
    assert read("device.host_idle_share", run) == pytest.approx(30.0)
    assert read("device.idle_share", run) == pytest.approx(40.0)


def test_host_idle_share_counts_no_idle_inside_a_wait():
    trace = HOST_TRACE + [ev(HOST, "unified_step_chained", 12, 10)]
    run = run_of(trace=trace, window=(0.0, 100e-9))
    assert read("device.host_idle_share", run) == pytest.approx(10.0)


@pytest.mark.parametrize("trace", [None, [e for e in HOST_TRACE
                                          if e["name"] != "engine.step"]],
                         ids=["untraced", "no-engine-step"])
def test_host_idle_share_none(trace):
    run = run_of(trace=trace, window=(0.0, 100e-9))
    assert read("device.host_idle_share", run) is None


# -------------------------------------------- kernel.chunk_prefill_roofline
CHUNK_TRACE = [ev(DEV, "%flash_attention_chunk.4 = bf16[1] custom-call()",
                  0, 2e6),
               ev(DEV, "%flash_attention.5 = bf16[1] custom-call()", 0, 9e6)]


def test_chunk_prefill_roofline_by_hand():
    spans = [span("dispatch:unified_chained", start=256, length=512,
                  rows=63),
             span("dispatch:chunk", start=0, length=100, rows=0),
             span("dispatch:unified", start=0, length=0, rows=64),
             span("dispatch:megastep", n_steps=8, rows=64)]
    run = run_of(spans, CHUNK_TRACE, window=(0.0, 4e-3))
    L, H, KV, Dh = S["L"], S["H"], S["KV"], S["Dh"]
    ops = 4 * H * Dh * (512 * (256 + 513 / 2) + 100 * (0 + 101 / 2)) * L
    nbytes = L * (2 * 256 * KV * Dh * 1 + 2 * 2 * KV * 4      # prefix, int8
                  + 2 * 612 * KV * Dh * 2                     # own K, V
                  + 2 * 612 * H * Dh * 2)                     # Q in, O out
    least = max(ops / PEAKS["bf16_flops"], nbytes / PEAKS["hbm_bytes_per_s"])
    assert read("kernel.chunk_prefill_roofline", run) == \
        pytest.approx(100 * least / 2e-3)


@pytest.mark.parametrize("spans,trace", [
    ([span("dispatch:chunk", start=0, length=64, rows=0)], None),
    ([span("dispatch:unified", start=0, length=0, rows=64)], CHUNK_TRACE),
    ([span("dispatch:chunk", start=0, length=64, rows=0)],
     CHUNK_TRACE[1:])], ids=["untraced", "no-chunk", "no-kernel-time"])
def test_chunk_prefill_roofline_none(spans, trace):
    run = run_of(spans, trace, window=(0.0, 4e-3))
    assert read("kernel.chunk_prefill_roofline", run) is None


# ----------------------------------------- the six readers, recorded trace
def recorded_run():
    """The recorded 12 ms of the offline cell with a hand-made step of
    spans, the 64 slots' requests (one decode token each in the window,
    and one 64-token prompt whose prefill ends in it), and one more
    ``step()`` call opening inside the window (the recorded one opened
    before it)."""
    doc = benchtest.fixture("trace_v5e_excerpt.json")
    reqs = []
    for i in range(64):
        r = Request(index=i, due=0.0, prompt=[1] * (600 + 13 * i),
                    max_tokens=2048, temperature=0.0, top_p=1.0, seed=None)
        r.first_t, r.events = -1.0, [(0.006, 100 + i, 1)]
        reqs.append(r)
    new = Request(index=64, due=0.0, prompt=[1] * 64, max_tokens=512,
                  temperature=0.0, top_p=1.0, seed=None)
    new.first_t, new.events = 0.009, [(0.009, 0, 1)]
    reqs.append(new)
    ms = 1_000_000
    spans = [span("engine.step", 0, 12 * ms, cat="step"),
             span("plan", ms // 10, ms // 2, cat="host"),
             span("dispatch:megastep", 2 * ms, 9 * ms, n_steps=1),
             span("readback", 3 * ms, 7 * ms),
             span("absorb", 11 * ms, ms // 2, cat="host")]
    events = doc["events"] + [ev(HOST, "bench.step", 6 * ms, ms)]
    run = run_of(spans, events, window=(0.0, 0.012), requests=reqs)
    run.trace_t0 = doc["window"][0]
    return run


@pytest.mark.parametrize("name,value", [
    ("sched.host_ms_per_step", 3.0),
    ("step.device_ms_per_step", 12.0),
    ("step.mfu", 16.018146111675126),
    ("kernel.paged_decode_roofline", 23.949777748394393),
    ("kernel.gptq_mm_roofline", 95.88460388837521),
    ("device.idle_share", 0.0)])
def test_existing_readers_read_as_before_on_the_recorded_trace(name, value):
    assert read(name, recorded_run()) == pytest.approx(value, rel=1e-9)


def test_new_readers_on_the_recorded_trace():
    run = recorded_run()
    # the recorded trace predates the program's engine.step annotation
    # and holds no chunk; its spans carry no rows
    assert read("device.host_idle_share", run) is None
    assert read("kernel.chunk_prefill_roofline", run) is None
    assert read("sched.decode_rows_per_pass", run) is None
    run.engine_spans[2].args["rows"] = 64
    assert read("sched.decode_rows_per_pass", run) == 64.0
