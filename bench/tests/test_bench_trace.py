"""The trace reduction and the operation and byte counts."""
from types import SimpleNamespace as NS

import pytest

import benchtest
from bench import flops, readings, trace_reduce as tr
from bench.traffic import Request

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, name, ts, dur, line=None):
    return {"plane": plane, "line": line or ("XLA Ops" if plane == DEV
                                             else "python3"),
            "name": name, "ts": float(ts), "dur": float(dur)}


SYNTH = [
    ev(DEV, "%gptq_matmul.3 = bf16[64,256] custom-call(%x)", 0, 10),
    ev(DEV, "%fusion.7 = f32[8] fusion(%y)", 5, 10),          # overlaps
    ev(DEV, "%while.1 = (s32[]) while(%z)", 0, 40),          # container
    ev(DEV, "%paged_attention_quant.2 = bf16[64,2,6,128] custom-call()",
       30, 10),
    ev(DEV, "%copy.1 = f32[2] copy(%w)", 60, 5, line="Async XLA Ops"),
    ev(HOST, "bench.step", 0, 100),
    ev(HOST, "bench.add", 42, 6),
    ev(HOST, "megastep", 50, 20),
]


def test_instruction_names():
    assert tr.instruction(SYNTH[0]) == "gptq_matmul"
    assert tr.instruction({"name": "%broadcast.15.clone.2 = f32[1] x"}) \
        == "broadcast"
    assert tr.instruction({"name": "%slice-start.3 = ((s8[1]))"}) \
        == "slice-start"


def test_busy_idle_kernels_and_gaps_by_hand():
    busy = tr.busy(SYNTH, 0, 100)
    assert busy == [(0.0, 40.0)]              # the async line is not an op
    assert tr.total(busy) == 40.0
    assert tr.gaps(busy, 0, 100) == [(40.0, 100.0)]
    assert tr.kernel_ns(SYNTH, ["gptq_matmul"], 0, 100) == 10.0
    assert tr.kernel_ns(SYNTH, ["paged_attention", "paged_attention_quant"],
                        0, 35) == 5.0
    tops = dict(tr.top_ops(SYNTH, 0, 100))
    assert "while" not in tops and tops["fusion"] == pytest.approx(1e-8)
    idle = dict(tr.idle_by_host(SYNTH, busy, 0, 100,
                                labels=("bench.", "megastep")))
    # the one gap (40, 100) has its midpoint at 70: inside bench.step only
    assert idle == {"bench.step": pytest.approx(60e-9)}
    busy2 = tr.busy(SYNTH[:2], 0, 100)
    idle2 = dict(tr.idle_by_host(SYNTH, busy2, 0, 100,
                                 labels=("bench.", "megastep")))
    # gaps (15, 100): midpoint 57.5 lies in megastep (50-70), the
    # innermost annotation covering it
    assert idle2 == {"megastep": pytest.approx(85e-9)}
    assert tr.count(SYNTH, "bench.add", 0, 100) == 1


def test_recorded_trace_reduction_is_consistent():
    doc = benchtest.fixture("trace_v5e_excerpt.json")
    events = doc["events"]
    t0, t1 = doc["window"]
    dev = tr.device_events(events)
    assert dev, "the recorded trace holds device ops"
    busy = tr.busy(events, t0, t1)
    idle = tr.total(tr.gaps(busy, t0, t1))
    assert tr.total(busy) + idle == pytest.approx(t1 - t0)
    assert 0 < tr.total(busy) <= t1 - t0
    # kernel time is the plain sum of that instruction's events
    direct = sum(min(e["ts"] + e["dur"], t1) - max(e["ts"], t0) for e in dev
                 if e["name"].startswith("%gptq_matmul.")
                 and e["ts"] < t1 and e["ts"] + e["dur"] > t0)
    assert direct > 0
    assert tr.kernel_ns(events, ["gptq_matmul"], t0, t1) == \
        pytest.approx(direct)
    # a loop's own event spans its body's ops: busy counts it, the top
    # ops leave it out, so the leaves' union lies inside busy
    leaves = tr.union(
        (max(e["ts"], t0), min(e["ts"] + e["dur"], t1)) for e in dev
        if tr.instruction(e) not in tr.CONTAINERS
        and e["ts"] < t1 and e["ts"] + e["dur"] > t0)
    assert 0 < tr.total(leaves) <= tr.total(busy)
    # every idle nanosecond is labelled, and the labels are host spans
    labels = ("bench.", "megastep", "unified")
    by_host = tr.idle_by_host(events, busy, t0, t1, labels=labels)
    assert sum(v for _, v in by_host) * 1e9 == pytest.approx(idle)
    assert all(k.startswith(labels) or k == "no host span"
               for k, _ in by_host)
    assert tr.host_window(events, "bench.window") is not None


S = {"L": 28, "d": 1536, "H": 12, "KV": 2, "Dh": 128, "F": 8960,
     "V": 151936}


def test_counts_on_hand_worked_shapes():
    # one layer's matmul parameters of qwen2-1.5b
    assert flops.layer_matmul_params(S) == \
        1536 * 1536 + 2 * 1536 * 256 + 1536 * 1536 + 3 * 1536 * 8960
    f = flops.forward_flops(S, ctx=10, sampled=False)
    assert f == 28 * (2 * flops.layer_matmul_params(S) + 4 * 12 * 128 * 10)
    assert flops.forward_flops(S, 10, True) - f == 2 * 1536 * 151936
    # a prompt of n tokens = n single tokens at ctx 1..n, head once
    n = 7
    by_token = sum(flops.forward_flops(S, c, False) for c in range(1, n + 1))
    assert flops.prompt_flops(S, n) == pytest.approx(
        by_token + 2 * 1536 * 151936)
    # decode attention over 300 tokens of an int8 pool, 128-token pages
    fl, by = flops.paged_decode_work(S, [300], itemsize=1, scale_bytes=4,
                                     block=128)
    assert fl == 28 * 4 * 12 * 128 * 300
    assert by == 28 * (2 * 300 * 2 * 128 + 2 * 3 * 2 * 4 + 2 * 12 * 128 * 2)
    # the W4A16 weights: half a byte per weight plus two f32 per group
    fl, by = flops.int4_matmul_work(S, passes=1, tokens=0, group=128)
    assert fl == 0
    w = sum(k * n / 2 + 2 * (k // 128) * n * 4
            for k, n in flops.matmul_shapes(S))
    assert by == 28 * w


def test_a_share_cannot_pass_one_at_the_ideal_time():
    for work in ((1e12, 1e9), (1e9, 1e12), (5e11, 5e11)):
        least, side = flops.least_time(*work, 197e12, 819e9)
        assert side in ("compute", "memory")
        ideal = max(work[0] / 197e12, work[1] / 819e9)
        assert least / ideal == pytest.approx(1.0)
        assert least / (ideal * 1.5) < 1.0


def _span(name, **args):
    return NS(name=name, cat="device", ts=0, dur=1, args=args or None)


def test_a_mixed_step_is_one_weight_pass(monkeypatch):
    # a megastep of 8 steps; a chained step whose decode rows and chunk
    # share one dispatch (the engine counts it as a decode step and a
    # prefill chunk); a chunk dispatched alone
    spans = [_span("dispatch:megastep", n_steps=8), _span("readback"),
             _span("dispatch:unified_chained", start=0, length=512),
             _span("dispatch:chunk", start=512, length=100)]
    dec = Request(index=0, due=0.0, prompt=[1] * 50, max_tokens=20,
                  temperature=0.0, top_p=1.0, seed=None)
    dec.events = [(1.0, 1, 8), (2.0, 9, 1)]            # 9 decode tokens
    pre = Request(index=1, due=0.0, prompt=[1] * 612, max_tokens=20,
                  temperature=0.0, top_p=1.0, seed=None)
    pre.first_t, pre.events = 2.0, [(2.0, 0, 1)]      # its prefill ended
    run = NS(engine_spans=spans, requests=[dec, pre], t0=0.0, t1=3.0,
             sizes=S, cfg={"quantization": {"method": "rtn-int4",
                                            "group_size": 128}})
    assert readings.forward_passes(run) == 10
    monkeypatch.setattr(readings, "roofline_percent",
                        lambda run, label, work: work)
    read = benchtest.mf.Manifest().metric_reader("kernel.gptq_mm_roofline")
    assert read(run) == flops.int4_matmul_work(S, 10, 9 + 612, 128)
