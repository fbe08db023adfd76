"""Unified single-dispatch serving step: greedy tokens against the
teacher-forced ``T.forward`` reference on both KV pool formats, fused
sampling bitwise-identical to the whole-prompt path's own sample
dispatch, preemption mid-prefill, the single-compile guarantee, and the
dispatch-count accounting."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_reduced
from repro.core.kv_quant import cache_from_state
from repro.models import transformer as T
from repro.serving import SamplingParams, ServingEngine

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small():
    cfg = get_reduced("qwen1.5-0.5b", num_layers=2)
    params = T.init_params(cfg, KEY)
    return cfg, params


def _prompts(n, seed=0, lo=4, hi=20):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, 200, int(rng.integers(lo, hi))))
            for _ in range(n)]


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_blocks_per_seq", 8)
    kw.setdefault("max_num_batched_tokens", 16)
    return ServingEngine(cfg, params, **kw)


def _drain(eng, prompts, sps):
    for p, sp in zip(prompts, sps):
        eng.add(p, sp)
    eng.run_until_done()
    return {r.rid: list(r.output) for r in eng.finished}, \
        {r.rid: r.finish_reason for r in eng.finished}


# How far below the reference's best logit an int8-pool greedy token's
# reference logit may sit.  The int8 pool stores every K/V element to
# within half a quantization step (amax / 254 of its block and head), an
# error the dense pool does not make, so where the reference's top logits
# nearly tie the int8 engine may take the runner-up.  On this two-layer
# model the logits are bf16 of magnitude below 1, where one rounding step
# is 2**-8; the measured gap is at most one such step.  0.02 allows five,
# and stays below the median top-two margin (about 0.04), so a token that
# is not a near-tie still fails.  A chunk whose K/V lands one position
# off moves the logits far more than that.
INT8_LOGIT_TOL = 0.02


def _reference_gaps(cfg, params, eng):
    """For every served token: the teacher-forced reference's best logit
    minus its logit of the served token, given the served prefix (one
    ``T.forward`` per request over prompt + output)."""
    fwd = jax.jit(lambda t: T.forward(cfg, params, {"tokens": t}))
    gaps = []
    for r in eng.finished:
        prompt, out = list(r.prompt_token_ids), list(r.output)
        logits = np.asarray(fwd(jnp.asarray([prompt + out[:-1]])),
                            np.float32)[0, len(prompt) - 1:]
        gaps += [float(row.max() - row[t]) for row, t in zip(logits, out)]
    return gaps


@pytest.mark.parametrize("kv_cache_dtype", ["bf16", "int8"])
def test_unified_token_exact_vs_reference(small, kv_cache_dtype):
    """Acceptance: multi-chunk greedy serving through the unified
    single-dispatch step serves the teacher-forced reference's argmax at
    every position on the dense pool, and a token within
    ``INT8_LOGIT_TOL`` of it on the int8 pool, from exactly one
    unified-step compile."""
    cfg, params = small
    prompts = _prompts(5, seed=21, lo=24, hi=60)
    sps = [SamplingParams(max_tokens=10)] * 5
    eng = _engine(cfg, params, kv_cache_dtype=kv_cache_dtype)
    _, fin = _drain(eng, prompts, sps)
    assert eng.metrics["prefill_chunks"] > len(prompts)   # really chunked
    assert set(fin.values()) == {"length"}
    gaps = _reference_gaps(cfg, params, eng)
    assert len(gaps) == 5 * 10
    tol = 0.0 if kv_cache_dtype == "bf16" else INT8_LOGIT_TOL
    assert max(gaps) <= tol, sorted(gaps)[-5:]
    assert eng.runner.unified_compiles() == 1
    assert eng.runner.prefill_compiles() == 1


def test_unified_sampling_bitwise_vs_whole_prompt(small):
    """Fused sampling inside the unified dispatch (decode rows + the
    chunk's first token, one sample kernel over max_slots + 1 rows) is
    bitwise-identical to the whole-prompt path, whose first token comes
    from its own sample dispatch, across mixed sampling modes, including
    seeded requests."""
    cfg, params = small
    prompts = _prompts(4, seed=31, lo=20, hi=40)
    sps = [SamplingParams(max_tokens=8),
           SamplingParams(temperature=0.9, max_tokens=8),
           SamplingParams(temperature=0.8, top_k=5, max_tokens=8),
           SamplingParams(temperature=0.7, top_p=0.9, seed=7, max_tokens=8)]
    ref = _engine(cfg, params, enable_chunked_prefill=False,
                  prefill_bucket=16)
    o_ref, _ = _drain(ref, prompts, sps)
    eng = _engine(cfg, params)
    o_chk, _ = _drain(eng, prompts, sps)
    assert ref.runner._unified is None and eng.metrics["prefill_chunks"]
    assert o_ref == o_chk


@pytest.mark.parametrize("kv_cache_dtype", ["bf16", "int8"])
def test_unified_preemption_mid_prefill_parity(small, kv_cache_dtype):
    """A block-starved unified run that preempts a sequence mid-prefill
    still matches the roomy unified run token-for-token."""
    cfg, params = small
    rng = np.random.default_rng(51)
    prompts = [list(rng.integers(1, 200, n)) for n in (28, 28, 64)]
    sps = [SamplingParams(max_tokens=24)] * 3
    roomy, _ = _drain(
        _engine(cfg, params, max_num_batched_tokens=8, num_blocks=256,
                kv_cache_dtype=kv_cache_dtype), prompts, sps)
    eng = _engine(cfg, params, max_num_batched_tokens=8, num_blocks=9,
                  kv_cache_dtype=kv_cache_dtype)
    tight, _ = _drain(eng, prompts, sps)
    assert eng.metrics["preemptions_mid_prefill"] > 0, \
        "scenario must preempt a sequence mid-prefill"
    assert roomy == tight


def test_prefill_beside_multi_token_horizon_parity(small):
    """A preemption inside ``plan_horizon`` can free room for prefill
    after the horizon was chosen.  That plan's decodes run as its
    megastep and its chunks as chunk-only unified dispatches, and the
    block-starved run stays token-exact against a roomy one."""
    cfg, params = small
    prompts = _prompts(4, seed=11, lo=17, hi=30)
    sps = [SamplingParams(max_tokens=32)] * 4
    roomy, _ = _drain(_engine(cfg, params, num_blocks=256), prompts, sps)
    eng = _engine(cfg, params, num_blocks=8)
    plans, megasteps = [], []
    plan_step, dispatch_decode = eng.scheduler.plan_step, eng._dispatch_decode

    def spy(*a, **kw):
        plans.append(plan_step(*a, **kw))
        return plans[-1]

    def spy_decode(plan, outs):
        megasteps.append(plan)
        return dispatch_decode(plan, outs)
    eng.scheduler.plan_step, eng._dispatch_decode = spy, spy_decode
    tight, _ = _drain(eng, prompts, sps)
    mixed = [p for p in plans if p.prefill and p.decode_slots
             and p.horizon > 1]
    assert mixed, "scenario must plan prefill beside h > 1"
    assert all(any(p is m for m in megasteps) for p in mixed)
    assert tight == roomy


def test_unified_one_compile_across_heterogeneous_prompts(
        small, recompile_sentinel):
    """Acceptance: the unified step compiles exactly once no matter how
    prompt lengths, chunk offsets and decode compositions vary — and a
    second heterogeneous wave through the warm engine compiles nothing."""
    cfg, params = small
    prompts = _prompts(7, seed=61, lo=4, hi=120)
    eng = _engine(cfg, params, max_num_batched_tokens=32,
                  max_blocks_per_seq=16, num_blocks=128)
    _drain(eng, prompts, [SamplingParams(max_tokens=4)] * 7)
    assert eng.runner.unified_compiles() == 1
    assert eng.runner.prefill_compiles() == 1
    recompile_sentinel.arm(eng.runner, "unified")
    _drain(eng, _prompts(5, seed=62, lo=4, hi=90),
           [SamplingParams(max_tokens=4)] * 5)
    recompile_sentinel.check()


def test_unified_single_dispatch_in_steady_mixed_state(
        small, recompile_sentinel):
    """One long prompt chunking over a warm decoding batch: every engine
    iteration in the steady mixed window is exactly ONE device dispatch
    (the two-call path pays a decode + a chunk + a sample dispatch) —
    and compiles nothing new."""
    cfg, params = small
    eng = _engine(cfg, params, max_num_batched_tokens=12, max_slots=2,
                  num_blocks=128, max_blocks_per_seq=16)
    eng.add(_prompts(1, seed=41)[0], SamplingParams(max_tokens=40))
    for _ in range(3):                     # short prompt is decoding now
        eng.step()
    recompile_sentinel.arm(eng.runner, "steady-mixed")
    rid = eng.add(_prompts(1, seed=42, lo=60, hi=61)[0],
                  SamplingParams(max_tokens=4))
    eng.reset_dispatch_window()
    while any(r.rid == rid for r in eng.waiting) \
            or any(s.prefilling for s in eng.running.values()):
        eng.step()
    rep = eng.report()
    assert rep["device_dispatches_per_step"] == 1.0
    eng.run_until_done()


def _compiled(eng):
    return {s.args["executable"] for s in eng.tracer.spans()
            if s.name == "compile"}


def test_unified_follows_chunked_prefill(small):
    """The unified step serves exactly the chunked engines: a chunked
    engine runs prefill through it (plus the megastep for decode-only
    steps) and never compiles the whole-prompt prefill or its sample; an
    engine without chunked prefill builds no unified executable and
    serves through the whole-prompt wave and the megastep."""
    cfg, params = small
    prompts = _prompts(2, seed=71)
    sps = [SamplingParams(max_tokens=4)] * 2
    eng = _engine(cfg, params)
    a, _ = _drain(eng, prompts, sps)
    assert eng.chunked and eng.async_step
    assert _compiled(eng) <= {"unified_step", "unified_step_chained",
                              "megastep", "copy_cow"}
    assert eng.runner.unified_compiles() == 1
    eng = _engine(cfg, params, enable_chunked_prefill=False,
                  prefill_bucket=16)
    b, _ = _drain(eng, prompts, sps)
    assert not eng.chunked and not eng.async_step
    assert eng.runner._unified is None and eng.runner._unified_chained is None
    assert _compiled(eng) == {"prefill", "sample", "megastep"}
    assert np.isnan(eng.runner.unified_compiles())
    assert a == b


# the step's named regions, by executable: every region of the decode
# rows and of the chunk, and the feed gather of the chained step.  The
# kernels read the stacked pools in place, so no executable has a
# pool_slice region (each layer's pool slice) any more.
_REGIONS = ("embed", "attention", "kv_write", "mlp", "lm_head")


@pytest.mark.parametrize("executable,scopes", [
    ("unified", _REGIONS + ("sample",)),
    ("unified_chained", _REGIONS + ("sample", "chain_gather")),
    ("megastep", _REGIONS + ("sample",)),
    ("chunk", _REGIONS)])
def test_step_regions_are_named_scopes(small, executable, scopes):
    cfg, params = small
    slots, mb, w = 3, 8, 16
    state = T.make_decode_state(cfg, slots, 64, mb, kv_cache_dtype="int8")
    n = slots + 1
    sp = {"keys": jnp.zeros((n, 2), jnp.uint32),
          "counts": jnp.zeros((n,), jnp.int32),
          "temps": jnp.zeros((n,), jnp.float32),
          "top_ks": jnp.zeros((n,), jnp.int32),
          "top_ps": jnp.ones((n,), jnp.float32)}
    toks = jnp.zeros((slots,), jnp.int32)
    active = jnp.ones((slots,), bool)
    chunk = (jnp.zeros((1, w), jnp.int32), jnp.zeros((1, mb), jnp.int32),
             jnp.int32(0), jnp.int32(5))
    fns = {
        "unified": (lambda: T.unified_step(
            cfg, params, state, toks, sp, active, *chunk)),
        "unified_chained": (lambda: T.unified_step_chained(
            cfg, params, state, jnp.zeros((n,), jnp.int32), toks,
            active, toks, sp, active, *chunk)),
        "megastep": (lambda: T.decode_megastep(
            cfg, params, state, toks, {k: v[:slots] for k, v in sp.items()},
            active, jnp.int32(2), max_horizon=2)),
        "chunk": (lambda: T.prefill_chunk(
            cfg, params, cache_from_state(state), *chunk,
            rt={"use_pallas": True, "interpret": True})),
    }
    text = jax.jit(fns[executable]).lower().as_text(debug_info=True)
    found = {part for loc in re.findall(r'loc\("([^"]*)"', text)
             for part in loc.split("/")}
    missing = [s for s in scopes if s not in found]
    assert not missing, f"{executable}: no ops in {missing}"
    assert "pool_slice" not in found, executable
