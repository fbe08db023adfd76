"""Unified single-dispatch serving step vs the two-call oracle
(``enable_unified_step=False``): greedy token-exactness on both KV pool
formats, bitwise-identical fused sampling, preemption mid-prefill, the
single-compile guarantee, and the dispatch-count accounting."""
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


@pytest.mark.parametrize("kv_cache_dtype", ["bf16", "int8"])
def test_unified_token_exact_vs_two_call(small, kv_cache_dtype):
    """Acceptance: multi-chunk greedy serving through the unified
    single-dispatch step is token-exact against the two-call oracle on
    the dense AND int8 pools, from exactly one unified-step compile."""
    cfg, params = small
    prompts = _prompts(5, seed=21, lo=24, hi=60)
    sps = [SamplingParams(max_tokens=10)] * 5
    o_ref, f_ref = _drain(
        _engine(cfg, params, enable_unified_step=False,
                kv_cache_dtype=kv_cache_dtype), prompts, sps)
    eng = _engine(cfg, params, kv_cache_dtype=kv_cache_dtype)
    o_chk, f_chk = _drain(eng, prompts, sps)
    assert eng.metrics["prefill_chunks"] > len(prompts)   # really chunked
    assert o_ref == o_chk and f_ref == f_chk
    assert eng.runner.unified_compiles() == 1
    assert eng.runner.prefill_compiles() == 1


def test_unified_sampling_bitwise_vs_two_call(small):
    """Fused sampling inside the unified dispatch (decode rows + the
    chunk's first token, one sample kernel over max_slots + 1 rows) is
    bitwise-identical to the two-call path's megastep + batched-sample
    pair across mixed sampling modes, including seeded requests."""
    cfg, params = small
    prompts = _prompts(4, seed=31, lo=20, hi=40)
    sps = [SamplingParams(max_tokens=8),
           SamplingParams(temperature=0.9, max_tokens=8),
           SamplingParams(temperature=0.8, top_k=5, max_tokens=8),
           SamplingParams(temperature=0.7, top_p=0.9, seed=7, max_tokens=8)]
    o_ref, _ = _drain(_engine(cfg, params, enable_unified_step=False),
                      prompts, sps)
    o_chk, _ = _drain(_engine(cfg, params), prompts, sps)
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


def test_unified_requires_chunked_and_fused(small):
    """enable_unified_step quietly degrades to the two-call paths when
    its prerequisites (chunked prefill + fused decode) are off."""
    cfg, params = small
    eng = _engine(cfg, params, enable_chunked_prefill=False)
    assert not eng.unified
    eng = _engine(cfg, params, use_fused=False)
    assert not eng.unified
    prompts = _prompts(2, seed=71)
    a, _ = _drain(eng, prompts, [SamplingParams(max_tokens=4)] * 2)
    b, _ = _drain(_engine(cfg, params, use_fused=False,
                          enable_unified_step=False),
                  prompts, [SamplingParams(max_tokens=4)] * 2)
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
