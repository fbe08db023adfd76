"""Token-budget chunked prefill vs the stop-the-world whole-prompt
oracle: greedy token-exactness (bf16 AND int8 KV pools), multi-token
horizon vs per-token bitwise parity within chunked mode, preemption
mid-prefill, the single-compile guarantee of the fixed-shape chunk
executable, and the direct transformer-level chunk-vs-prefill check."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_reduced
from repro.core.kv_quant import cache_from_state, cache_to_state
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
    kw.setdefault("prefill_bucket", 16)
    return ServingEngine(cfg, params, **kw)


def _drain(eng, prompts, sps):
    for p, sp in zip(prompts, sps):
        eng.add(p, sp)
    eng.run_until_done()
    return {r.rid: list(r.output) for r in eng.finished}, \
        {r.rid: r.finish_reason for r in eng.finished}


# --------------------------------------------------- transformer-level parity

def test_prefill_chunk_executable_matches_whole_prompt():
    """The fixed-shape chunk executable reproduces T.prefill: identical
    pool contents (bf16 exactly — the chunk overlays its raw K/V like
    the whole-prompt path writes them) and matching last-token argmax,
    from ONE compile across chunk offsets and live lengths."""
    cfg = get_reduced("qwen1.5-0.5b", num_layers=2)
    params = T.init_params(cfg, KEY)
    mb, nb = 8, 32
    S, W = 23, 8
    toks = np.asarray(jax.random.randint(jax.random.fold_in(KEY, 1),
                                         (1, S), 1, cfg.vocab_size))
    bt = np.zeros((1, mb), np.int32)
    bt[0, :4] = [3, 5, 1, 7]
    st = T.make_decode_state(cfg, 1, nb, mb, dtype=jnp.float32)
    st["block_table"] = jnp.asarray(bt)
    pad = np.zeros((1, 32), np.int32)
    pad[0, :S] = toks[0]
    l_ref, s_ref = T.prefill(cfg, params, dict(st),
                             {"tokens": jnp.asarray(pad),
                              "ctx_lens": jnp.asarray([S])})
    fn = jax.jit(lambda p, c, t, b, o, tl: T.prefill_chunk(
        cfg, p, c, t, b, o, tl))
    cache = cache_from_state(st)
    for off in range(0, S, W):
        n = min(W, S - off)
        tc = np.zeros((1, W), np.int32)
        tc[0, :n] = toks[0, off:off + n]
        logits, cache = fn(params, cache, jnp.asarray(tc), jnp.asarray(bt),
                           jnp.int32(off), jnp.int32(off + n))
    s_chk = cache_to_state(cache)
    np.testing.assert_array_equal(np.asarray(s_ref["k_pool"]),
                                  np.asarray(s_chk["k_pool"]))
    np.testing.assert_array_equal(np.asarray(s_ref["v_pool"]),
                                  np.asarray(s_chk["v_pool"]))
    np.testing.assert_allclose(np.asarray(l_ref), np.asarray(logits),
                               atol=2e-2)
    assert int(jnp.argmax(l_ref[0])) == int(jnp.argmax(logits[0]))
    assert fn._cache_size() == 1          # every chunk hit one executable


def test_prefill_chunk_rejects_non_full_attention_archs():
    assert not T.supports_chunked_prefill(get_reduced("falcon-mamba-7b"))
    assert not T.supports_chunked_prefill(get_reduced("h2o-danube-3-4b"))
    assert not T.supports_chunked_prefill(get_reduced("recurrentgemma-2b"))
    # encoders are full-attention-homogeneous but bidirectional: no
    # causal chunk decomposition, no KV cache — must not claim support
    assert not T.supports_chunked_prefill(get_reduced("hubert-xlarge"))
    assert T.supports_chunked_prefill(get_reduced("qwen2-moe-a2.7b"))


# ------------------------------------------------------- engine-level parity

@pytest.mark.parametrize("kv_cache_dtype", ["bf16", "int8"])
def test_chunked_serving_token_exact_vs_oracle(small, kv_cache_dtype):
    """Acceptance: multi-chunk greedy serving (budget far below the
    prompt lengths) is token-exact against the whole-prompt oracle, for
    the dense AND the int8-quantized KV pool."""
    cfg, params = small
    prompts = _prompts(5, seed=21, lo=24, hi=60)      # several chunks each
    sps = [SamplingParams(max_tokens=10)] * 5
    o_ref, f_ref = _drain(
        _engine(cfg, params, enable_chunked_prefill=False,
                kv_cache_dtype=kv_cache_dtype), prompts, sps)
    eng = _engine(cfg, params, max_num_batched_tokens=16,
                  kv_cache_dtype=kv_cache_dtype)
    o_chk, f_chk = _drain(eng, prompts, sps)
    assert eng.metrics["prefill_chunks"] > len(prompts)   # really chunked
    assert o_ref == o_chk and f_ref == f_chk
    assert eng.runner.prefill_compiles() == 1


def test_chunked_horizon_matches_per_token_bitwise(small):
    """Within chunked mode a multi-token megastep horizon and one decode
    dispatch per token (``max_horizon=1``) stay bitwise-identical across
    mixed sampling modes."""
    cfg, params = small
    prompts = _prompts(4, seed=31, lo=20, hi=40)
    sps = [SamplingParams(max_tokens=8),
           SamplingParams(temperature=0.9, max_tokens=8),
           SamplingParams(temperature=0.8, top_k=5, max_tokens=8),
           SamplingParams(temperature=0.7, top_p=0.9, seed=7, max_tokens=8)]
    o_one, _ = _drain(_engine(cfg, params, max_horizon=1,
                              max_num_batched_tokens=16), prompts, sps)
    o_fus, _ = _drain(_engine(cfg, params, max_num_batched_tokens=16),
                      prompts, sps)
    assert o_one == o_fus


def test_runner_prefill_chunk_matches_unified_chunk(small):
    """``ModelRunner.prefill_chunk``, the standalone chunk executable the
    engine no longer dispatches, writes the same pool as a chunk-only
    unified dispatch of the same chunk, and its logits' argmax is that
    dispatch's greedy first token."""
    cfg, params = small
    prompt = _prompts(1, seed=81, lo=12, hi=13)[0]
    engs, chunks = [], []
    for _ in range(2):
        eng = _engine(cfg, params, max_num_batched_tokens=16)
        eng.add(prompt, SamplingParams(max_tokens=1))
        (c,) = eng.scheduler.plan_step(16).prefill
        engs.append(eng)
        chunks.append(c)
    (a, b), (ca, cb) = engs, chunks
    logits = a.runner.prefill_chunk(ca.seq, ca.start, ca.length)
    n = b.max_slots
    out = b.runner.unified_step(
        np.zeros(n, np.int32), b._sampling_rows([None] * n + [cb.seq.req]),
        np.zeros(n, bool), cb.seq.req.prompt, cb.seq.block_ids, cb.start,
        cb.length)
    assert int(np.asarray(out)[n]) == int(jnp.argmax(logits[0]))
    for k in ("k_pool", "v_pool"):
        np.testing.assert_array_equal(np.asarray(a.runner.state[k]),
                                      np.asarray(b.runner.state[k]))


def test_chunked_interleaves_decode_with_long_prefill(
        small, recompile_sentinel):
    """A long prompt arriving over a decoding batch no longer stalls it:
    decode tokens keep flowing between its chunks (the ITL bound) — and
    the warm executables compile nothing new while it chunks."""
    cfg, params = small
    eng = _engine(cfg, params, max_num_batched_tokens=12, max_slots=2,
                  num_blocks=128, max_blocks_per_seq=16)
    eng.add(_prompts(1, seed=41)[0], SamplingParams(max_tokens=40))
    for _ in range(3):                     # short prompt is decoding now
        eng.step()
    recompile_sentinel.arm(eng.runner, "interleaved")
    long_prompt = _prompts(1, seed=42, lo=60, hi=61)[0]
    rid = eng.add(long_prompt, SamplingParams(max_tokens=4))
    decoded_during_prefill = 0
    while any(s.prefilling for s in eng.running.values()) or \
            any(r.rid == rid for r in eng.waiting):
        before = eng.metrics["gen_tokens"]
        eng.step()
        if any(s.prefilling for s in eng.running.values()):
            decoded_during_prefill += eng.metrics["gen_tokens"] - before
    assert decoded_during_prefill > 0      # decode never stopped
    eng.run_until_done()
    assert {r.finish_reason for r in eng.finished} <= {"length", "stop"}


@pytest.mark.parametrize("kv_cache_dtype", ["bf16", "int8"])
def test_preemption_mid_prefill_parity(small, kv_cache_dtype):
    """A block-starved run that preempts a sequence *mid-prefill*
    (partially-computed KV freed, chunk walk restarted from zero on
    re-admission) still matches the roomy run token-for-token."""
    cfg, params = small
    rng = np.random.default_rng(51)
    # two decoders plus one long prompt whose chunk walk is still in
    # flight when decode growth exhausts the 9-block pool
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


def test_one_compile_across_heterogeneous_prompts(
        small, recompile_sentinel):
    """Acceptance: the chunk-prefill executable compiles exactly once no
    matter how prompt lengths and wave compositions vary, while the
    oracle's padded wave path recompiles per (wave, bucket) shape."""
    cfg, params = small
    prompts = _prompts(7, seed=61, lo=4, hi=120)
    eng = _engine(cfg, params, max_num_batched_tokens=32,
                  max_blocks_per_seq=16, num_blocks=128)
    _drain(eng, prompts, [SamplingParams(max_tokens=4)] * 7)
    assert eng.runner.prefill_compiles() == 1
    recompile_sentinel.arm(eng.runner, "chunked")
    _drain(eng, _prompts(5, seed=62, lo=4, hi=90),
           [SamplingParams(max_tokens=4)] * 5)
    recompile_sentinel.check()
    oracle = _engine(cfg, params, enable_chunked_prefill=False,
                     max_blocks_per_seq=16, num_blocks=128)
    _drain(oracle, prompts, [SamplingParams(max_tokens=4)] * 7)
    assert oracle.runner.prefill_compiles() > 1


def test_budget_respected_and_reported(small):
    cfg, params = small
    eng = _engine(cfg, params, max_num_batched_tokens=16)
    _drain(eng, _prompts(4, seed=71, lo=20, hi=50),
           [SamplingParams(max_tokens=6)] * 4)
    rep = eng.report()
    assert 0 < rep["budget_utilization"] <= 1.0
    assert rep["prefill_chunks"] == eng.metrics["prefill_chunks"] > 4
    assert np.isfinite(rep["itl_p50_ms"]) and np.isfinite(rep["itl_p99_ms"])
    assert rep["itl_p50_ms"] <= rep["itl_p99_ms"]


def test_engine_rejects_budget_not_exceeding_slots(small):
    cfg, params = small
    with pytest.raises(ValueError, match="max_num_batched_tokens"):
        _engine(cfg, params, max_slots=8, max_num_batched_tokens=8)


def test_non_full_attention_arch_falls_back_to_oracle():
    """SSM archs serve through the whole-prompt path even when chunked
    prefill is requested — no crash, same outputs as oracle mode."""
    cfg = get_reduced("falcon-mamba-7b", num_layers=2)
    params = T.init_params(cfg, KEY)
    prompts = _prompts(2, seed=81)
    a, _ = _drain(_engine(cfg, params, enable_chunked_prefill=True),
                  prompts, [SamplingParams(max_tokens=4)] * 2)
    b, _ = _drain(_engine(cfg, params, enable_chunked_prefill=False),
                  prompts, [SamplingParams(max_tokens=4)] * 2)
    assert a == b
