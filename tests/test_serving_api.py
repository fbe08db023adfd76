"""vLLM-style serving API: per-request SamplingParams at a multi-token
horizon and one token per dispatch (bitwise), stop-token semantics,
streaming, the LLM facade and the deprecation shim."""
import jax
import numpy as np
import pytest

from repro.configs.registry import get_reduced
from repro.models import transformer as T
from repro.serving import (LLM, Request, RequestOutput, SamplingParams,
                           ServingEngine)

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


# ---------------------------------------------------- heterogeneous parity

def test_heterogeneous_params_horizon_matches_per_token(small):
    """Acceptance: one batch mixing greedy / temperature / top-k / top-p /
    seeded requests decodes bitwise-identically at a multi-token megastep
    horizon and at one dispatch per token (``max_horizon=1``)."""
    cfg, params = small
    prompts = _prompts(6, seed=5)
    sps = [SamplingParams(max_tokens=10),
           SamplingParams(temperature=0.9, max_tokens=10),
           SamplingParams(temperature=0.8, top_k=5, max_tokens=10),
           SamplingParams(temperature=1.1, top_p=0.8, max_tokens=10),
           SamplingParams(temperature=0.7, top_k=12, top_p=0.95, seed=42,
                          max_tokens=10),
           SamplingParams(max_tokens=10)]
    o_one, _ = _drain(_engine(cfg, params, max_horizon=1), prompts, sps)
    o_fus, fr = _drain(_engine(cfg, params), prompts, sps)
    assert len(o_one) == len(o_fus) == 6
    assert o_one == o_fus
    assert all(r == "length" for r in fr.values())


def test_seeded_request_reproduces_across_batch_compositions(small):
    """A request's sampling stream is keyed per slot by (seed, position),
    so its tokens do not depend on who shares the batch."""
    cfg, params = small
    prompts = _prompts(3, seed=9)
    sp = SamplingParams(temperature=0.8, top_k=20, seed=123, max_tokens=8)
    fillers = [SamplingParams(temperature=1.3, max_tokens=8)] * 2
    batched, _ = _drain(_engine(cfg, params), prompts, [sp] + fillers)
    solo, _ = _drain(_engine(cfg, params, max_slots=1), prompts[:1], [sp])
    assert solo[0] == batched[0]


# ---------------------------------------------------- stop-token semantics

def test_stop_token_finishes_and_releases_blocks_immediately(small):
    """A stop token ends the request with finish_reason='stop' (tokens past
    it are discarded) and its KV blocks return to the pool in the same
    engine step, while other sequences keep running."""
    cfg, params = small
    probe, _ = _drain(_engine(cfg, params), _prompts(1, seed=3),
                      [SamplingParams(max_tokens=12)])
    greedy = probe[0]
    stop_tok = greedy[4]
    idx = greedy.index(stop_tok)            # first occurrence wins

    eng = _engine(cfg, params, max_slots=2, max_horizon=4)
    eng.add(_prompts(1, seed=3)[0], SamplingParams(max_tokens=12,
                                                   stop=[stop_tok]))
    other_prompt = list(np.random.default_rng(8).integers(1, 200, 10))
    eng.add(other_prompt, SamplingParams(max_tokens=40))
    total = eng.alloc.num_blocks
    for _ in range(100):
        eng.step()
        if eng.finished and eng.finished[0].finish_reason == "stop":
            break
    assert eng.finished[0].finish_reason == "stop"
    assert list(eng.finished[0].output) == greedy[:idx + 1]
    # the stopped request's blocks are free again; only the still-running
    # sequence holds pool blocks
    assert len(eng.running) == 1
    (live,) = eng.running.values()
    assert eng.alloc.num_free == total - len(live.block_ids)
    eng.run_until_done()
    assert eng.finished[-1].finish_reason in ("length", "capacity")


def test_stop_midhorizon_parity_horizon_vs_per_token(small):
    cfg, params = small
    probe, _ = _drain(_engine(cfg, params), _prompts(2, seed=4),
                      [SamplingParams(max_tokens=12)] * 2)
    stop = [probe[0][3], probe[1][5]]
    sps = [SamplingParams(max_tokens=12, stop=[stop[0]]),
           SamplingParams(max_tokens=12, stop=[stop[1]])]
    o_one, f_one = _drain(_engine(cfg, params, max_horizon=1),
                          _prompts(2, seed=4), sps)
    o_fus, f_fus = _drain(_engine(cfg, params, max_horizon=8),
                          _prompts(2, seed=4), sps)
    assert o_one == o_fus and f_one == f_fus
    assert set(f_fus.values()) == {"stop"}


# ---------------------------------------------------- streaming intake

def test_stream_yields_first_output_before_batch_finishes(small):
    cfg, params = small
    eng = _engine(cfg, params, max_slots=2, max_horizon=4)
    for p in _prompts(4, seed=6):
        eng.add(p, SamplingParams(max_tokens=16))
    first_event_had_work_left = None
    events = []
    for out in eng.stream():
        if first_event_had_work_left is None:
            first_event_had_work_left = eng.scheduler.has_work()
        events.append(out)
    assert first_event_had_work_left is True
    assert all(isinstance(e, RequestOutput) for e in events)
    fin = [e for e in events if e.finished]
    assert len(fin) == 4
    # deltas reassemble exactly into the cumulative outputs
    for rid in range(4):
        deltas = sum((e.new_token_ids for e in events
                      if e.request_id == rid), [])
        assert deltas == next(e.token_ids for e in reversed(events)
                              if e.request_id == rid)


def test_add_request_while_streaming(small):
    cfg, params = small
    eng = _engine(cfg, params, max_slots=2)
    prompts = _prompts(5, seed=7)
    eng.add(prompts[0], SamplingParams(max_tokens=8))
    pending = prompts[1:]
    for _out in eng.stream():
        if pending:                          # continuous intake mid-stream
            eng.add(pending.pop(0), SamplingParams(max_tokens=8))
    assert len(eng.finished) == 5
    assert all(len(r.output) == 8 for r in eng.finished)


def test_filter_keeps_all_tokens_when_top_p_disabled():
    """A top_p=1.0 (disabled) row must keep its whole top-k set even when
    the filter runs because another slot requested filtering — f32 cumsum
    rounds tail prior-mass to exactly 1.0 on peaked rows, and truncating
    there would make the row's sample depend on batch composition."""
    import jax.numpy as jnp
    from repro.core.sampling import _filter_top_k_top_p
    peaked = np.zeros((1, 64), np.float32)
    peaked[0, 7] = 50.0                     # softmax mass ~1.0 at token 7
    out = _filter_top_k_top_p(jnp.asarray(peaked / 0.05),
                              jnp.asarray([0], jnp.int32),
                              jnp.asarray([1.0], jnp.float32))
    assert bool(jnp.isfinite(out).all())    # nothing masked
    # a genuinely filtering row still truncates
    out2 = _filter_top_k_top_p(jnp.asarray(peaked / 0.05),
                               jnp.asarray([0], jnp.int32),
                               jnp.asarray([0.9], jnp.float32))
    assert not bool(jnp.isfinite(out2).all())


def test_detokenizer_fills_text_incrementally(small):
    cfg, params = small
    det = lambda toks: "".join(chr(65 + t % 26) for t in toks)  # noqa: E731
    eng = _engine(cfg, params, detokenizer=det, max_slots=2, max_horizon=4)
    eng.add(_prompts(1, seed=15)[0], SamplingParams(max_tokens=10))
    events = list(eng.stream())
    final = [e for e in events if e.finished][0]
    assert final.text == det(final.token_ids)   # delta-accumulated == full
    assert "".join(e.new_text for e in events) == final.text


# ---------------------------------------------------- finish reasons

def test_capacity_finish_reason(small):
    cfg, params = small
    eng = _engine(cfg, params, max_slots=2, num_blocks=8,
                  max_blocks_per_seq=2, prefill_bucket=32)
    eng.add(list(range(1, 18)), SamplingParams(max_tokens=48))
    eng.run_until_done()
    assert eng.finished[0].finish_reason == "capacity"
    assert 0 < len(eng.finished[0].output) < 48


# ---------------------------------------------------- deprecation shim

def test_legacy_request_shim_drains_and_matches_new_api(small):
    cfg, params = small
    prompts = _prompts(4, seed=11)
    eng_new = _engine(cfg, params)
    new_out, _ = _drain(eng_new, prompts,
                        [SamplingParams(max_tokens=6)] * 4)
    eng_old = _engine(cfg, params)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    with pytest.warns(DeprecationWarning):
        for r in reqs:
            eng_old.add_request(r)
    eng_old.run_until_done()
    assert len(eng_old.finished) == 4
    # the shim shares the output list with the caller's Request objects
    assert {r.rid: r.output for r in reqs} == new_out
    # ... and mirrors the timestamps the old engine used to set
    for r in reqs:
        assert r.first_token_t is not None and r.done_t is not None
        assert r.done_t - r.arrival >= 0


# ---------------------------------------------------- LLM facade

def test_llm_load_generate_rtn_and_stop(small):
    llm = LLM.load("qwen1.5-0.5b", quant="rtn-int4", reduced=True,
                   overrides=dict(num_layers=2), max_slots=3,
                   num_blocks=64, max_blocks_per_seq=8, prefill_bucket=16)
    prompts = _prompts(3, seed=2)
    [probe] = llm.generate([prompts[0]], SamplingParams(max_tokens=10))
    assert probe.finished and probe.finish_reason == "length"
    stop_tok = probe.token_ids[2]
    outs = llm.generate(prompts,
                        [SamplingParams(max_tokens=10, stop=[stop_tok]),
                         SamplingParams(max_tokens=10, top_k=40,
                                        temperature=0.9),
                         SamplingParams(max_tokens=10)])
    assert [o.request_id for o in outs] == sorted(o.request_id for o in outs)
    assert outs[0].finish_reason == "stop"
    assert outs[0].token_ids == probe.token_ids[
        :probe.token_ids.index(stop_tok) + 1]
    assert all(o.finished for o in outs)


def test_llm_load_gptq_int4_end_to_end():
    llm = LLM.load("qwen2-1.5b", quant="gptq-int4", reduced=True,
                   overrides=dict(num_layers=2), max_slots=2,
                   num_blocks=64, max_blocks_per_seq=8, prefill_bucket=16)
    outs = llm.generate(_prompts(2, seed=1),
                        SamplingParams(top_k=40, max_tokens=6))
    assert all(o.finished and len(o.token_ids) == 6 for o in outs)


def test_llm_load_checkpoint_restores_params(small, tmp_path):
    from repro.checkpoint.checkpointer import Checkpointer
    cfg, params = small
    Checkpointer(str(tmp_path)).save(3, {"params": params})
    llm = LLM.load("qwen1.5-0.5b", checkpoint=str(tmp_path), reduced=True,
                   overrides=dict(num_layers=2), max_slots=2,
                   num_blocks=64, max_blocks_per_seq=8, prefill_bucket=16)
    prompts = _prompts(2, seed=13)
    outs = llm.generate(prompts, SamplingParams(max_tokens=6))
    ref, _ = _drain(_engine(cfg, params, max_slots=2), prompts,
                    [SamplingParams(max_tokens=6)] * 2)
    assert {o.request_id: o.token_ids for o in outs} == ref


def test_llm_load_rejects_unknown_quant():
    with pytest.raises(ValueError, match="quant"):
        LLM.load("qwen1.5-0.5b", quant="int3", reduced=True)


def test_llm_load_gptq_rejects_non_dense():
    with pytest.raises(ValueError, match="rtn-int4"):
        LLM.load("falcon-mamba-7b", quant="gptq-int4", reduced=True)
