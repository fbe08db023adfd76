"""Fused multi-token decode horizon vs one dispatch per token
(``max_horizon=1``), CoW device copy, preemption-requeue determinism,
gather_kv partial-tail."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_reduced
from repro.core.paged_cache import (BlockAllocator, OutOfBlocksError,
                                    copy_blocks, gather_kv, make_kv_pool,
                                    write_prefill_kv)
from repro.models import transformer as T
from repro.serving.engine import Request, ServingEngine

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small():
    cfg = get_reduced("qwen1.5-0.5b", num_layers=2)
    params = T.init_params(cfg, KEY)
    return cfg, params


def _run(cfg, params, prompts, *, temperature=0.0, max_new_tokens=10,
         **kw):
    eng = ServingEngine(cfg, params, **kw)
    for i, p in enumerate(prompts):
        eng.add_request(Request(rid=i, prompt=p, temperature=temperature,
                                max_new_tokens=max_new_tokens))
    rep = eng.run_until_done()
    return {r.rid: list(r.output) for r in eng.finished}, rep, eng


def _prompts(n, seed=0, lo=4, hi=20):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, 200, int(rng.integers(lo, hi))))
            for _ in range(n)]


# ------------------------------------------------- horizon == per-token

def test_horizon_matches_per_token_greedy(small):
    """Acceptance: multi-token-horizon outputs bitwise-identical (greedy)
    to one dispatch per token (``max_horizon=1``) on the reduced
    qwen1.5-0.5b config."""
    cfg, params = small
    kw = dict(max_slots=3, num_blocks=64, max_blocks_per_seq=8,
              prefill_bucket=16)
    o_one, rep1, _ = _run(cfg, params, _prompts(6), max_horizon=1, **kw)
    o_fus, rep, _ = _run(cfg, params, _prompts(6), **kw)
    assert len(o_one) == len(o_fus) == 6
    assert o_one == o_fus
    # the horizon actually fused: fewer dispatches than decode steps,
    # while max_horizon=1 dispatches once per step
    assert rep["decode_dispatches"] < rep["decode_steps"]
    assert rep1["decode_dispatches"] == rep1["decode_steps"]


def test_horizon_matches_per_token_temperature(small):
    """The megastep splits the PRNG key once per step, so even
    temperature sampling matches one dispatch per token, token for
    token."""
    cfg, params = small
    kw = dict(max_slots=2, num_blocks=64, max_blocks_per_seq=8,
              prefill_bucket=16)
    o_one, _, _ = _run(cfg, params, _prompts(3, seed=7), max_horizon=1,
                       temperature=0.9, **kw)
    o_fus, _, _ = _run(cfg, params, _prompts(3, seed=7),
                       temperature=0.9, **kw)
    assert o_one == o_fus


def test_fused_single_sync_per_horizon(small):
    """Acceptance: steady-state decode performs at most one host<->device
    round trip per dispatched horizon."""
    cfg, params = small
    _, rep, _ = _run(cfg, params, _prompts(3, seed=3),
                     max_slots=4, num_blocks=64, max_blocks_per_seq=8,
                     prefill_bucket=16)
    # all admitted in one wave: total syncs = 1 prefill + 1 per dispatch
    assert rep["host_syncs"] == rep["decode_dispatches"] + 1
    assert rep["syncs_per_decode_step"] < 1.0


def test_fused_greedy_matches_direct_forward(small):
    """Fused engine greedy decode == teacher-forced model argmax."""
    cfg, params = small
    prompt = [5, 9, 13, 2, 7, 11]
    outs, _, _ = _run(cfg, params, [prompt],
                      max_new_tokens=6, max_slots=2, num_blocks=64,
                      max_blocks_per_seq=8, prefill_bucket=8)
    toks = list(prompt)
    for _ in range(6):
        logits = T.forward(cfg, params, {"tokens": jnp.asarray([toks])})
        toks.append(int(jnp.argmax(logits[0, -1])))
    assert outs[0] == toks[len(prompt):]


# ------------------------------------------------------------ CoW device copy

def test_fork_append_triggers_cow_with_device_copy():
    """Forked sequence sharing a partial tail: the next append must CoW the
    tail and the device block-copy must preserve its contents."""
    bs = 4
    a = BlockAllocator(16, bs)
    ids, _ = a.allocate_prompt(list(range(6)))      # 1 full + 1 partial
    fork = a.fork_sequence(ids)
    assert a._blocks[ids[-1]].ref == 2
    # device pool with recognizable contents in the shared tail
    kp, _ = make_kv_pool(2, 16, bs, 1, 8, dtype=jnp.float32)
    bt = jnp.asarray([ids + [0] * (4 - len(ids))], jnp.int32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 1, 8))
    kp = write_prefill_kv(kp, 0, k, bt, jnp.asarray([6]))
    kp = write_prefill_kv(kp, 1, k, bt, jnp.asarray([6]))
    # fork appends token at position 6 (partial shared tail) -> CoW
    grown, cow = a.grow(fork, 6, 1)
    src, dst = cow
    assert src == ids[-1] and dst == grown[-1] != ids[-1]
    assert a.stats["cow"] == 1
    assert a._blocks[ids[-1]].ref == 1              # original keeps its tail
    kp = copy_blocks(kp, jnp.asarray([src], jnp.int32),
                     jnp.asarray([dst], jnp.int32))
    # every layer's tail contents survived the copy; original untouched
    np.testing.assert_allclose(np.asarray(kp[:, dst, 0, :2]),
                               np.asarray(kp[:, src, 0, :2]))
    np.testing.assert_allclose(np.asarray(kp[0, src, 0, :2]),
                               np.asarray(k[0, 4:6, 0], np.float32))


def test_cow_with_horizon_growth_targets_replacement_block():
    """CoW + multi-token growth in one grow() call: the device-copy dst is
    the *replacement* tail, not the last freshly appended growth block."""
    bs = 4
    a = BlockAllocator(16, bs)
    ids, _ = a.allocate_prompt(list(range(6)))      # 1 full + 1 partial
    fork = a.fork_sequence(ids)
    grown, cow = a.grow(fork, 6, 6)                 # CoW + spills 1 block
    src, dst = cow
    assert src == ids[-1]
    assert dst == grown[1] != grown[-1]             # replacement, not growth
    assert len(grown) == 3
    assert a._blocks[grown[-1]].ref == 1


def test_blocks_needed_accounts_for_cow_and_horizon():
    bs = 4
    a = BlockAllocator(16, bs)
    ids, _ = a.allocate_prompt(list(range(6)))      # capacity 8, len 6
    assert a.blocks_needed(ids, 6, 2) == 0          # fits the partial tail
    assert a.blocks_needed(ids, 6, 3) == 1          # spills into one block
    assert a.blocks_needed(ids, 6, 7) == 2
    fork = a.fork_sequence(ids)
    assert a.blocks_needed(fork, 6, 1) == 1         # CoW replacement block
    grown, cow = a.grow(fork, 6, 7)                 # CoW + 2 growth blocks
    assert cow[0] == ids[-1] and len(grown) == 4


def test_grow_is_atomic_on_exhaustion():
    """A grow that cannot fit must not leak blocks or touch refcounts."""
    bs = 4
    a = BlockAllocator(4, bs)
    ids, _ = a.allocate_prompt(list(range(6)))      # 2 blocks, 2 free
    free_before = a.num_free
    with pytest.raises(OutOfBlocksError):
        a.grow(ids, 6, 16)                          # needs 4 blocks > 2 free
    assert a.num_free == free_before                # nothing leaked
    fork = a.fork_sequence(ids)
    a._free = []                                    # exhaust the pool
    with pytest.raises(OutOfBlocksError):
        a.grow(fork, 6, 1)                          # CoW needs 1 block
    assert a._blocks[ids[-1]].ref == 2              # tail ref untouched


# ------------------------------------------------------ preemption determinism

def test_preemption_requeue_identical_outputs(small):
    """Recompute-style preemption must not change greedy outputs: a run
    forced through preemption matches an unconstrained run request-for-
    request."""
    cfg, params = small
    prompts = _prompts(4, seed=11, lo=17, hi=30)
    roomy, _, _ = _run(cfg, params, prompts,
                       max_new_tokens=32, max_slots=3, num_blocks=256,
                       max_blocks_per_seq=8, prefill_bucket=16)
    tight, rep, eng = _run(cfg, params, prompts,
                           max_new_tokens=32, max_slots=3, num_blocks=9,
                           max_blocks_per_seq=8, prefill_bucket=16)
    assert eng.metrics["preemptions"] > 0, "scenario must exercise preemption"
    assert tight == roomy


def test_preemption_identical_per_token_vs_horizon(small):
    cfg, params = small
    prompts = _prompts(4, seed=11, lo=17, hi=30)
    kw = dict(max_new_tokens=32, max_slots=3, num_blocks=9,
              max_blocks_per_seq=8, prefill_bucket=16)
    o_one, _, eng_1 = _run(cfg, params, prompts, max_horizon=1, **kw)
    o_fus, _, eng_f = _run(cfg, params, prompts, **kw)
    assert eng_1.metrics["preemptions"] > 0
    assert eng_f.metrics["preemptions"] > 0
    assert o_one == o_fus


@pytest.mark.parametrize("max_horizon", [1, 8])
def test_sequence_truncated_at_block_table_capacity(small, max_horizon):
    """A generation that would overflow the mb-wide block table is
    truncated (force-finished), not crashed in _sync_tables."""
    cfg, params = small
    prompt = list(range(1, 18))                     # 17 tokens, cap 2*16=32
    outs, _, eng = _run(cfg, params, [prompt], max_horizon=max_horizon,
                        max_new_tokens=48, max_slots=2, num_blocks=8,
                        max_blocks_per_seq=2, prefill_bucket=32)
    assert len(eng.finished) == 1
    assert 0 < len(outs[0]) < 48                    # truncated at capacity
    # never grew past the table width
    assert all(len(s.block_ids) <= 2 for s in eng.running.values())


def test_overlong_prompt_clamped_at_admission(small):
    """A prompt that would overflow the block table is clamped at admission
    (leaving room to generate) instead of crashing the prefill scatter."""
    cfg, params = small
    prompt = list(range(1, 40))                     # 39 tokens > cap 2*16=32
    outs, _, eng = _run(cfg, params, [prompt],
                        max_new_tokens=4, max_slots=2, num_blocks=8,
                        max_blocks_per_seq=2, prefill_bucket=32)
    assert eng.metrics["truncated_prompts"] == 1
    assert len(eng.finished) == 1 and len(outs[0]) >= 1


# ------------------------------------------------------------ gather_kv tail

def test_gather_kv_partial_tail_not_truncated():
    bs = 4
    kp, _ = make_kv_pool(1, 8, bs, 2, 8, dtype=jnp.float32)
    bt = jnp.asarray([[3, 5, 1]], jnp.int32)
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 10, 2, 8))
    kp = write_prefill_kv(kp, 0, k, bt, jnp.asarray([10]))
    g = gather_kv(kp, 0, bt, 10)                    # 2.5 blocks
    assert g.shape == (1, 10, 2, 8)
    np.testing.assert_allclose(np.asarray(g), np.asarray(k, np.float32))
    # block-multiple path unchanged
    g8 = gather_kv(kp, 0, bt, 8)
    assert g8.shape == (1, 8, 2, 8)
    np.testing.assert_allclose(np.asarray(g8), np.asarray(k[:, :8],
                                                          np.float32))
