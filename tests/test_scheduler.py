"""Host-side Scheduler unit tests: admission, watermark, clamping,
horizon planning, preemption, capacity and the token-budget step planner
(``plan_step``) — no model, no device arrays."""
import random

import pytest

from repro.core.paged_cache import BlockAllocator
from repro.serving.params import SamplingParams
from repro.serving.scheduler import (RequestState, Scheduler,
                                     Sequence, StepPlan)

BS = 4


def _sched(num_blocks=32, max_slots=3, mb=4, **kw):
    alloc = BlockAllocator(num_blocks, BS, watermark_frac=0.0)
    return Scheduler(alloc, max_slots=max_slots, max_blocks_per_seq=mb, **kw)


def _req(rid, n_prompt, max_tokens=8, arrival=None):
    r = RequestState(rid=rid, prompt=list(range(1, n_prompt + 1)),
                     sampling=SamplingParams(max_tokens=max_tokens))
    r.arrival = float(rid + 1 if arrival is None else arrival)
    r.prompt_len0 = n_prompt
    return r


def test_admission_fifo_and_slot_bound():
    s = _sched(max_slots=2)
    for i in range(4):
        s.add(_req(i, 6))
    admitted = s.try_admit()
    assert [q.req.rid for q in admitted] == [0, 1]   # FIFO, 2 slots
    assert len(s.waiting) == 2 and len(s.running) == 2
    assert all(q.seq_len == 6 and q.last_token == 6 for q in admitted)


def test_admission_watermark_blocks():
    # 4 blocks total; each 6-token prompt wants ceil(6/4)+1 = 3 blocks
    alloc = BlockAllocator(4, BS, watermark_frac=0.5)   # watermark = 2
    s = Scheduler(alloc, max_slots=4, max_blocks_per_seq=4)
    s.add(_req(0, 6))
    s.add(_req(1, 6))
    admitted = s.try_admit()
    assert admitted == []                    # 3 needed > 4 free - 2 watermark
    assert len(s.waiting) == 2


def test_overlong_prompt_clamped_at_admission():
    s = _sched(mb=2)                         # cap = 2 * 4 = 8 tokens
    s.add(_req(0, 20))
    [q] = s.try_admit()
    assert q.seq_len == 8 and len(q.req.prompt) == 8
    assert s.metrics["truncated_prompts"] == 1
    # prompt_token_ids reflects the prompt actually served, even after a
    # preemption folds generated tokens into the recompute prompt
    q.req.output.extend([50, 51])
    q.seq_len += 2
    s.preempt_youngest()
    assert q.req.prompt_token_ids == list(range(1, 9))


def test_plan_horizon_bounded_by_remaining_and_capacity():
    s = _sched(mb=4)                         # cap = 16
    s.add(_req(0, 6, max_tokens=20))
    s.add(_req(1, 6, max_tokens=3))
    for q in s.try_admit():
        q.seq_len += 1                       # first sampled token absorbed
    s.running[0].req.output.append(7)
    s.running[1].req.output.append(7)
    # finish boundary: rid 1 has 2 tokens left -> horizon 2
    assert s.plan_horizon(8) == 2
    s.running[1].req.output.extend([7, 7])   # now 0 left... but capacity
    # writes_left: rid 0 seq_len 7 -> 16 - 6 = 10; horizon capped by caller
    assert s.plan_horizon(4) == 1            # max(1, min(0, ...)) floor


def test_plan_horizon_preempts_youngest_when_blocks_exhausted():
    s = _sched(num_blocks=6, max_slots=2, mb=4)
    s.add(_req(0, 8, arrival=1.0))           # 2 full blocks
    r1 = _req(1, 8, arrival=2.0)
    r1.prompt = list(range(101, 109))        # distinct: no prefix sharing
    s.add(r1)                                # 2 more blocks
    for q in s.try_admit():
        q.seq_len += 1
    # exhaust the pool so even one growth block cannot be found
    held = [s.alloc._alloc_raw() for _ in range(s.alloc.num_free)]
    h = s.plan_horizon(8)
    assert s.metrics["preemptions"] >= 1
    assert 1 not in s.running or 0 in s.running   # youngest (rid 1) evicted
    # requeued at the head with prompt+output folded for recompute
    assert s.waiting and s.waiting[0].rid == 1
    for b in held:
        s.alloc.free(b)
    assert h >= 1 or not s.running


def test_grow_for_horizon_returns_cow_pairs_for_shared_tail():
    s = _sched(num_blocks=16, max_slots=2, mb=4)
    ids, _ = s.alloc.allocate_prompt(list(range(6)))   # 1 full + 1 partial
    fork = s.alloc.fork_sequence(ids)
    r0, r1 = _req(0, 6), _req(1, 6)
    s.running[0] = Sequence(req=r0, slot=0, block_ids=ids, seq_len=7,
                            last_token=9, computed_len=6)
    s.running[1] = Sequence(req=r1, slot=1, block_ids=fork, seq_len=7,
                            last_token=9, computed_len=6)
    cows = s.grow_for_horizon(1)             # both write at pos 6 (shared)
    assert len(cows) == 1                    # first grow CoWs, second owns
    src, dst = cows[0]
    assert src == ids[-1]
    assert s.running[0].block_ids[-1] != s.running[1].block_ids[-1]


def test_finish_at_capacity_sets_reason_and_frees():
    s = _sched(mb=2)                         # cap = 8
    s.add(_req(0, 8, max_tokens=50))
    [q] = s.try_admit()
    q.seq_len += 1                           # next write pos = 8 == cap
    free_before = s.alloc.num_free
    done = s.finish_at_capacity()
    assert [r.finish_reason for r in done] == ["capacity"]
    assert not s.running and s.free_slots and s.alloc.num_free > free_before


def test_preemption_requeues_with_generated_prefix():
    s = _sched(max_slots=2)
    s.add(_req(0, 5, arrival=1.0))
    s.add(_req(1, 5, arrival=2.0))
    for q in s.try_admit():
        q.req.output.extend([100, 101])
        q.seq_len += 2
    s.preempt_youngest()
    assert s.waiting[0].rid == 1
    assert s.waiting[0].prompt == list(range(1, 6)) + [100, 101]
    assert s.waiting[0].prompt_len0 == 5     # reporting keeps the original
    assert s.metrics["preemptions"] == 1


def test_double_preemption_does_not_duplicate_folded_tokens():
    """A second preemption must *replace* the previously folded generated
    suffix, not append the whole output again."""
    s = _sched(max_slots=1)
    s.add(_req(0, 4, max_tokens=20))
    [q] = s.try_admit()
    q.req.output.extend([10, 11])
    q.seq_len += 2
    s.preempt_youngest()
    assert s.waiting[0].prompt == [1, 2, 3, 4, 10, 11]
    [q] = s.try_admit()                      # re-admitted with folded prefix
    q.req.output.append(12)
    q.seq_len += 1
    s.preempt_youngest()
    assert s.waiting[0].prompt == [1, 2, 3, 4, 10, 11, 12]
    assert s.waiting[0].output == [10, 11, 12]
    assert s.waiting[0].prompt_len0 == 4


# ------------------------------------------------------- token-budget planner

def _execute_plan(s: Scheduler, plan: StepPlan, tok: int = 500) -> None:
    """Deviceless stand-in for the engine's plan execution: absorb
    ``horizon`` decode tokens per decode slot, mark chunks computed, and
    sample a first token when a prompt's final chunk lands."""
    for slot in plan.decode_slots:
        q = s.running.get(slot)
        if q is None:
            continue
        for _ in range(plan.horizon):
            q.req.output.append(tok)
            q.last_token = tok
            q.seq_len += 1
            if q.req.tokens_remaining() <= 0:
                s.finish(q, "length")
                break
    for c in plan.prefill:
        s.complete_chunk(c)
        if c.last and c.seq.slot in s.running:
            c.seq.req.output.append(tok)       # first sampled token
            c.seq.last_token = tok
            c.seq.seq_len += 1
            if c.seq.req.tokens_remaining() <= 0:
                s.finish(c.seq, "length")


def _drive(s: Scheduler, budget: int, max_horizon: int = 4,
           max_steps: int = 500):
    """Run plan/execute to drain; yields every plan for invariant checks."""
    plans = []
    for _ in range(max_steps):
        if not (s.waiting or s.running):
            break
        for _q in s.finish_at_capacity():
            pass
        plan = s.plan_step(budget, max_horizon=max_horizon)
        plans.append(plan)
        _execute_plan(s, plan)
    return plans


def test_plan_step_budget_never_exceeded():
    s = _sched(num_blocks=64, max_slots=3, mb=8)     # cap 32
    for i, n in enumerate([3, 25, 9, 31, 14, 6, 22]):
        s.add(_req(i, n, max_tokens=5))
    budget = 11
    plans = _drive(s, budget)
    assert len(s.finished) == 7
    assert all(p.used <= budget for p in plans)
    assert any(p.prefill for p in plans) and any(p.decode_slots for p in plans)
    # a 25/31-token prompt cannot fit one 11-token budget: chunking happened
    assert max(len(p.prefill) and max(c.length for c in p.prefill)
               for p in plans) <= budget


def test_plan_step_decode_priority_and_interleave():
    """Running decodes claim budget first; prefill chunks only pack the
    remainder, and the decode horizon is pinned to 1 while prefill work
    is pending (bounded inter-token latency)."""
    s = _sched(num_blocks=64, max_slots=3, mb=8)
    s.add(_req(0, 4, max_tokens=50))
    _execute_plan(s, s.plan_step(32, max_horizon=4))  # admit + full prefill
    assert not s.running[0].prefilling
    s.add(_req(1, 20, max_tokens=50))                 # long prompt arrives
    plan = s.plan_step(8, max_horizon=4)
    assert plan.decode_slots == [0]
    assert plan.horizon == 1                          # interleaved, not fused
    assert len(plan.prefill) == 1
    assert plan.prefill[0].length == 7                # budget 8 - 1 decode
    assert plan.used == 8


def test_plan_step_full_horizon_without_prefill_work():
    s = _sched(num_blocks=64, max_slots=2, mb=8)
    s.add(_req(0, 4, max_tokens=40))
    _execute_plan(s, s.plan_step(32, max_horizon=4))
    plan = s.plan_step(32, max_horizon=4)
    assert plan.decode_slots == [0] and plan.horizon == 4


def test_plan_step_no_starvation_under_steady_decode_load():
    """A waiting prompt makes monotonic chunk progress every step even
    while every slot's decode keeps claiming budget first."""
    s = _sched(num_blocks=64, max_slots=2, mb=8)
    s.add(_req(0, 4, max_tokens=10 ** 6))             # decodes forever
    _execute_plan(s, s.plan_step(32, max_horizon=4))
    s.add(_req(1, 21, max_tokens=5))
    budget = 6                                        # 1 decode + 5 prefill
    seen = []
    for _ in range(10):
        plan = s.plan_step(budget, max_horizon=4)
        assert plan.used <= budget
        _execute_plan(s, plan)
        q = next((x for x in s.running.values() if x.req.rid == 1), None)
        if q is None:                                 # finished prefill+gen
            break
        seen.append(q.computed_len)
    assert seen == sorted(seen)                       # monotone progress
    assert any(x.req.rid == 1 and not x.prefilling
               for x in s.running.values()) or \
        any(r.rid == 1 for r in s.finished)
    # progress took ceil(21/5) = 5 chunk steps, not a stall-out
    assert len(seen) <= 6


def test_plan_step_incremental_blocks_never_exceed_whole_prompt():
    """Chunked admission allocates per chunk; at no point may a
    mid-prefill sequence hold more blocks than whole-prompt admission
    would have allocated up front (ceil(len/bs) + 1)."""
    s = _sched(num_blocks=64, max_slots=1, mb=8)
    n = 30
    s.add(_req(0, n, max_tokens=2))
    whole = -(-n // BS) + 1
    peak = 0
    for _ in range(20):
        plan = s.plan_step(7, max_horizon=2)
        for q in s.running.values():
            peak = max(peak, len(q.block_ids))
        _execute_plan(s, plan)
        if s.finished:
            break
    assert s.finished and peak <= whole
    # and strictly fewer while the first chunks were in flight
    assert peak == -(-n // BS)                        # never the +1 upfront


def test_plan_step_admission_is_watermark_gated():
    alloc = BlockAllocator(8, BS, watermark_frac=0.25)  # watermark = 2
    s = Scheduler(alloc, max_slots=2, max_blocks_per_seq=8)
    held = [alloc._alloc_raw() for _ in range(3)]       # another tenant
    s.add(_req(0, 20, max_tokens=4))                    # feasible: 6 <= 8-2
    plan = s.plan_step(32, max_horizon=2)
    # the first chunk is clipped to the watermarked headroom:
    # (5 free - 2 watermark) * BS = 12 tokens, not the whole 20
    assert sum(c.length for c in plan.prefill) == 12
    for b in held:
        alloc.free(b)


def test_plan_step_never_admits_pool_infeasible_prompt():
    """A prompt that could never complete on this pool stays in waiting
    (exactly like whole-prompt admission) instead of being parked
    mid-prefill on blocks it can never finish with."""
    alloc = BlockAllocator(4, BS, watermark_frac=0.5)   # watermark = 2
    s = Scheduler(alloc, max_slots=2, max_blocks_per_seq=4)
    s.add(_req(0, 12, max_tokens=4))                    # needs 4 > 4 - 2
    plan = s.plan_step(16, max_horizon=2)
    assert not plan.prefill and len(s.waiting) == 1
    assert alloc.num_free == 4                          # nothing held
    # ... and the stuck head must not pin running decodes to horizon 1
    ids, _ = alloc.allocate_prompt([900])
    s.running[0] = Sequence(req=_req(9, 1, max_tokens=50), slot=0,
                            block_ids=ids, seq_len=2, last_token=900,
                            computed_len=1)
    s.free_slots.remove(0)
    plan = s.plan_step(16, max_horizon=2)
    assert plan.decode_slots == [0] and plan.horizon == 2


def test_plan_step_preempts_mid_prefill_and_recomputes_from_zero():
    """Out-of-blocks preemption may evict a mid-prefill sequence: its
    blocks free immediately, the untouched prompt requeues, and
    re-admission restarts the chunk walk at computed_len = 0."""
    s = _sched(num_blocks=6, max_slots=2, mb=6)
    s.add(_req(0, 8, max_tokens=50, arrival=1.0))     # 2 blocks + grow
    _execute_plan(s, s.plan_step(32, max_horizon=1))
    r1 = _req(1, 16, max_tokens=5, arrival=2.0)
    r1.prompt = list(range(101, 117))                  # no prefix sharing
    s.add(r1)
    plan = s.plan_step(5, max_horizon=1)               # 1 decode + 4 prefill
    _execute_plan(s, plan)
    young = next(x for x in s.running.values() if x.req.rid == 1)
    assert young.prefilling and young.computed_len == 4
    # decode growth now exhausts the pool -> youngest (mid-prefill) evicted
    for _ in range(30):
        plan = s.plan_step(5, max_horizon=1)
        _execute_plan(s, plan)
        if s.metrics["preemptions_mid_prefill"]:
            break
    assert s.metrics["preemptions_mid_prefill"] >= 1
    # the evicted request lost nothing: untouched prompt, no folded output,
    # and (whether still queued or already re-admitted) the chunk walk
    # restarted from zero
    assert r1.prompt == list(range(101, 117)) and r1.folded == 0
    readmitted = next((x for x in s.running.values() if x.req.rid == 1),
                      None)
    if readmitted is not None:
        assert readmitted.computed_len <= 4            # restarted, not 4+
    else:
        assert s.waiting and s.waiting[0].rid == 1
    # rid 0 drains, rid 1 re-admits at computed_len 0 and completes
    while s.waiting or s.running:
        for _q in s.finish_at_capacity():
            pass
        _execute_plan(s, s.plan_step(5, max_horizon=1))
    assert {r.rid for r in s.finished} == {0, 1}


def test_plan_step_deadlock_guard_evicts_youngest():
    """All-prefilling, zero-free-blocks: the planner must evict rather
    than return empty plans forever."""
    s = _sched(num_blocks=4, max_slots=2, mb=4)        # cap 16
    r0 = _req(0, 16, max_tokens=2, arrival=1.0)
    r1 = _req(1, 16, max_tokens=2, arrival=2.0)
    r1.prompt = list(range(201, 217))                  # distinct blocks
    ids0, _ = s.alloc.allocate_prompt(r0.prompt[:8])   # 2 blocks each:
    ids1, _ = s.alloc.allocate_prompt(r1.prompt[:8])   # pool exhausted
    s.running[0] = Sequence(req=r0, slot=0, block_ids=ids0, seq_len=8,
                            last_token=8, computed_len=8)
    s.running[1] = Sequence(req=r1, slot=1, block_ids=ids1, seq_len=8,
                            last_token=8, computed_len=8)
    s.free_slots.clear()
    assert s.alloc.num_free == 0
    plan = s.plan_step(16, max_horizon=1)              # nothing schedulable
    assert not plan.decode_slots and not plan.prefill
    assert s.metrics["preemptions"] == 1               # guard fired
    assert s.metrics["preemptions_mid_prefill"] == 1   # ... on rid 1
    # and the survivor's next chunk continues from where it stopped
    plan = s.plan_step(16, max_horizon=1)
    assert plan.prefill and plan.prefill[0].seq.req.rid == 0
    assert plan.prefill[0].start == 8


def test_plan_step_property_random_arrivals():
    """Hypothesis sweep: for any arrival/budget/length mix the planner
    never exceeds the budget, never regresses computed_len, and never
    holds more blocks than whole-prompt admission would."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.data())
    def run(data):
        budget = data.draw(st.integers(3, 40), label="budget")
        horizon = data.draw(st.integers(1, 8), label="horizon")
        lens = data.draw(st.lists(st.integers(1, 40), min_size=1,
                                  max_size=8), label="lens")
        s = _sched(num_blocks=32, max_slots=2, mb=8)   # cap 32, tight-ish
        pending = [_req(i, min(n, 40), max_tokens=3)
                   for i, n in enumerate(lens)]
        for i, r in enumerate(pending):
            r.prompt = [1000 * (i + 1) + t for t in range(len(r.prompt))]
        steps = 0
        while (pending or s.waiting or s.running) and steps < 300:
            steps += 1
            if pending and steps % 2:                  # staggered arrivals
                s.add(pending.pop(0))
            for _q in s.finish_at_capacity():
                pass
            plan = s.plan_step(budget, max_horizon=horizon)
            assert plan.used <= budget
            for c in plan.prefill:
                assert c.start == c.seq.computed_len
                assert c.length >= 1
            before = {id(x): x.computed_len for x in s.running.values()}
            _execute_plan(s, plan)
            for x in s.running.values():
                if id(x) in before:
                    assert x.computed_len >= before[id(x)]
                assert x.computed_len <= len(x.req.prompt)
                assert len(x.block_ids) <= -(-len(x.req.prompt) // BS) + 1
        assert not pending and not s.waiting and not s.running

    run()


def test_plan_step_budget_bound_holds_standalone():
    """StepPlan's used <= budget contract holds even for a degenerate
    budget <= decodable count (no engine validation in front): overflow
    slots sit the iteration out instead of over-batching."""
    s = _sched(num_blocks=64, max_slots=4, mb=8)
    for i in range(4):
        r = _req(i, 4, max_tokens=50)
        r.prompt = [100 * (i + 1) + t for t in range(4)]
        s.add(r)
    while s.waiting:
        _execute_plan(s, s.plan_step(64, max_horizon=1))
    assert len(s.decodable()) == 4
    plan = s.plan_step(3, max_horizon=4)
    assert plan.used <= 3
    assert len(plan.decode_slots) == 3 and plan.horizon == 1


def test_plan_step_zero_headroom_keeps_full_horizon():
    """A feasible waiting prompt that cannot admit a single token this
    step (watermarked headroom exhausted) must not pin decodes to
    horizon 1 — no chunk could run anyway."""
    alloc = BlockAllocator(8, BS, watermark_frac=0.25)  # watermark = 2
    s = Scheduler(alloc, max_slots=2, max_blocks_per_seq=6)
    s.add(_req(0, 4, max_tokens=200))
    _execute_plan(s, s.plan_step(32, max_horizon=1))
    held = []
    while alloc.num_free > alloc.watermark:             # headroom -> 0
        held.append(alloc._alloc_raw())
    s.add(_req(1, 8, max_tokens=4))                     # feasible, stuck
    plan = s.plan_step(32, max_horizon=4)
    assert plan.horizon == 4 and not plan.prefill       # full fused speed
    for b in held:
        alloc.free(b)
    plan = s.plan_step(32, max_horizon=4)               # headroom is back
    assert plan.horizon == 1 and plan.prefill


# ------------------------------------------------- unified-dispatch layout

def test_unified_dispatch_layout():
    """The plan's unified-dispatch layout: the first dispatch carries
    every decode slot plus the first chunk, admission-burst chunks each
    dispatch alone, and only final chunks mark their sample row."""
    s = _sched(num_blocks=64, max_slots=3, mb=8)
    s.add(_req(0, 4, max_tokens=100))
    _execute_plan(s, s.plan_step(32, max_horizon=4))   # rid 0 decoding
    s.add(_req(1, 21, max_tokens=5))                   # needs 2+ chunks
    s.add(_req(2, 6, max_tokens=5))                    # fits one chunk
    plan = s.plan_step(32, max_horizon=4)
    ds = plan.unified_dispatches()
    assert [d.chunk for d in ds] == plan.prefill       # one each, in order
    assert ds[0].decode_slots == plan.decode_slots
    assert all(d.decode_slots == [] for d in ds[1:])
    assert [d.sample_chunk for d in ds] == [c.last for c in plan.prefill]
    # pure-decode plans have no unified dispatch (megastep territory)
    _execute_plan(s, plan)
    while any(q.prefilling for q in s.running.values()):
        _execute_plan(s, s.plan_step(32, max_horizon=4))
    plan = s.plan_step(32, max_horizon=4)
    assert not plan.prefill and plan.unified_dispatches() == []


def test_unified_dispatch_layout_under_random_arrivals():
    """Over seeded random arrivals into tight pools, every plan's decodes
    run exactly once.  Beside prefill at horizon 1 they ride the first
    unified dispatch.  At a longer horizon they run as the plan's
    megastep and every chunk dispatches alone: the planner emits such a
    plan when ``plan_horizon`` preempts after the horizon pin was
    decided and the freed blocks or slot let prefill in."""
    rng = random.Random(0)
    longer = 0
    for _ in range(200):
        slots = rng.randint(1, 4)
        alloc = BlockAllocator(rng.randint(6, 24), BS,
                               watermark_frac=rng.choice([0.0, 0.25]))
        s = Scheduler(alloc, max_slots=slots,
                      max_blocks_per_seq=rng.randint(2, 8))
        budget, horizon = rng.randint(slots + 1, 40), rng.randint(2, 8)
        pending = [_req(i, rng.randint(1, 40), max_tokens=rng.randint(1, 30))
                   for i in range(rng.randint(1, 10))]
        for i, r in enumerate(pending):
            r.prompt = [1000 * (i + 1) + t for t in range(len(r.prompt))]
        for _step in range(400):
            if not (pending or s.waiting or s.running):
                break
            if pending and rng.random() < 0.4:
                s.add(pending.pop(0))
            for _q in s.finish_at_capacity():
                pass
            plan = s.plan_step(budget, max_horizon=horizon)
            ds = plan.unified_dispatches()
            assert [d.chunk for d in ds] == plan.prefill
            assert all(d.decode_slots == [] for d in ds[1:])
            if ds and plan.horizon <= 1:
                assert ds[0].decode_slots == plan.decode_slots
            elif ds:
                assert ds[0].decode_slots == []
                longer += bool(plan.decode_slots)
            _execute_plan(s, plan)
    assert longer > 0, "the sweep must reach prefill beside a longer horizon"


# ---------------------------------------------- register-on-write hashing

def test_register_on_write_full_blocks_reused_across_requests():
    """A repeated 2-chunk prompt reuses ALL its full blocks: the first
    chunk's via ``allocate_prompt`` hashing, the continuation chunk's via
    register-on-write + content-addressed ``grow_prefill``."""
    s = _sched(num_blocks=64, max_slots=2, mb=8)
    prompt = list(range(1, 23))                        # 22 tokens, BS=4
    r0 = _req(0, 1, max_tokens=4)
    r0.prompt = list(prompt)
    r0.prompt_len0 = len(prompt)
    s.add(r0)
    # two chunks: 12 + 10 (budget 12) — 5 full blocks + private tail
    for _ in range(4):
        _execute_plan(s, s.plan_step(12, max_horizon=1))
        if not any(q.prefilling for q in s.running.values()):
            break
    q0 = next(q for q in s.running.values() if q.req.rid == 0)
    assert not q0.prefilling
    assert q0.hashed_blocks == len(prompt) // BS       # all 5 registered
    r1 = _req(1, 1, max_tokens=4)
    r1.prompt = list(prompt)
    r1.prompt_len0 = len(prompt)
    s.add(r1)
    before = s.alloc.stats["reused"]
    # budget 13 = 1 decode (rid 0) + 12 prefill: rid 1's chunk walk lands
    # on the same block-aligned 12 + 10 split rid 0 took
    while any(q.prefilling for q in s.running.values()) or \
            any(r.rid == 1 for r in s.waiting):
        _execute_plan(s, s.plan_step(13, max_horizon=1))
    q1 = next(q for q in s.running.values() if q.req.rid == 1)
    # every full block is shared with rid 0's live sequence
    n_full = len(prompt) // BS
    assert s.alloc.stats["reused"] - before == n_full
    assert q1.block_ids[:n_full] == q0.block_ids[:n_full]
    assert q1.block_ids[n_full] != q0.block_ids[n_full]   # tails private


def test_register_on_write_skips_chunk_straddling_blocks():
    """A block filled across two chunks (the int8 boundary-merge case)
    is never registered — only whole-chunk-covered blocks are shareable."""
    s = _sched(num_blocks=64, max_slots=1, mb=8)
    r = _req(0, 1, max_tokens=4)
    r.prompt = list(range(1, 25))                      # 24 tokens
    r.prompt_len0 = 24
    s.add(r)
    # chunks of 6: blocks 1 (tokens 4..8) and 4 (16..20) straddle
    while any(q.prefilling for q in s.running.values()) or s.waiting:
        _execute_plan(s, s.plan_step(7, max_horizon=1))   # 1 dec + 6 pre
    q = next(iter(s.running.values()))
    hashed = [s.alloc._blocks[b].token_hash is not None
              for b in q.block_ids[:6]]
    # block 0 hashed by allocate_prompt (first chunk covers it whole);
    # straddled blocks stay private, fully-covered later ones register
    assert hashed[0] and not all(hashed[1:])
    assert any(hashed[1:])                             # some registered
