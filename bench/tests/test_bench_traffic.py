"""The traffic generator and the latency arithmetic."""
from collections import Counter

import numpy as np
import pytest

import benchtest
from bench import stats
from bench.traffic import Request, Traffic, arrival_gaps, quantiles

CHAT = benchtest.fixture("tiny-chat.json")
OFFLINE = benchtest.fixture("tiny-offline.json")
BIG_SEED = 2 ** 31 + 12345


def schedule(seed, mix=CHAT, cell=None):
    t = Traffic(mix, cell or {"rate_rps": 10.0}, seed, vocab=256)
    return t.open_loop(2.0, 4.0, 1.0)


def key(reqs):
    return [(r.due, tuple(r.prompt), r.max_tokens, r.temperature, r.seed)
            for r in reqs]


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_same_seed_same_schedule(seed):
    assert key(schedule(seed)) == key(schedule(seed))


def test_another_seed_another_schedule():
    assert key(schedule(1)) != key(schedule(2))


def test_every_seed_gets_the_same_work_in_another_order():
    a, b = schedule(1), schedule(BIG_SEED)
    for phase in ("warmup", "window", "tail"):
        pa = [r for r in a if r.phase == phase]
        pb = [r for r in b if r.phase == phase]
        assert Counter((len(r.prompt), r.max_tokens, r.greedy)
                       for r in pa) == \
            Counter((len(r.prompt), r.max_tokens, r.greedy) for r in pb)
    win = [r for r in a if r.phase == "window"]
    assert win[0].due == pytest.approx(2.0)
    assert max(r.due for r in win) < 6.0


def test_each_group_spans_the_lengths_and_its_seconds():
    mix = dict(CHAT, group=4,
               prompt_len={"dist": "lognormal", "median": 100, "sigma": 0.5,
                           "min": 1, "max": 10000})
    reqs = Traffic(mix, {"rate_rps": 10.0}, 3, vocab=256).block("w", 40, 4.0)
    lens = sorted(len(r.prompt) for r in reqs)
    assert len(set(lens)) == 40
    for k in range(0, 40, 4):
        grp = reqs[k:k + 4]
        # one request from each quarter of the sorted lengths
        ranks = sorted(lens.index(len(r.prompt)) // 10 for r in grp)
        assert ranks == [0, 1, 2, 3]
        assert all(k * 0.1 - 1e-9 <= r.due < (k + 4) * 0.1 for r in grp)


def test_without_groups_the_seed_orders_the_whole_block():
    """Arrivals bunch and long prompts cluster: the seed permutes the
    block's gaps and length pairs as a whole."""
    assert "group" not in CHAT
    gaps = arrival_gaps(CHAT["arrival"], 10.0, 4.0, 40)
    quarters, crowded = set(), []
    for seed in range(20):
        reqs = Traffic(CHAT, {"rate_rps": 10.0}, seed, 256).block("w", 40,
                                                                  4.0)
        due = [r.due for r in reqs]
        assert sorted(np.diff(due + [4.0])) == pytest.approx(sorted(gaps))
        quarters.add(tuple(np.histogram(due, bins=4, range=(0, 4))[0]))
        # the longest eighth of the prompts, by the eight arrivals each
        # falls among (groups of 8 would hold exactly one each)
        longest = sorted(range(40), key=lambda i: -len(reqs[i].prompt))[:5]
        crowded.append(max(Counter(i // 8 for i in longest).values()))
    # the count per second of the block varies with the seed, and long
    # prompts often share their eight arrivals
    assert len(quarters) > 5
    assert sum(c > 1 for c in crowded) > 10


def test_backlog_blocks_are_stratified_and_greedy():
    t = Traffic(OFFLINE, {}, BIG_SEED, vocab=256)
    it = t.backlog(OFFLINE["block"])
    blk = [next(it) for _ in range(OFFLINE["block"])]
    assert all(r.greedy and r.due == 0.0 for r in blk)
    assert sorted(len(r.prompt) for r in blk) == \
        sorted(quantiles(OFFLINE["prompt_len"], OFFLINE["block"]).tolist())
    assert all(1 <= tok < 256 for r in blk for tok in r.prompt)


def test_quantiles_and_gaps():
    q = quantiles({"dist": "lognormal", "median": 100, "sigma": 1.0,
                   "min": 10, "max": 400}, 101)
    assert q[50] == 100 and q.min() >= 10 and q.max() <= 400
    assert list(q) == sorted(q)
    g = arrival_gaps({"process": "permuted-exponential"}, 5.0, 3.0, 15)
    assert g.sum() == pytest.approx(3.0)
    assert g.min() > 0


def test_percentile_matches_hand_values_and_numpy():
    assert stats.percentile([], 90) is None
    assert stats.percentile([5.0], 90) == 5.0
    # ranks 0..9: p90 sits at rank 8.1 -> 9 + 0.1 * (10 - 9)
    assert stats.percentile(range(1, 11), 90) == pytest.approx(9.1)
    xs = np.random.default_rng(0).lognormal(size=37)
    assert stats.percentile(xs, 90) == pytest.approx(np.percentile(xs, 90))


def _req(first, last, n, due=0.0, fin=True):
    r = Request(index=0, due=0.0, prompt=[1], max_tokens=n, temperature=0.0,
                top_p=1.0, seed=None)
    r.due_t, r.first_t, r.last_t, r.n_out = due, first, last, n
    r.finish_t = last if fin else None
    return r


def test_tpot_ttft_and_tokens_by_hand():
    a = _req(1.0, 2.0, 11, due=0.5)        # 100 ms per token, TTFT 500 ms
    b = _req(3.0, 3.0, 1)                  # one token: no TPOT
    c = _req(1.0, 4.0, 4, fin=False)       # unfinished: no TPOT
    assert stats.tpot_ms([a, b, c]) == [pytest.approx(100.0)]
    assert stats.ttft_ms([a], t_end=9.0) == [pytest.approx(500.0)]
    late = _req(None, None, 0, due=2.0, fin=False)
    assert stats.ttft_ms([late], t_end=3.5) == [pytest.approx(1500.0)]
    a.events = [(1.0, 0, 1), (1.5, 1, 8), (2.0, 9, 2)]
    assert stats.tokens_in([a], 1.2, 2.0) == 8
