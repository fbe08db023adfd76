"""One general generator for every traffic mix under ``bench/traffic``.

A mix file holds parameters only: the loop type, the arrival process,
the length distributions and the sampling settings.  A cell file under
``bench/cells`` may add the cell's own numbers (its fixed rate).

Every seed gets the same work in another order.  Each block of ``n``
requests (an open loop's warm-up, window and tail; a backlog's blocks)
holds the same multiset of prompt and output lengths whatever the seed:
stratified quantiles of their distributions, paired by a fixed
permutation.  The one arrival process, ``permuted-exponential``, is not
a Poisson process: its gaps are the stratified quantiles of the
exponential distribution, scaled so that a block spans exactly its
seconds, in an order the seed draws.  Arrivals bunch and long prompts
cluster as the seed orders them, but a block's count and its multisets
of gaps and lengths are fixed.  A mix may set ``group``: the block is
then cut into groups of that many consecutive requests that each span
the whole range of lengths (a backlog's slots then fill with the same
lengths whatever the seed).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np

# the fixed permutation that pairs prompt-length and output-length
# quantiles: a property of the mix, the same for every seed
PAIRING_SEED = 20240611


@dataclass
class Request:
    """One generated request.  ``due`` is seconds after its block's
    start for an open loop and 0 for a backlog."""
    index: int
    due: float
    prompt: List[int]
    max_tokens: int
    temperature: float
    top_p: float
    seed: Optional[int]
    phase: str = "window"
    # filled in by the harness as the request is served
    due_t: float = 0.0
    rid: int = -1
    first_t: Optional[float] = None
    last_t: Optional[float] = None
    n_out: int = 0
    finish_t: Optional[float] = None
    reason: Optional[str] = None
    tokens: List[int] = field(default_factory=list)
    events: List[tuple] = field(default_factory=list)

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles (at (i + 1/2) / n) of a length
    distribution, rounded and clipped: the same multiset for every seed."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(v), dist.get("min", 1), dist.get("max", 1 << 30)
                   ).astype(np.int64)


def arrival_gaps(process: dict, rate: float, seconds: float, n: int
                 ) -> np.ndarray:
    """Stratified inter-arrival gaps of a block of ``n`` requests that
    spans exactly ``seconds`` (the ``permuted-exponential`` process)."""
    if process["process"] != "permuted-exponential":
        raise ValueError(f"unknown arrival process {process['process']!r}")
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u) / rate
    return g * (seconds / g.sum())


class Traffic:
    """A mix bound to a cell and a seed."""

    def __init__(self, mix: dict, cell: dict, seed: int, vocab: int):
        self.mix = mix
        self.cell = cell
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.loop = mix["loop"]
        self.rate = float(cell.get("rate_rps", 0.0))
        if self.loop == "open" and self.rate <= 0:
            raise ValueError("an open-loop mix needs the cell's rate_rps")
        self._next_index = 0

    def _rng(self, *tag) -> np.random.Generator:
        return np.random.default_rng(
            [self.seed % (1 << 64)] + [hash_tag(t) for t in tag])

    def block(self, phase: str, n: int, seconds: float = 0.0
              ) -> List[Request]:
        """``n`` requests due over ``seconds`` (open loop) or all at once
        (backlog).  The work is the same for every seed: the pairs of
        prompt and output length (quantiles paired by a fixed
        permutation), which of them decode greedily, and, where the mix
        sets ``group``, the groups of consecutive requests that each span
        the whole range of lengths and their share of the seconds.  The
        seed orders the groups and the requests inside each, orders each
        group's arrival gaps and draws the token ids."""
        rng = self._rng(phase, self._next_index)
        s = self.mix["sampling"]
        prompts = quantiles(self.mix["prompt_len"], n)
        outs = quantiles(self.mix["output_len"], n)[
            np.random.default_rng(PAIRING_SEED).permutation(n)]
        share = 1.0 if s.get("temperature", 0.0) == 0.0 \
            else float(s.get("greedy_share", 0.0))
        greedy = np.floor((np.arange(n) + 1) * share) > \
            np.floor(np.arange(n) * share)
        n_groups = -(-n // int(self.mix.get("group", n)))
        groups = [np.arange(g, n, n_groups) for g in range(n_groups)]
        perm = [groups[g] for g in rng.permutation(n_groups)]
        order = np.concatenate([rng.permutation(g) for g in perm])
        due = np.zeros(n)
        if self.loop == "open" and seconds > 0:
            t, k = 0.0, 0
            for g in perm:
                span = seconds * len(g) / n
                gaps = rng.permutation(arrival_gaps(
                    self.mix["arrival"], self.rate, span, len(g)))
                due[k:k + len(g)] = t + np.concatenate(
                    [[0.0], np.cumsum(gaps)[:-1]])
                t, k = t + span, k + len(g)
        reqs = []
        for i, j in enumerate(order):
            greedy_j = bool(greedy[j])
            reqs.append(Request(
                index=self._next_index + i, due=float(due[i]),
                prompt=rng.integers(1, self.vocab, int(prompts[j])).tolist(),
                max_tokens=int(outs[j]),
                temperature=0.0 if greedy_j else float(s["temperature"]),
                top_p=1.0 if greedy_j else float(s.get("top_p", 1.0)),
                seed=None if greedy_j else int(rng.integers(0, 2 ** 31 - 1)),
                phase=phase))
        self._next_index += n
        return reqs

    def open_loop(self, warmup_s: float, seconds: float, tail_s: float
                  ) -> List[Request]:
        """Warm-up, window and tail blocks of an open loop, each its own
        stratified block, with due times relative to the warm-up start."""
        out, t0 = [], 0.0
        for phase, dur in (("warmup", warmup_s), ("window", seconds),
                           ("tail", tail_s)):
            n = max(1, int(round(self.rate * dur)))
            blk = self.block(phase, n, dur)
            for r in blk:
                r.due += t0
            out += blk
            t0 += dur
        return out

    def backlog(self, block: int) -> Iterator[Request]:
        """An endless backlog, one stratified block at a time."""
        k = 0
        while True:
            yield from self.block(f"backlog{k}", block)
            k += 1


def hash_tag(tag) -> int:
    """A stable 32-bit integer for a seed-sequence entry."""
    if isinstance(tag, int):
        return tag % (1 << 32)
    h = 2166136261
    for ch in str(tag).encode():
        h = ((h ^ ch) * 16777619) % (1 << 32)
    return h
