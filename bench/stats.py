"""Latency arithmetic of the benchmark, kept here so that no change to the
program can change how a number is computed.

Every latency is on the harness's clock: from the moment a request was
due to the moment ``step()`` returned the ``RequestOutput`` that carried
the token.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Iterable[float], p: float) -> Optional[float]:
    """The ``p``-th percentile with linear interpolation between closest
    ranks (numpy's default method); None for no values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    rank = (len(xs) - 1) * p / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def ttft_ms(reqs: Sequence, t_end: float) -> List[float]:
    """Due time to first token of each request; one still without a
    token counts with the time it waited until ``t_end``."""
    return [((r.first_t if r.first_t is not None else t_end) - r.due_t)
            * 1e3 for r in reqs]


def tpot_ms(reqs: Sequence) -> List[float]:
    """(last token time - first token time) / (output tokens - 1) of each
    finished request with at least two tokens."""
    return [(r.last_t - r.first_t) * 1e3 / (r.n_out - 1) for r in reqs
            if r.finish_t is not None and r.n_out > 1]


def tokens_in(reqs: Sequence, t0: float, t1: float) -> int:
    """Generated tokens returned by ``step()`` in ``[t0, t1)``."""
    return sum(n for r in reqs for (t, _, n) in r.events if t0 <= t < t1)

