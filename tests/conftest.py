"""Shared fixtures: the deterministic rng and the recompile sentinel
that steady-state serving tests use to prove no shapes leak into a
jitted executable after warmup."""
import math

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


class RecompileSentinel:
    """Snapshots a ModelRunner's jit compile-cache sizes after warmup;
    ``check()`` (also run at fixture teardown) asserts the steady-state
    window that followed compiled nothing new.

    Executables whose cache size reads as NaN (the private jax
    ``_cache_size`` API drifted, or the path is disabled) are skipped,
    so a jax bump degrades this gate to a no-op instead of a fake
    regression — matching ``ModelRunner._cache_size``.
    """

    _EXECUTABLES = ("_unified", "_unified_chained", "_megastep", "_sample")

    def __init__(self):
        self._armed = []

    def arm(self, runner, label="runner"):
        """Snapshot ``runner`` post-warmup; returns the snapshot."""
        snap = self._snapshot(runner)
        self._armed.append((runner, label, snap))
        return snap

    @staticmethod
    def _snapshot(runner):
        from repro.serving.model_runner import ModelRunner
        snap = {}
        for name in RecompileSentinel._EXECUTABLES:
            fn = getattr(runner, name, None)
            if fn is None:
                continue
            n = ModelRunner._cache_size(fn)
            if not math.isnan(n):
                snap[name] = n
        return snap

    def check(self):
        grew = []
        for runner, label, before in self._armed:
            after = self._snapshot(runner)
            for name, n0 in sorted(before.items()):
                n1 = after.get(name, n0)
                if n1 > n0:
                    grew.append(f"{label}.{name}: {n0:g} -> {n1:g}")
        self._armed.clear()
        assert not grew, (
            "steady-state recompilation detected (a shape leaked into a "
            "jitted executable after warmup): " + "; ".join(grew))


@pytest.fixture
def recompile_sentinel():
    s = RecompileSentinel()
    yield s
    s.check()
