"""A run with the timed path broken underneath must come out not
correct: the harness's check, and nothing else, has to catch it."""
import jax.numpy as jnp
import numpy as np
import pytest

import benchtest
from repro.serving.model_runner import ModelRunner

VOCAB = benchtest.fixture("tiny.json")["vocab_size"]


def _alter(x):
    """Every sampled token moved to its neighbour, as produced."""
    if isinstance(x, np.ndarray):
        return np.where(x > 0, (x + 1) % VOCAB, x)
    return jnp.where(x > 0, (x + 1) % VOCAB, x)


def _patch_tokens(monkeypatch):
    for name in ("megastep", "unified_step", "unified_step_chained"):
        orig = getattr(ModelRunner, name)

        def wrapped(self, *a, _orig=orig, **k):
            return _alter(_orig(self, *a, **k))
        monkeypatch.setattr(ModelRunner, name, wrapped)


def _patch_state(monkeypatch):
    """Every dispatch returns the KV pools it was given: the step's
    writes are lost."""
    keys = ("k_pool", "v_pool", "k_scales", "v_scales")
    for name in ("megastep", "unified_step", "unified_step_chained",
                 "prefill_chunk"):
        orig = getattr(ModelRunner, name)

        def wrapped(self, *a, _orig=orig, **k):
            saved = {x: jnp.copy(self.state[x]) for x in keys
                     if x in self.state}
            out = _orig(self, *a, **k)
            self.state.update(saved)
            return out
        monkeypatch.setattr(ModelRunner, name, wrapped)


def _patch_half_batch(monkeypatch):
    """Every dispatch computes only the even decode slots: the other half
    of the batch is left out."""
    where = {"megastep": 2, "unified_step": 2, "unified_step_chained": 5}
    for name, i in where.items():
        orig = getattr(ModelRunner, name)

        def wrapped(self, *a, _orig=orig, _i=i, **k):
            a = list(a)
            active = np.asarray(a[_i]).copy()
            active[1::2] = False
            a[_i] = active
            return _orig(self, *a, **k)
        monkeypatch.setattr(ModelRunner, name, wrapped)


FAULTS = {"token_altered": _patch_tokens, "state_unchanged": _patch_state,
          "half_batch_left_out": _patch_half_batch}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    out, info = benchtest.run_tiny("tiny.offline", seed=11)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > benchtest.TINY_LIMIT
