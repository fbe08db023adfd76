"""Token sampling: greedy / temperature / top-k / top-p.

``sample_from_logits`` (re-exported from ``repro.core.sampling``) is the
jit-friendly per-slot core used by the fused executables and the
whole-prompt wave's first-token sample (``ModelRunner.sample``);
``sample`` is a host-facing convenience wrapper over the single-key
batch sampler.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.sampling import sample_device, sample_from_logits

__all__ = ["sample", "sample_device", "sample_from_logits"]


def sample(logits: jnp.ndarray, key, temperatures: Sequence[float],
           top_k: int = 0) -> np.ndarray:
    """Host wrapper: python temperature list in, numpy token ids out."""
    t = jnp.asarray(list(temperatures), jnp.float32)
    return np.asarray(sample_device(logits, key, t, top_k))
