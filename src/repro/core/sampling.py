"""Device-side token sampling: greedy / temperature / top-k / top-p.

Lives in core (pure jnp, no model or serving dependencies) so both the
serving layer and ``models.transformer.decode_megastep`` can use it
without a serving -> models -> serving import cycle.

The per-slot entry point is ``sample_from_logits``: every slot carries its
own (temperature, top_k, top_p) and — crucially — its own PRNG stream.  A
slot's step key is ``fold_in(base_key, num_generated_tokens)``, i.e. the
stream is indexed by *position in the generation*, not by engine step.
That single choice buys three properties at once:

* the fused megastep (device ``fori_loop``) at any horizon, the unified
  step and the whole-prompt wave's standalone sample compute
  byte-identical keys, so their sampled tokens match bitwise;
* a request's tokens do not depend on batch composition (slots never
  share a key), so seeded requests reproduce across runs and schedules;
* recompute-style preemption resumes the stream where it left off
  (``counts`` = tokens generated so far survives the requeue).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _filter_top_k_top_p(scaled: jnp.ndarray, top_ks: jnp.ndarray,
                        top_ps: jnp.ndarray) -> jnp.ndarray:
    """Mask logits outside each row's top-k / nucleus (top-p) set to -inf.

    scaled: [B, V]; top_ks: [B] i32 (<= 0 disables); top_ps: [B] f32
    (>= 1.0 disables).  A single values-only descending sort serves both
    filters (XLA CPU sorts are the expensive primitive here — no argsort,
    no inverse-permutation scatter): the kept set reduces to one per-row
    *value threshold* (the smallest sorted logit still inside both the
    top-k prefix and the nucleus), because nucleus-kept entries are a
    prefix of the top-k prefix.  Logits tied with the threshold are all
    kept — deterministic, and identical on every path that calls this.
    """
    V = scaled.shape[-1]
    svals = -jnp.sort(-scaled, axis=-1)                         # [B, V] desc
    rank = jnp.arange(V)[None, :]
    k_eff = jnp.where(top_ks <= 0, V, jnp.clip(top_ks, 1, V))[:, None]
    in_k = rank < k_eff
    # nucleus over the top-k-filtered distribution: keep the smallest
    # prefix whose mass reaches top_p (the top-1 token is always kept —
    # its preceding cumulative mass is 0).
    probs = jax.nn.softmax(jnp.where(in_k, svals, -jnp.inf), axis=-1)
    prior_mass = jnp.cumsum(probs, axis=-1) - probs
    # top_p >= 1.0 must keep the row's whole top-k set even though f32
    # cumsum rounds tail prior_mass up to exactly 1.0 on peaked rows —
    # otherwise a filter-disabled row would be truncated whenever some
    # *other* slot's params force the filter to run, making its sample
    # depend on batch composition.
    keep_sorted = in_k & ((prior_mass < top_ps[:, None])
                          | (top_ps[:, None] >= 1.0))
    thr = jnp.min(jnp.where(keep_sorted, svals, jnp.inf), axis=-1,
                  keepdims=True)
    return jnp.where(scaled >= thr, scaled, -jnp.inf)


def sample_from_logits(logits: jnp.ndarray, base_keys: jnp.ndarray,
                       counts: jnp.ndarray, temps: jnp.ndarray,
                       top_ks: jnp.ndarray, top_ps: jnp.ndarray,
                       poison: jnp.ndarray = None,
                       guard: bool = False) -> jnp.ndarray:
    """Per-slot sampling. Returns [B] i32 token ids.

    logits:    [B, V]
    base_keys: [B, 2] uint32 — one PRNG stream per slot
    counts:    [B] i32 — tokens generated so far (the stream position)
    temps:     [B] f32 — <= 0 means greedy (argmax)
    top_ks:    [B] i32 — <= 0 disables top-k
    top_ps:    [B] f32 — >= 1.0 disables nucleus filtering
    poison:    optional [B] f32 bias added per row before sampling —
               the fault-injection hook (NaN rows exercise the guard end
               to end on device); None means not traced at all
    guard:     static flag — when True, a row whose logits contain any
               non-finite value samples token ``-1`` instead of
               propagating garbage (argmax over NaNs), so the engine can
               fail exactly the poisoned rows.  Rows with finite logits
               are untouched: guard on/off is sample-for-sample
               identical on healthy batches.

    Pure jnp — safe inside jit / lax loops (the fused megastep).  The
    expensive stages are gated on what the batch actually requests
    (``lax.cond`` runs one branch at runtime): an all-greedy batch pays
    only the argmax, and the sort-based top-k/top-p filter runs only
    when some slot asked for it — so the fused decode megastep's warm
    per-step latency is unchanged for the common greedy/temperature
    workloads.
    """
    if poison is not None:
        logits = logits + poison[:, None]
    greedy = jnp.argmax(logits, axis=-1)

    def _sampled(_):
        scaled = logits / jnp.maximum(temps[:, None], 1e-6)
        # only slots that actually sample can need the sort-based filter:
        # a greedy slot's top_k/top_p are irrelevant to its argmax
        needs_filter = jnp.any((temps > 0.0)
                               & ((top_ks > 0) | (top_ps < 1.0)))
        masked = jax.lax.cond(
            needs_filter,
            lambda s: _filter_top_k_top_p(s, top_ks, top_ps),
            lambda s: s, scaled)
        step_keys = jax.vmap(jax.random.fold_in)(base_keys, counts)
        return jax.vmap(lambda k, l: jax.random.categorical(k, l))(
            step_keys, masked)

    sampled = jax.lax.cond(jnp.any(temps > 0.0), _sampled,
                           lambda _: greedy, None)
    tok = jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)
    if guard:
        # one max-reduce instead of isfinite+all over [B, V]: NaN
        # propagates through max, +inf IS the max, and an all--inf row
        # maxes to -inf — while mask-legal -inf entries under a finite
        # max still pass.  The guard costs one reduce over the logits.
        ok = jnp.isfinite(jnp.max(logits, axis=-1))
        tok = jnp.where(ok, tok, -1)
    return tok


def sample_device(logits: jnp.ndarray, key, temperatures: jnp.ndarray,
                  top_k: int = 0) -> jnp.ndarray:
    """Legacy single-key batch sampler (one shared key, uniform top_k).

    Kept for callers that predate per-slot ``SamplingParams``; new code
    should use ``sample_from_logits``.
    """
    t = temperatures[:, None]
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(t, 1e-6)
    if top_k > 0:
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(t[:, 0] <= 0.0, greedy, sampled).astype(jnp.int32)
