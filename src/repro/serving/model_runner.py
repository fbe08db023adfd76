"""Device-side half of the serving engine: decode state + jitted calls.

Owns the paged KV pools / SSM state pools, the jitted prefill /
per-token decode / fused megastep executables, on-device sampling for the
legacy loop, and the copy-on-write block copies.  It knows nothing about
queues, slots-as-policy, or request lifecycles — the ``Scheduler`` does;
the engine facade wires the two together.

Buffer-donation invariant (see docs/PERF.md): the megastep donates the
whole decode state, so after a fused dispatch the previous ``state``
arrays are dead — always re-read ``runner.state``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence as Seq, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.kv_quant import (cache_from_state, cache_to_state,
                                 normalize_kv_cache_dtype)
from repro.core.paged_cache import copy_blocks
from repro.core.sampling import sample_from_logits
from repro.models import transformer as T
from repro.obs.metrics import MetricsDict, MetricsRegistry
from repro.obs.trace import NULL_TRACER

# decode-state entries that are pool-shaped [L, NB, ...] and therefore
# owned globally by the engine (scattered whole, not per-slot)
_POOL_KEYS = ("k_pool", "v_pool", "k_scales", "v_scales")


class ModelRunner:
    def __init__(self, cfg: ModelConfig, params, *, max_slots: int,
                 num_blocks: int, max_blocks_per_seq: int,
                 rt: Optional[dict] = None, max_horizon: int = 8,
                 state_dtype=jnp.float32, kv_cache_dtype: str = "bf16",
                 chunk_tokens: Optional[int] = None,
                 unified: bool = False, tracer=None,
                 metrics: Optional[MetricsDict] = None):
        self.cfg = cfg
        self.params = params
        # engine-owned span tracer (obs); NULL_TRACER = zero-work no-op.
        # Dispatch spans carry the profiler label of their executable.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # engine-owned counters: forward passes, the live decode rows
        # they compute, the live KV pages those rows read per layer, and
        # compiles seen at dispatch time
        self.metrics = metrics if metrics is not None \
            else MetricsDict(MetricsRegistry())
        for k in ("forward_passes", "decode_rows", "decode_pages",
                  "compiles"):
            self.metrics.setdefault(k, 0)
        # executable name -> jit cache size last seen after a dispatch
        # (``copy_blocks`` is one jit for the process: start from now)
        self._compiled: Dict[str, float] = {
            "copy_cow": self._cache_size(copy_blocks)}
        self.max_slots = max_slots
        self.num_blocks = num_blocks
        self.mb = max_blocks_per_seq
        self.rt = dict(rt or {})
        self.max_horizon = max(1, max_horizon)
        self.kv_cache_dtype = normalize_kv_cache_dtype(kv_cache_dtype)
        self.chunk_tokens = chunk_tokens
        self.unified = bool(unified and chunk_tokens)
        # device dispatches issued so far (jitted calls + CoW copies) —
        # the engine diffs this around each step for
        # ``device_dispatches_per_step`` (host->device table uploads are
        # transfers, not dispatches, and are not counted)
        self.dispatches = 0
        # live KV pages per layer of the slots the last ``sync_tables``
        # gave a length: what one decode pass over them reads
        self.decode_pages = 0
        self.state = T.make_decode_state(cfg, max_slots, num_blocks, self.mb,
                                         dtype=state_dtype,
                                         kv_cache_dtype=self.kv_cache_dtype)

        self._prefill = jax.jit(
            lambda p, s, b: T.prefill(cfg, p, s, b, None, self.rt))
        # the serving chunk executable: [1, chunk_tokens] + scalar offsets
        # regardless of prompt length or batch composition, so it compiles
        # exactly once. Pools are donated: the chunk scatter updates the
        # [L, NB, KV, BS, D] arrays (+ int8 scales) in place.
        self._prefill_chunk = None
        if chunk_tokens:
            self._prefill_chunk = jax.jit(
                lambda p, c, t, bt, off, tl: T.prefill_chunk(
                    cfg, p, c, t, bt, off, tl, None, self.rt),
                donate_argnums=(1,))
        self._decode = jax.jit(
            lambda p, s, t: T.decode_step(cfg, p, s, t, None, self.rt))
        # the fused megastep donates the whole decode state: the KV pools
        # are updated in place instead of copied every token.
        self._megastep = jax.jit(
            lambda p, s, t, sp, a, n: T.decode_megastep(
                cfg, p, s, t, sp, a, n,
                max_horizon=self.max_horizon, ctx=None, rt=self.rt),
            donate_argnums=(1,))
        # the unified step: ONE donated dispatch = one decode step for the
        # active slots + one prefill chunk + per-row sampling.  Shapes are
        # pinned to [max_slots] decode rows and the [1, chunk_tokens]
        # chunk window, so it compiles exactly once.
        self._unified = None
        self._unified_chained = None
        if self.unified:
            self._unified = jax.jit(
                lambda p, s, t, sp, a, c, cbt, off, tl: T.unified_step(
                    cfg, p, s, t, sp, a, c, cbt, off, tl, None, self.rt),
                donate_argnums=(1,))
            # the async pipeline's executable: same unified step, but the
            # decode feed tokens are gathered on device from the PREVIOUS
            # dispatch's (still in-flight) output buffer.  Deliberately
            # NOT donated: donating a buffer the in-flight dispatch is
            # still producing forces the XLA CPU client to run the call
            # synchronously (measured: zero host/device overlap), which
            # is exactly what the pipeline exists to avoid.  The state
            # copy this costs is ~the pool size per step and is hidden
            # under the overlapped host work (see docs/PERF.md).
            self._unified_chained = jax.jit(
                lambda p, s, pv, ci, up, t, sp, a, c, cbt, off, tl:
                T.unified_step_chained(cfg, p, s, pv, ci, up, t, sp, a,
                                       c, cbt, off, tl, None, self.rt))
        # host-known zero feed buffer for pipeline-restart dispatches
        # (use_prev all False): allocated once so the chained executable
        # keeps a single (shape, dtype) signature either way
        self.zero_prev = jnp.zeros((max_slots + 1,), jnp.int32)
        # legacy-loop sampling: the SAME per-slot kernel the megastep runs,
        # jitted standalone so both paths are bitwise identical.  ``guard``
        # is trace-static (a python bool branching on jnp.isfinite): with
        # guards off the traced program is identical to the pre-guard one.
        self._sample = jax.jit(sample_from_logits,
                               static_argnames=("guard",))

    # ------------------------------------------------------------ obs
    def _count_pass(self, rows: int, passes: int = 1) -> None:
        """Counters of one forward-pass dispatch: ``passes`` passes (a
        megastep's horizon) each computing ``rows`` live decode rows that
        read ``decode_pages`` KV pages per layer (none without rows)."""
        self.metrics["forward_passes"] += passes
        self.metrics["decode_rows"] += rows * passes
        self.metrics["decode_pages"] += self._pages(rows) * passes

    def _pages(self, rows: int) -> int:
        """The ``pages`` arg of a forward-pass span with ``rows`` decode
        rows: the live pages of the synced slots, 0 when none decode."""
        return self.decode_pages if rows else 0

    def _note_compile(self, name: str, fn) -> None:
        """After a dispatch: when the executable's jit cache grew, the
        call compiled — record a ``compile`` instant naming it and count
        it in ``repro_compiles``."""
        n = self._cache_size(fn)
        seen = self._compiled.get(name, 0.0)
        if n > seen:                      # NaN (API drift) never fires
            self._compiled[name] = n
            self.metrics["compiles"] += n - seen
            self.tracer.instant("compile", cat="compile",
                                args={"executable": name})

    # ------------------------------------------------------------ tables
    def sync_tables(self, running: Dict[int, "object"]) -> None:
        """Rebuild seq_lens / block_table device rows from host truth."""
        bt = np.zeros((self.max_slots, self.mb), np.int32)
        sl = np.zeros((self.max_slots,), np.int32)
        for slot, s in running.items():
            bt[slot, :len(s.block_ids)] = s.block_ids
            sl[slot] = s.seq_len
        bs = self.cfg.paging.block_size
        self.decode_pages = int((-(-sl // bs)).sum())
        if "block_table" in self.state:
            self.state["block_table"] = jnp.asarray(bt)
        self.state["seq_lens"] = jnp.asarray(sl)

    # ------------------------------------------------------------ prefill
    def prefill(self, seqs: List["object"], maxlen: int) -> jnp.ndarray:
        """Prefill a wave of admitted sequences (padded to ``maxlen``);
        scatters pool / per-slot state rows back into the live engine
        state and returns last-token logits [len(seqs), V]."""
        B = len(seqs)
        with self.tracer.span("inputs", cat="host"):
            toks = np.zeros((B, maxlen), np.int32)
            lens = np.zeros((B,), np.int32)
            for i, s in enumerate(seqs):
                toks[i, :s.seq_len] = s.req.prompt
                lens[i] = s.seq_len
            # temporary contiguous state for the prefill batch, then
            # scatter into the live engine state at each sequence's
            # slot/table.
            sub = dict(self.state)
            bt = np.zeros((B, self.mb), np.int32)
            for i, s in enumerate(seqs):
                bt[i, :len(s.block_ids)] = s.block_ids
            sub["block_table"] = jnp.asarray(bt) if "block_table" in sub \
                else None
            sub = {k: v for k, v in sub.items() if v is not None}
            # prefill writes pools in-place via the shared pool arrays:
            # pools are engine-global, per-slot state rows are
            # gathered/scattered.
            per_seq = {}
            for k in ("ssm_h", "ssm_conv", "lru_h", "rec_conv"):
                if k in sub:
                    per_seq[k] = sub[k][:, [s.slot for s in seqs]]
                    sub[k] = per_seq[k]
            sub["seq_lens"] = jnp.asarray(lens)
            batch = {"tokens": jnp.asarray(toks),
                     "ctx_lens": jnp.asarray(lens)}
        self.dispatches += 1
        with self.tracer.span("dispatch:prefill", cat="device",
                              args={"batch": B, "maxlen": maxlen, "rows": 0,
                                    "pages": 0},
                              label="prefill"):
            logits, sub = self._prefill(self.params, sub, batch)
        self._count_pass(0)
        self._note_compile("prefill", self._prefill)
        for k in _POOL_KEYS:
            if k in sub:
                self.state[k] = sub[k]
        for k in per_seq:
            self.state[k] = self.state[k].at[:, [s.slot for s in seqs]].set(
                sub[k])
        return logits

    def prefill_chunk(self, seq, start: int, length: int) -> jnp.ndarray:
        """Run one prefill chunk of one sequence through the fixed-shape
        executable: tokens [1, W] right-padded, scalar position offset.
        Scatters the chunk K/V into the live pools (donated, in place)
        and returns the last-live-token logits [1, V] as a *device*
        array — the engine batches first-token sampling across the
        step's final chunks, so no host sync happens here."""
        toks, bt = self._chunk_inputs(seq.req.prompt, seq.block_ids, start,
                                      length)
        cache = cache_from_state(self.state)
        self.dispatches += 1
        with self.tracer.span("dispatch:chunk", cat="device",
                              args={"start": start, "length": length,
                                    "rows": 0, "pages": 0},
                              label="prefill_chunk"):
            logits, cache = self._prefill_chunk(
                self.params, cache, jnp.asarray(toks), jnp.asarray(bt),
                jnp.int32(start), jnp.int32(start + length))
        self.state.update(cache_to_state(cache))
        self._count_pass(0)
        self._note_compile("prefill_chunk", self._prefill_chunk)
        return logits

    def _chunk_inputs(self, prompt: Seq[int], block_ids: Seq[int],
                      start: int, length: int):
        """The chunk operands of one dispatch: the ``[1, W]`` right-padded
        tokens and the sequence's ``[1, MB]`` block row."""
        with self.tracer.span("inputs", cat="host"):
            toks = np.zeros((1, self.chunk_tokens), np.int32)
            toks[0, :length] = prompt[start:start + length]
            bt = np.zeros((1, self.mb), np.int32)
            bt[0, :len(block_ids)] = block_ids
        return toks, bt

    def _put_sampling(self, sampling: Dict[str, np.ndarray]):
        with self.tracer.span("inputs", cat="host"):
            return {k: jnp.asarray(v) for k, v in sampling.items()}

    def unified_step(self, tokens: np.ndarray,
                     sampling: Dict[str, np.ndarray], active: np.ndarray,
                     chunk_prompt: Seq[int], block_ids: Seq[int],
                     start: int, length: int) -> jnp.ndarray:
        """ONE donated device dispatch for a whole mixed engine iteration:
        a single decode step over the active slots, one prefill chunk of
        one sequence, and the per-row sampling for both.  Returns the
        ``[max_slots + 1]`` token buffer as a *device* array — the engine
        reads it back after the whole step's dispatches are in flight, so
        an admission burst of several chunks pipelines behind one sync.
        Rows [0, max_slots) are the decode slots' samples; row max_slots
        is the chunk's first token (meaningful only on final chunks)."""
        toks, bt = self._chunk_inputs(chunk_prompt, block_ids, start, length)
        sp = self._put_sampling(sampling)
        rows = int(np.count_nonzero(active))
        self.dispatches += 1
        with self.tracer.span("dispatch:unified", cat="device",
                              args={"start": start, "length": length,
                                    "rows": rows, "pages": self._pages(rows)},
                              label="unified_step"):
            out, self.state = self._unified(
                self.params, self.state, jnp.asarray(tokens), sp,
                jnp.asarray(active), jnp.asarray(toks), jnp.asarray(bt),
                jnp.int32(start), jnp.int32(start + length))
        self._count_pass(rows)
        self._note_compile("unified_step", self._unified)
        return out

    def unified_step_chained(self, prev_out, chain_idx: np.ndarray,
                             use_prev: np.ndarray, tokens: np.ndarray,
                             sampling: Dict[str, np.ndarray],
                             active: np.ndarray, chunk_prompt: Seq[int],
                             block_ids: Seq[int], start: int,
                             length: int) -> jnp.ndarray:
        """``unified_step`` for the async pipeline: the decode feed
        tokens are gathered ON DEVICE from ``prev_out`` — the previous
        dispatch's still-in-flight ``[max_slots + 1]`` output buffer —
        wherever ``use_prev`` is set (``chain_idx`` names the source
        row; row ``max_slots`` is the chunk sample).  Returns this
        dispatch's own ``[max_slots + 1]`` buffer as a device array the
        engine reads back one step later.  Non-donating (see __init__):
        the previous state stays alive until its readback."""
        toks, bt = self._chunk_inputs(chunk_prompt, block_ids, start, length)
        sp = self._put_sampling(sampling)
        if prev_out is None:
            prev_out = self.zero_prev
        rows = int(np.count_nonzero(active))
        self.dispatches += 1
        with self.tracer.span("dispatch:unified_chained", cat="device",
                              args={"start": start, "length": length,
                                    "rows": rows, "pages": self._pages(rows)},
                              label="unified_step_chained"):
            out, self.state = self._unified_chained(
                self.params, self.state, prev_out,
                jnp.asarray(chain_idx), jnp.asarray(use_prev),
                jnp.asarray(tokens), sp, jnp.asarray(active),
                jnp.asarray(toks), jnp.asarray(bt),
                jnp.int32(start), jnp.int32(start + length))
        self._count_pass(rows)
        self._note_compile("unified_step_chained", self._unified_chained)
        return out

    @staticmethod
    def _cache_size(fn) -> float:
        """Jit compile count via the wrapper's ``_cache_size`` (private
        jax API): NaN if a jax bump removed it, so gates skip with an
        API-drift notice instead of reading as a fake regression."""
        if not hasattr(fn, "_cache_size"):     # pragma: no cover - jax API
            return float("nan")
        return float(fn._cache_size())

    def prefill_compiles(self) -> float:
        """Compile count of the executable that actually runs prefill
        work: the unified step (which embeds the chunk path) in unified
        mode, else the fixed-shape chunk executable — 1 forever for
        either fixed-shape path; one per distinct (wave size, bucket)
        shape for the whole-prompt oracle (the recompile explosion the
        chunked path removes)."""
        if self.unified and self._unified is not None:
            return self.unified_compiles()
        fn = self._prefill_chunk if self._prefill_chunk is not None \
            else self._prefill
        return self._cache_size(fn)

    def unified_compiles(self) -> float:
        """Max compile count across the unified step executables (NaN
        when unified dispatch is off or the private jax cache API
        drifted).  The async engine runs mixed steps through the chained
        variant and the flush fallbacks through the donated one — each
        fixed-shape executable must compile exactly once, so a healthy
        run reads 1.0 whichever subset actually dispatched."""
        if self._unified is None:
            return float("nan")
        counts = [self._cache_size(self._unified)]
        if self._unified_chained is not None:
            counts.append(self._cache_size(self._unified_chained))
        return float(max(counts))

    # ------------------------------------------------------------ decode
    def decode(self, tokens: np.ndarray, rows: int) -> jnp.ndarray:
        """One per-token decode step for all slots; tokens: [max_slots],
        ``rows`` of them live (the slots ``sync_tables`` gave a length)."""
        self.dispatches += 1
        with self.tracer.span("dispatch:decode", cat="device",
                              args={"rows": rows, "pages": self._pages(rows)},
                              label="decode"):
            logits, self.state = self._decode(self.params, self.state,
                                              jnp.asarray(tokens))
        self._count_pass(rows)
        self._note_compile("decode", self._decode)
        return logits

    def megastep(self, tokens: np.ndarray, sampling: Dict[str, np.ndarray],
                 active: np.ndarray, n_steps: int) -> np.ndarray:
        """Dispatch one fused horizon; returns the [n_steps, max_slots]
        token buffer as numpy (the ONE host sync of the dispatch)."""
        sp = self._put_sampling(sampling)
        rows = int(np.count_nonzero(active))
        self.dispatches += 1
        with self.tracer.span("dispatch:megastep", cat="device",
                              args={"n_steps": int(n_steps), "rows": rows,
                                    "pages": self._pages(rows)},
                              label="megastep"):
            out, self.state = self._megastep(
                self.params, self.state, jnp.asarray(tokens), sp,
                jnp.asarray(active), jnp.int32(n_steps))
            with self.tracer.span("readback", cat="device"):
                out_np = np.asarray(out[:n_steps])
        self._count_pass(rows, int(n_steps))
        self._note_compile("megastep", self._megastep)
        return out_np

    def sample(self, logits, sampling: Dict[str, np.ndarray]) -> np.ndarray:
        """Per-slot sampling for the legacy loop / prefill first token.
        An optional "poison" row-bias (fault injection) and the
        non-finite guard flag ride through so the two-call oracle path
        gets the exact same protection as the fused executables."""
        self.dispatches += 1
        kw = {}
        if "poison" in sampling:
            kw["poison"] = jnp.asarray(sampling["poison"])
        with self.tracer.span("dispatch:sample", cat="device",
                              label="sample"):
            out = np.asarray(self._sample(
                logits, jnp.asarray(sampling["keys"]),
                jnp.asarray(sampling["counts"]),
                jnp.asarray(sampling["temps"]),
                jnp.asarray(sampling["top_ks"]),
                jnp.asarray(sampling["top_ps"]),
                guard=bool(self.rt.get("sampling_guard")), **kw))
        self._note_compile("sample", self._sample)
        return out

    # ------------------------------------------------------------ CoW
    def copy_cow(self, pairs: Seq[Tuple[int, int]]) -> None:
        """Resolve copy-on-write on device: block contents never visit the
        host. pairs: [(src_block, dst_block), ...]. Padded to a fixed
        ``max_slots`` length so ``copy_blocks`` compiles once, not once per
        CoW batch size. Padding entries are self-copies of the first src
        block: a pad index can never collide with a real dst (dst blocks
        are freshly allocated, src blocks are still live), so the scatter
        stays duplicate-free on every real destination."""
        pad = (pairs[0][0],) * (self.max_slots - len(pairs))
        src = np.asarray([p[0] for p in pairs] + list(pad), np.int32)
        dst = np.asarray([p[1] for p in pairs] + list(pad), np.int32)
        self.dispatches += 1
        # int8 mode: the scale rows ride along with the value blocks —
        # a fork that dropped them would dequantize its prefix with junk
        with self.tracer.span("dispatch:cow", cat="device",
                              args={"pairs": len(pairs)}, label="copy_cow"):
            for k in _POOL_KEYS:
                if k in self.state:
                    self.state[k] = copy_blocks(self.state[k], src, dst)
        self._note_compile("copy_cow", copy_blocks)

    # ------------------------------------------------------------ memory
    def kv_pool_bytes(self) -> int:
        """Device bytes held by the paged KV pools (values + scales)."""
        return sum(int(self.state[k].size) * self.state[k].dtype.itemsize
                   for k in _POOL_KEYS if k in self.state)

    def kv_bytes_per_token(self) -> float:
        """KV bytes per cached token position, across all attention layers
        (scales amortized over the block): the figure the int8 pool halves
        vs bf16 (~4x vs the f32 CPU pools)."""
        bs = self.cfg.paging.block_size
        return self.kv_pool_bytes() / float(self.num_blocks * bs)
