"""Paged KV-cache (paper §III.A 'Management of Shared Key-Value Vectors').

Two halves, mirroring vLLM on TPU:

* **Host side** — ``BlockAllocator``: pre-allocated fixed pool of block ids,
  free-list allocation, ref-counted blocks, prefix-hash reuse
  (copy-on-write), watermark admission. Pure Python, drives the scheduler.

* **Device side** — the pool itself is ONE dense array stacked over
  layers, ``[layers, num_blocks, kv_heads, block_size, head_dim]``
  (pre-allocated: the paper's "pre-allocate memory pools to minimize
  allocation overhead"), plus an int32 ``block_table [max_seqs,
  max_blocks_per_seq]``. Jitted scatter/gather ops below index the
  stacked pool directly; the Pallas kernels read the pool + table in
  place, one ``[block_size, head_dim]`` page tile per (block, head).
"""
from __future__ import annotations

import functools
import hashlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# --------------------------------------------------------------------------
# Host-side allocator
# --------------------------------------------------------------------------


class OutOfBlocksError(RuntimeError):
    pass


@dataclass
class _Block:
    ref: int = 0
    token_hash: Optional[bytes] = None   # set only for full, immutable blocks


class BlockAllocator:
    """Ref-counted fixed-pool allocator with prefix reuse.

    Prefix reuse: a *full* block of a prompt is content-addressed by the
    hash of (all tokens up to and including the block). A new request whose
    prompt shares that prefix gets the same physical block with ref+1 —
    the paper's "cache reuse strategy based on request features".
    """

    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_reuse: bool = True,
                 watermark_frac: float = 0.01):
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_reuse = enable_prefix_reuse
        self.watermark = max(1, int(num_blocks * watermark_frac))
        self._blocks = [_Block() for _ in range(num_blocks)]
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._hash_to_block: Dict[bytes, int] = {}
        self.stats = {"allocated": 0, "reused": 0, "freed": 0, "cow": 0}

    # -- basics ---------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    def can_allocate(self, n: int) -> bool:
        return self.num_free - n >= self.watermark

    def _alloc_raw(self) -> int:
        if not self._free:
            raise OutOfBlocksError("KV block pool exhausted")
        b = self._free.pop()
        self._blocks[b].ref = 1
        self._blocks[b].token_hash = None
        self.stats["allocated"] += 1
        return b

    def free(self, block_id: int) -> None:
        blk = self._blocks[block_id]
        assert blk.ref > 0, f"double free of block {block_id}"
        blk.ref -= 1
        if blk.ref == 0:
            if blk.token_hash is not None:
                self._hash_to_block.pop(blk.token_hash, None)
                blk.token_hash = None
            self._free.append(block_id)
            self.stats["freed"] += 1

    def free_sequence(self, block_ids: Sequence[int]) -> None:
        for b in block_ids:
            self.free(b)

    def fork_sequence(self, block_ids: Sequence[int]) -> List[int]:
        """Share a sequence's blocks with a fork (parallel sampling / beam
        candidates): every block's refcount is bumped, including a partial
        tail — the first divergent append on either fork triggers
        copy-on-write (``grow`` returns the source block for the device
        block-copy)."""
        for b in block_ids:
            assert self._blocks[b].ref > 0, f"fork of freed block {b}"
            self._blocks[b].ref += 1
        return list(block_ids)

    # -- prefix-aware prompt allocation ----------------------------------
    @staticmethod
    def _hash_prefix(tokens: Sequence[int]) -> bytes:
        return hashlib.blake2b(np.asarray(tokens, np.int32).tobytes(),
                               digest_size=16).digest()

    def allocate_prompt(self, tokens: Sequence[int],
                        register: bool = True) -> Tuple[List[int], int]:
        """Allocate blocks for a prompt. Returns (block_ids, num_reused_blocks).

        Full blocks are content-addressed and may be shared; the trailing
        partial block is always private.

        ``register=False`` still *looks up* (and shares) existing hashed
        blocks but does not content-address fresh ones — for callers that
        cannot guarantee the hashed content will ever land in the pool.
        The serving scheduler registers eagerly: a reusing prompt always
        rewrites the shared block bit-identically rather than trusting
        its contents, and ``free`` drops a block's hash entry the moment
        its refcount hits 0, so aborted or failed dispatches cannot leave
        stale prefix-cache entries behind.
        """
        n = len(tokens)
        n_full = n // self.block_size
        ids: List[int] = []
        reused = 0
        for i in range(n_full):
            h = self._hash_prefix(tokens[: (i + 1) * self.block_size])
            if self.enable_prefix_reuse and h in self._hash_to_block:
                b = self._hash_to_block[h]
                self._blocks[b].ref += 1
                ids.append(b)
                reused += 1
                continue
            b = self._alloc_raw()
            if register:
                self._blocks[b].token_hash = h
                self._hash_to_block[h] = b
            ids.append(b)
        if n % self.block_size or n == 0:
            ids.append(self._alloc_raw())
        self.stats["reused"] += reused
        return ids, reused

    def register_full_block(self, block_id: int,
                            tokens: Sequence[int]) -> None:
        """Content-address a block *after* allocation (register-on-write).

        ``allocate_prompt`` hashes only the full blocks of the tokens it
        is given — for a chunked admission, just the first chunk.  Blocks
        grown for continuation chunks become hashable only once the chunk
        that fills them has executed; the scheduler calls this with the
        prompt prefix through the block's last token.  No-ops when prefix
        reuse is off, when the block is already content-addressed (it was
        itself a reused prefix block), or when another live block owns
        the hash (first writer wins; we cannot retroactively dedupe a
        block that is already scattered into the pool).
        """
        if not self.enable_prefix_reuse:
            return
        blk = self._blocks[block_id]
        assert blk.ref > 0, f"register_full_block of freed block {block_id}"
        if blk.token_hash is not None:
            return
        h = self._hash_prefix(tokens)
        if h in self._hash_to_block:
            return
        blk.token_hash = h
        self._hash_to_block[h] = block_id

    def ref(self, block_id: int) -> int:
        """Current refcount of a block (0 == free)."""
        return self._blocks[block_id].ref

    def audit(self) -> Dict[str, int]:
        """Leak/consistency snapshot for tests and ``engine.health()``.

        live_blocks + num_free must equal num_blocks; every hash entry
        must map to a live block that owns that hash (a dangling entry
        would serve stale prefix-cache hits).  Raises AssertionError on
        inconsistency instead of returning a lie.
        """
        live = sum(1 for b in self._blocks if b.ref > 0)
        assert live + self.num_free == self.num_blocks, \
            f"block accounting broken: {live} live + {self.num_free} " \
            f"free != {self.num_blocks}"
        for h, bid in self._hash_to_block.items():
            blk = self._blocks[bid]
            assert blk.ref > 0, f"hash entry -> freed block {bid}"
            assert blk.token_hash == h, \
                f"hash entry -> block {bid} owning a different hash"
        return {"live_blocks": live, "free_blocks": self.num_free,
                "hash_entries": len(self._hash_to_block)}

    def grow_prefill(self, block_ids: List[int], start_pos: int,
                     num_tokens: int, tokens: Sequence[int]
                     ) -> Tuple[List[int], int]:
        """``grow`` for a prefill chunk, with content-addressed reuse.

        Any *new* block the chunk will completely cover (the chunk writes
        all ``block_size`` of its slots) may instead share an existing
        block whose registered hash matches ``tokens`` up to that block's
        end — the continuation-chunk counterpart of ``allocate_prompt``'s
        prefix reuse.  Safe because the chunk then rewrites the shared
        block with bit-identical content (same tokens, same absolute
        positions, deterministic projections — and a fully-covered block
        is always a *fresh* quantize in int8 mode, never a boundary
        merge).  Partially-covered blocks (the chunk's tail) stay
        private raw allocations.  Prefill chunks never CoW: ``start_pos``
        is this sequence's own computed length, so the current tail is
        private.  Returns (block_ids, num_reused_blocks).
        """
        assert not self._tail_needs_cow(block_ids, start_pos)
        if self.blocks_needed(block_ids, start_pos, num_tokens) \
                > self.num_free:
            raise OutOfBlocksError("KV block pool exhausted")
        block_ids = list(block_ids)
        end = start_pos + num_tokens
        reused = 0
        while len(block_ids) * self.block_size < end:
            i = len(block_ids)                       # next block index
            blk_end = (i + 1) * self.block_size
            if self.enable_prefix_reuse and blk_end <= end:
                h = self._hash_prefix(tokens[:blk_end])
                b = self._hash_to_block.get(h)
                if b is not None:
                    self._blocks[b].ref += 1
                    block_ids.append(b)
                    reused += 1
                    continue
            block_ids.append(self._alloc_raw())
        self.stats["reused"] += reused
        return block_ids, reused

    def append_slot(self, block_ids: List[int], seq_len: int) -> Tuple[List[int], Optional[int]]:
        """Ensure capacity for one more token at position seq_len.

        Returns (block_ids, copied_from): if the tail block is shared
        (ref > 1) it is copy-on-write'd; copied_from is the old block id the
        device must copy data out of, else None.
        """
        block_ids, cow = self.grow(block_ids, seq_len, 1)
        return block_ids, (cow[0] if cow else None)

    def _tail_needs_cow(self, block_ids: Sequence[int],
                        start_pos: int) -> bool:
        """A write at start_pos lands in the current tail block and that
        tail is shared — the single predicate both ``blocks_needed`` and
        ``grow`` must agree on (the fused planner budgets with the former
        and relies on the latter not raising)."""
        return bool(start_pos % self.block_size and block_ids
                    and self._blocks[block_ids[-1]].ref > 1)

    def blocks_needed(self, block_ids: Sequence[int], start_pos: int,
                      num_tokens: int) -> int:
        """New blocks ``grow`` would consume for writes at positions
        [start_pos, start_pos + num_tokens), including a CoW replacement."""
        end = start_pos + num_tokens
        n = max(0, -(-end // self.block_size) - len(block_ids))
        if self._tail_needs_cow(block_ids, start_pos):
            n += 1                                   # CoW'd tail is a new block
        return n

    def grow(self, block_ids: List[int], start_pos: int,
             num_tokens: int = 1
             ) -> Tuple[List[int], Optional[Tuple[int, int]]]:
        """Ensure capacity for ``num_tokens`` writes starting at start_pos.

        Bulk form of ``append_slot`` for the fused decode horizon: allocates
        every block the horizon will touch in one host pass. Returns
        (block_ids, cow): cow is a (src_block, dst_block) pair the device
        must copy (shared tail copy-on-write), else None. Only the current
        tail can need CoW: blocks past it are freshly allocated and private.

        Atomic: capacity is checked up front, so a raise leaves both the
        allocator and the caller's block list untouched.
        """
        if self.blocks_needed(block_ids, start_pos, num_tokens) \
                > self.num_free:
            raise OutOfBlocksError("KV block pool exhausted")
        cow = None
        if self._tail_needs_cow(block_ids, start_pos):
            tail = block_ids[-1]                    # CoW: shared full-prefix tail
            nb = self._alloc_raw()
            self.free(tail)
            block_ids = block_ids[:-1] + [nb]
            cow = (tail, nb)
            self.stats["cow"] += 1
        else:
            block_ids = list(block_ids)
        end = start_pos + num_tokens
        while len(block_ids) * self.block_size < end:
            block_ids.append(self._alloc_raw())
        return block_ids, cow

    def utilization(self) -> float:
        return 1.0 - self.num_free / self.num_blocks


# --------------------------------------------------------------------------
# Device-side pool ops (jit-friendly, used by serve_step and the ref path)
# --------------------------------------------------------------------------


def make_kv_pool(num_layers: int, num_blocks: int, block_size: int,
                 num_kv_heads: int, head_dim: int, dtype=jnp.bfloat16):
    """Pre-allocated pool: (k_pool, v_pool) each [L, NB, KV, BS, D].

    Head-major pages: each (layer, block, head) is one dense ``[BS, D]``
    tile, which the paged kernels read in place from the stacked pool
    (no per-layer slice or relayout) and which is aligned to the TPU's
    tiling for any KV count once BS is a multiple of the sublane tile.
    """
    shape = (num_layers, num_blocks, num_kv_heads, block_size, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def pages_to_tokens(pages: jnp.ndarray) -> jnp.ndarray:
    """[..., n, KV, BS, D] pages -> the token-major [..., n * BS, KV, D]
    view the contiguous attention references take."""
    *lead, n, kv, bs, d = pages.shape
    return jnp.swapaxes(pages, -3, -2).reshape(*lead, n * bs, kv, d)


def write_decode_kv(pool: jnp.ndarray, layer, k_new: jnp.ndarray,
                    block_table: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """Scatter one token's K (or V) per sequence into the paged pool.

    pool: [L, NB, KV, BS, D]; k_new: [B, KV, D]; block_table: [B, MB];
    positions: [B] absolute position of the new token. Negative positions
    (inactive decode slots, seq_len == 0) are dropped instead of wrapping
    around and corrupting a live block.  Indexes the stacked pool
    directly: an in-place scatter, no per-layer slice.
    """
    bs = pool.shape[3]
    valid = positions >= 0
    pos = jnp.maximum(positions, 0)
    blk = jnp.take_along_axis(block_table, (pos // bs)[:, None], axis=1)[:, 0]
    blk = jnp.where(valid, blk, pool.shape[1])                 # OOB -> dropped
    off = pos % bs
    return pool.at[layer, blk, :, off].set(k_new.astype(pool.dtype),
                                           mode="drop")


def write_prefill_kv(pool: jnp.ndarray, layer, k: jnp.ndarray,
                     block_table: jnp.ndarray, ctx_lens: jnp.ndarray,
                     pos_offset=0) -> jnp.ndarray:
    """Scatter a prompt (or prompt chunk) K/V into the pool.

    pool: [L, NB, KV, BS, D]; k: [B, S, KV, D] (padded); k[:, i] holds
    position pos_offset + i; only absolute positions < ctx_lens are
    written (the rest are routed out of bounds and dropped).
    """
    S = k.shape[1]
    bs = pool.shape[3]
    pos = pos_offset + jnp.arange(S)
    blk = block_table[:, pos // bs]                       # [B, S]
    off = pos % bs                                         # [S]
    valid = pos[None, :] < ctx_lens[:, None]               # [B, S]
    blk = jnp.where(valid, blk, pool.shape[1])             # OOB -> dropped
    return pool.at[layer, blk, :, off[None, :]].set(k.astype(pool.dtype),
                                                    mode="drop")


def gather_kv_bounded(pool: jnp.ndarray, layer, block_table: jnp.ndarray,
                      max_len: int, num_live_blocks) -> jnp.ndarray:
    """``gather_kv`` that only touches the first ``num_live_blocks``
    (a *traced* count) table entries: the returned ``[B, max_len, ...]``
    view has zeros past the live pages instead of stale pool contents.

    The output shape stays static (``max_len``) — what becomes bounded is
    the *work*: a ``fori_loop`` with a dynamic trip count copies one page
    per live table entry, so a chunk-prefill gather costs
    O(ceil(total_len / BS)) page reads instead of O(table capacity) per
    layer per chunk.  Downstream attention masks every position past the
    live length to -inf before the softmax max, so zeros vs stale data is
    invisible in the output — the full-capacity gather path and this one
    are bitwise interchangeable.
    """
    bs = pool.shape[3]
    nb = -(-max_len // bs)
    B = block_table.shape[0]
    buf = jnp.zeros((B, nb) + pool.shape[2:], pool.dtype)

    def body(j, buf):
        page = pool[layer, block_table[:, j]]          # [B, KV, bs, D]
        return jax.lax.dynamic_update_slice_in_dim(buf, page[:, None], j,
                                                   axis=1)

    buf = jax.lax.fori_loop(
        0, jnp.minimum(jnp.asarray(num_live_blocks, jnp.int32), nb),
        body, buf)
    return pages_to_tokens(buf)[:, :max_len]


def gather_kv(pool: jnp.ndarray, layer, block_table: jnp.ndarray,
              max_len: int) -> jnp.ndarray:
    """Gather a contiguous [B, max_len, KV, D] view (reference path only).

    ``max_len`` need not be a block multiple: the tail partial block is
    gathered too and the result sliced back to exactly max_len rows.
    """
    bs = pool.shape[3]
    nb = -(-max_len // bs)                                 # ceil: keep the tail
    blk = block_table[:, :nb]                              # [B, nb]
    return pages_to_tokens(pool[layer, blk])[:, :max_len]


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_blocks(pool: jnp.ndarray, src: jnp.ndarray,
                dst: jnp.ndarray) -> jnp.ndarray:
    """Device-side block copy for the allocator's copy-on-write path.

    pool: [L, NB, ...] (a value pool [L, NB, KV, BS, D] or a scale pool
    [L, NB, KV]); src/dst: [n] int32 physical block ids. Copies
    pool[:, src[i]] -> pool[:, dst[i]] for every layer without the contents
    ever round-tripping through host numpy. Donated: updates in place.
    """
    return pool.at[:, dst].set(pool[:, src])


# --------------------------------------------------------------------------
# Attention-free (SSM) state pool — paper's memory-pool insight, degenerate
# block table (see DESIGN.md §5): one slot per sequence, O(1) state.
# --------------------------------------------------------------------------


def make_state_pool(num_layers: int, max_seqs: int, d_inner: int,
                    ssm_state: int, conv_width: int, dtype=jnp.float32):
    """(ssm_state_pool [L, B, d_inner, N], conv_state_pool [L, B, d_inner, W-1])."""
    return (jnp.zeros((num_layers, max_seqs, d_inner, ssm_state), dtype),
            jnp.zeros((num_layers, max_seqs, d_inner, conv_width - 1), dtype))
