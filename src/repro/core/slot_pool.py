"""Unified KV slot-pool: the static cache as a pool of sequence slots.

The paper's §4.1.2 static-shape discipline allocates ONE cache of shape
[slots, max_len, ...] and never reallocates it. Continuous batching
(Orca/vLLM-style) reinterprets that same allocation as ``slots``
*independent* sequence slots: each slot carries its own request, its own
``lengths`` counter, and can be evicted + refilled without touching its
neighbours — because every per-slot cache op in models/attention.py
(``write_decode``/``write_extend``/``write_slot_row``) and every validity
mask is already row-wise.

``SlotPool`` owns the pooled cache plus a host-side free-list. All device
updates are donated jitted programs (kv_cache.write_slot / reset_slots),
so admission and eviction replay two tiny compiled executables and the
pool's buffers are updated in place — the engine/scheduler/serve layers
above never see a reallocation.

``BlockPool`` is the paged variant (Fig 1: KV capacity, not FLOPs, bounds
the decode batch): the same slot free-list, but K/V storage is a shared
pool of fixed-size physical *blocks* addressed through per-slot block
tables, so a slot only ever reserves the blocks its tokens actually
occupy — see core/kv_cache.py ("Block-table addressing") for the full
contract. Both pools expose the same acquire/assign/evict/reset surface,
so the scheduler A/B isolates the allocation policy.
"""
from __future__ import annotations

import heapq
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hotpath import hot_path
from repro.core import kv_cache
from repro.models.registry import Model


class _PoolBase:
    """Slot accounting shared by both pools: a min-heap free-list (acquire
    is lowest-index-first in O(log slots) — the evict-time full re-sort it
    replaced was O(slots log slots) per eviction) plus the occupancy /
    reservation metrics the scheduler A/B reads. Subclasses own ``cache``
    and the assign/evict storage logic."""

    def __init__(self, slots: int):
        if slots < 1:
            raise ValueError("pool needs at least one slot")
        self.slots = slots
        self._free: List[int] = list(range(slots))  # min-heap: pop -> lowest

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return self.slots - len(self._free)

    @property
    def occupancy(self) -> float:
        """Fraction of slots doing real work this step (1 - idle share)."""
        return self.n_active / self.slots

    @property
    def reserved_bytes(self) -> int:
        """Bytes the pool holds allocated regardless of use (Fig 1 metric)."""
        return kv_cache.cache_bytes(self.cache)

    def acquire(self) -> Optional[int]:
        """Claim a free slot (lowest index first), or None if full."""
        return heapq.heappop(self._free) if self._free else None


class SlotPool(_PoolBase):
    """Fixed pool of ``slots`` sequence slots backed by one static cache.

    Invariants:
    - a slot is either on the free-list or assigned to exactly one request;
    - the HOST free-list is the sole source of truth for slot liveness:
      ``evict`` zeroes a freed slot's ``lengths``, but the pool-wide decode
      step still increments every row's counter, so a free slot's device
      counter drifts upward until ``assign`` overwrites it (its garbage
      compute is the dead padding continuous batching shrinks — never
      derive liveness from the device-side ``lengths``);
    - ``assign`` replaces a slot's entire cache row (K/V buffers *and*
      length counter) with a freshly prefilled single-sequence row.
    """

    def __init__(self, model: Model, slots: int, max_len: int):
        super().__init__(slots)
        self.model = model
        self.max_len = max_len
        self.cache: Any = model.init_cache(slots, max_len)

    # ---- device-side slot ops (donated, in-place) ------------------------
    def assign(self, slot: int, row_cache: Any, length: Optional[int] = None) -> None:
        """Install a prefilled single-sequence cache (leaves [1, ...]) into
        ``slot``. The row's ``lengths[0]`` becomes the slot's counter
        (``length`` is accepted for BlockPool signature parity)."""
        self.cache = kv_cache.write_slot(self.cache, row_cache, jnp.int32(slot))

    def evict(self, slot: int) -> None:
        """Finish a slot: zero its length and return it to the free-list."""
        mask = jnp.zeros((self.slots,), bool).at[slot].set(True)
        self.cache = kv_cache.reset_slots(self.cache, mask)
        heapq.heappush(self._free, slot)

    @hot_path
    def sync(self) -> None:
        """No host-side tables to flush (BlockPool signature parity)."""

    def truncate(self, slot: int, kv_len: int) -> None:
        """Nothing to release: contiguous slots reserve their whole row,
        so a speculative rewind is the pool-wide ``lengths`` reset the
        scheduler already ships (BlockPool signature parity)."""

    def reset(self) -> None:
        """Evict everything (serve-loop restart)."""
        self.cache = kv_cache.reset_slots(self.cache, jnp.ones((self.slots,), bool))
        self._free = list(range(self.slots))


class BlockPool(_PoolBase):
    """Paged KV pool: ``slots`` sequence slots over ``num_blocks`` shared
    physical blocks of ``block_size`` tokens each.

    Storage is ONE static ``[num_blocks, block_size, ...]`` K/V allocation
    per layer; a slot's logical positions map to physical blocks through
    its row of the host block table (shipped to the device by ``sync``).
    Blocks are REFCOUNTED so slot-group decoding profiles (beam groups)
    can share common-prefix blocks and permute ownership without device
    copies — see core/kv_cache.py ("Decoding profiles in the pool").
    Invariants (locked down by tests/test_paged.py + tests/test_profiles.py):

    - physical block 0 is the reserved garbage sink: never on the
      free-list, never in a live slot's table; freed slots' zeroed table
      rows route their pool-wide decode writes into it;
    - every block in 1..num_blocks-1 is in exactly one of THREE states:
      **free** (on the block free-list, refcount 0, not cached), **held**
      (owned by >= 1 slots and/or held by the cross-request prefix cache),
      with refcount equal to the number of owning slots PLUS one if the
      prefix cache holds it (``_cached``); a cached block with refcount 1
      is *cached-only* — resident in device memory but owned by nobody,
      the reclaimable overflow the prefix cache's LRU eviction returns to
      the free-list under pressure (without ``share``/``permute_group``/
      ``cache_ref`` this degenerates to the old exactly-one-owner rule);
    - a block is only ever WRITTEN while its refcount is 1: the write
      cursor's block is unshared copy-on-write by ``ensure_writable``,
      and fully-written shared prefix blocks are never revisited;
    - ``evict`` drops one reference per owned block; a block returns to
      the free-list when its last owner lets go;
    - both free-lists are min-heaps: acquire order stays lowest-first;
    - ``num_blocks - 1 >= max_blocks`` so one worst-case single-stream
      request always fits — the scheduler's preemption ladder terminates
      because the oldest request can always run alone (for an S-stream
      group the scheduler enforces ``S * max_blocks`` at submit).
    """

    def __init__(
        self,
        model: Model,
        slots: int,
        max_len: int,
        *,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
    ):
        super().__init__(slots)
        if block_size < 1 or block_size > max_len:
            raise ValueError("need 1 <= block_size <= max_len")
        cfg = model.config
        if getattr(cfg, "sliding_window", None):
            raise NotImplementedError("paged pool unsupported on ring/window caches")
        if getattr(cfg, "scan_layers", False):
            raise NotImplementedError("paged pool unsupported with scan_layers")
        if getattr(cfg, "encdec", None) is not None:
            # enc-dec caches carry per-SLOT cross-attention rows (encoder
            # frames), which have no block-granular layout; enc-dec profiles
            # serve through the contiguous SlotPool instead
            raise NotImplementedError(
                "paged pool unsupported for enc-dec cross-attention caches"
            )
        self.model = model
        self.max_len = max_len
        self.block_size = block_size
        self.max_blocks = -(-max_len // block_size)  # ceil: worst case / slot
        if num_blocks is None:
            # parity default: every slot can hold a worst-case request
            num_blocks = slots * self.max_blocks + 1
        if num_blocks - 1 < self.max_blocks:
            raise ValueError(
                f"num_blocks={num_blocks} cannot fit one worst-case request "
                f"({self.max_blocks} blocks + sink block 0)"
            )
        self.num_blocks = num_blocks

        cache = model.init_cache(num_blocks, block_size)
        cache["lengths"] = jnp.zeros((slots,), jnp.int32)  # per SLOT, not block
        self.block_tables = np.zeros((slots, self.max_blocks), np.int32)
        cache["block_tables"] = jnp.asarray(self.block_tables)
        self.cache: Any = cache

        self._free_blocks: List[int] = list(range(1, num_blocks))  # heap; 0=sink
        self._owned: List[List[int]] = [[] for _ in range(slots)]
        self._ref = np.zeros((num_blocks,), np.int32)  # holders per block
        # third block state (core/prefix_cache.py): True while the
        # cross-request prefix cache holds the block. A cached block
        # carries ONE extra refcount on top of its slot owners, so the
        # ordinary evict/truncate decrefs can never free it out from
        # under the trie; cache_unref (LRU reclaim) drops that bit.
        self._cached = np.zeros((num_blocks,), bool)
        self._bt_dirty = False
        self.n_cow_copies = 0  # copy-on-write unshares (device block copies)

    # ---- block accounting ------------------------------------------------
    @property
    def n_free_blocks(self) -> int:
        return len(self._free_blocks)

    @property
    def n_used_blocks(self) -> int:
        return (self.num_blocks - 1) - len(self._free_blocks)

    @property
    def block_occupancy(self) -> float:
        """Fraction of allocatable blocks currently owned by a slot."""
        return self.n_used_blocks / max(self.num_blocks - 1, 1)

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cached tokens."""
        return max(1, -(-n_tokens // self.block_size))

    def owned_blocks(self, slot: int) -> List[int]:
        return list(self._owned[slot])

    @property
    def n_cached_blocks(self) -> int:
        """Blocks the cross-request prefix cache currently holds (shared
        with slot owners or cached-only)."""
        return int(self._cached.sum())

    @property
    def n_reclaimable_blocks(self) -> int:
        """Cached blocks nobody owns (pool refcount 1 = the cache's own
        reference): exactly the blocks the prefix cache's LRU reclaim can
        return to the free-list, leaf chain by leaf chain — the
        admission gate counts them as free-list overflow."""
        return int((self._cached & (self._ref == 1)).sum())

    # ---- slot lifecycle --------------------------------------------------
    def assign(self, slot: int, row_cache: Any, length: int) -> None:
        """Admit a prefilled dense row (leaves [1, S_row, ...]) into
        ``slot``: allocate blocks for its ``length`` prompt tokens and copy
        them block by block (one compiled append_block executable)."""
        need = self.blocks_for(length)
        if need > self.max_blocks:
            raise ValueError(f"prompt of {length} tokens exceeds max_len")
        if need > len(self._free_blocks):
            raise RuntimeError("out of KV blocks (admission must gate on n_free_blocks)")
        assert not self._owned[slot], "assign into a slot that still owns blocks"
        for j in range(need):
            phys = heapq.heappop(self._free_blocks)
            self._ref[phys] = 1
            self._owned[slot].append(phys)
            self.block_tables[slot, j] = phys
            self.cache["layers"] = kv_cache.append_block(
                self.cache["layers"], row_cache["layers"],
                jnp.int32(phys), jnp.int32(j * self.block_size),
            )
        self._bt_dirty = True
        self.cache = kv_cache.set_slot_length(
            self.cache, jnp.int32(slot), jnp.int32(length)
        )

    def ensure(self, slot: int, kv_len: int) -> bool:
        """Grow ``slot`` until it owns the block its next write (logical
        position ``kv_len``) lands in. Host-only: a growth block becomes
        readable one position at a time as the validity mask advances, so
        no device copy or clear is needed. Returns False when the pool is
        out of blocks (caller applies back-pressure / preemption)."""
        needed = kv_len // self.block_size + 1
        while len(self._owned[slot]) < needed:
            if not self._free_blocks:
                return False
            phys = heapq.heappop(self._free_blocks)
            self._ref[phys] = 1
            j = len(self._owned[slot])
            self._owned[slot].append(phys)
            self.block_tables[slot, j] = phys
            self._bt_dirty = True
        return True

    def ensure_writable(self, slot: int, kv_len: int) -> bool:
        """``ensure`` plus copy-on-write: the block the next write lands in
        (logical position ``kv_len``) must be EXCLUSIVELY owned before the
        pool-wide step scatters into it, or a sibling stream sharing it
        would see the write. All but the last owner get a fresh block and
        one block-sized donated device copy (``kv_cache.copy_block``); the
        shared prefix blocks BEFORE the write cursor stay shared. Returns
        False when out of blocks (caller applies back-pressure)."""
        if not self.ensure(slot, kv_len):
            return False
        j = kv_len // self.block_size
        phys = int(self._owned[slot][j])
        if self._ref[phys] <= 1:
            return True
        if not self._free_blocks:
            return False
        fresh = heapq.heappop(self._free_blocks)
        self._ref[fresh] = 1
        self._ref[phys] -= 1
        self._owned[slot][j] = fresh
        self.block_tables[slot, j] = fresh
        self._bt_dirty = True
        self.cache["layers"] = kv_cache.copy_block(
            self.cache["layers"], jnp.int32(phys), jnp.int32(fresh)
        )
        self.n_cow_copies += 1
        return True

    # ---- cross-request prefix sharing (core/prefix_cache.py) -------------
    def adopt(self, slot: int, blocks: List[int], n_tokens: int) -> None:
        """Admission-time cache hit: attach ``blocks`` (the trie's matched
        full prompt blocks, logical order) to empty ``slot``'s table via
        refcounted sharing — the paged-pool primitive behind near-free
        prefill. The device length counter is pinned to ``n_tokens`` (=
        ``len(blocks) * block_size``) immediately so any pool-wide decode
        step that runs before the suffix's first chunk writes its garbage
        at positions >= the adopted span (block indices past the adopted
        blocks: sink or private growth blocks), never INSIDE a shared
        cached block. Adopted blocks are never written by this slot at
        all — chunked prefill resumes at the first uncached token, and
        every later write lands at a strictly higher logical position —
        so no copy-on-write is ever needed on the hit path."""
        assert not self._owned[slot], "adopt into a slot that still owns blocks"
        assert n_tokens == len(blocks) * self.block_size
        for j, phys in enumerate(blocks):
            assert self._cached[phys], "adopting a block the cache dropped"
            self._ref[phys] += 1
            self._owned[slot].append(phys)
            self.block_tables[slot, j] = phys
        self._bt_dirty = True
        self.cache = kv_cache.set_slot_length(
            self.cache, jnp.int32(slot), jnp.int32(n_tokens)
        )

    def cache_ref(self, phys: int) -> None:
        """Refcount handoff, insert half: the prefix cache takes its own
        reference on a block a finishing slot still owns — called BEFORE
        the slot's eviction decref, so the block moves owned -> cached
        without ever visiting the free-list."""
        assert self._ref[phys] >= 1, "cache_ref on an unowned block"
        assert not self._cached[phys], "block already cached"
        self._cached[phys] = True
        self._ref[phys] += 1

    def cache_unref(self, phys: int) -> None:
        """Drop the prefix cache's reference (LRU reclaim / trie reset):
        the block returns to the free-list iff no slot still owns it."""
        assert self._cached[phys], "cache_unref on an uncached block"
        self._cached[phys] = False
        self._ref[phys] -= 1
        if self._ref[phys] == 0:
            heapq.heappush(self._free_blocks, phys)

    def is_sole_cached_ref(self, phys: int) -> bool:
        """True while the prefix cache is ``phys``'s only holder — the
        reclaimability test of the trie's LRU eviction."""
        return bool(self._cached[phys]) and self._ref[phys] == 1

    def truncate(self, slot: int, kv_len: int) -> None:
        """Release the block-table suffix a rejected speculative window
        leaves behind: keep exactly the blocks through the one logical
        position ``kv_len`` (the slot's next write) lands in — the same
        convention as :meth:`ensure`, so accept-then-truncate composes
        with the next step's growth — and return the rest to the
        free-list. Host-only, mirroring ``ensure``'s growth direction:
        the stale K/V inside the dropped (and kept-partial-tail) blocks
        is masked by the validity window and overwritten one position at
        a time on reuse, so NO device zeroing program runs — rewind
        costs a table edit, never cache traffic. Shared blocks (a
        sibling stream still owns them) only drop a reference."""
        keep = kv_len // self.block_size + 1
        owned = self._owned[slot]
        while len(owned) > keep:
            phys = owned.pop()
            self.block_tables[slot, len(owned)] = 0
            self._ref[phys] -= 1
            if self._ref[phys] == 0:
                heapq.heappush(self._free_blocks, phys)
            self._bt_dirty = True

    def share(self, dst: int, src: int) -> None:
        """Admit ``dst`` as a copy-free clone of ``src``: same block table,
        every shared block's refcount bumped (common-prefix sharing for
        prefix-shared slot groups — beams prefill once). ``dst`` must not
        own blocks; the device length counter is copied too."""
        assert not self._owned[dst], "share into a slot that still owns blocks"
        self._owned[dst] = list(self._owned[src])
        for phys in self._owned[dst]:
            self._ref[phys] += 1
        self.block_tables[dst, :] = self.block_tables[src, :]
        self._bt_dirty = True
        self.cache = kv_cache.set_slot_length(
            self.cache, jnp.int32(dst), self.cache["lengths"][src]
        )

    def permute_group(self, slots: List[int], parent: np.ndarray) -> None:
        """Beam reorder as pure host-side index manipulation: stream ``i``
        of the group (pool slot ``slots[i]``) continues from stream
        ``parent[i]``'s cache. Children share the parent's physical blocks
        (refcounts up), orphaned blocks return to the free-list — NO device
        KV gather or copy runs here; the next write's block is unshared
        lazily by ``ensure_writable``."""
        old = [self._owned[s] for s in slots]
        # references first: a block both dropped and re-adopted must never
        # transit through the free-list
        for i in range(len(slots)):
            for phys in old[int(parent[i])]:
                self._ref[phys] += 1
        for blks in old:
            for phys in blks:
                self._ref[phys] -= 1
                if self._ref[phys] == 0:
                    heapq.heappush(self._free_blocks, phys)
        for i, s in enumerate(slots):
            src = old[int(parent[i])]
            self._owned[s] = list(src)
            self.block_tables[s, :] = 0
            self.block_tables[s, : len(src)] = src
        self._bt_dirty = True

    def evict(self, slot: int) -> None:
        """Finish (or preempt) a slot: one reference dropped per owned
        block (a block returns to the free-list when its LAST owner lets
        go), the table row is zeroed (future garbage writes hit the sink
        block), and the length counter is zeroed on device."""
        for phys in self._owned[slot]:
            self._ref[phys] -= 1
            if self._ref[phys] == 0:
                heapq.heappush(self._free_blocks, phys)
        self._owned[slot] = []
        self.block_tables[slot, :] = 0
        self._bt_dirty = True
        heapq.heappush(self._free, slot)
        mask = jnp.zeros((self.slots,), bool).at[slot].set(True)
        self.cache = kv_cache.free_blocks(self.cache, mask)

    @hot_path
    def sync(self) -> None:
        """Ship the host block table to the device if it changed since the
        last decode step (one tiny [slots, max_blocks] int32 transfer),
        committed where the old table was: a step keys its compile on
        whether its inputs are committed, so the table keeps its placement
        whether or not it changed."""
        if self._bt_dirty:
            old = self.cache["block_tables"]
            self.cache["block_tables"] = (
                jax.device_put(self.block_tables, old.sharding)
                if old.committed else jnp.asarray(self.block_tables))
            self._bt_dirty = False

    def reset(self) -> None:
        for slot in range(self.slots):
            self._owned[slot] = []
        self.block_tables[:, :] = 0
        self._free = list(range(self.slots))
        self._free_blocks = list(range(1, self.num_blocks))
        self._ref[:] = 0
        self._cached[:] = False  # a stale PrefixCache must be reset with us
        self._bt_dirty = True
        self.cache = kv_cache.free_blocks(
            self.cache, jnp.ones((self.slots,), bool)
        )
        self.sync()
