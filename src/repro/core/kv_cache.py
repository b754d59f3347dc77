"""KV-cache management: the paper's §4.1.2 static-cache discipline plus the
Obs #4 beam-search reorder lever.

The cache layout itself lives with the models (models/attention.py etc.);
this module owns the cross-cutting operations:

- ``reorder``          — beam-search KV reorder as a batch-axis gather. The
                         jitted variant donates the cache so XLA aliases
                         input/output buffers — the TPU analogue of the
                         paper's ``torch.Tensor.copy_`` fix (no fresh
                         allocation + fusable into the step program).
- ``reorder_realloc``  — the paper's *unoptimized* ``index_select``
                         behavior: forces a fresh buffer each step
                         (``engine.generate_beam(donate_reorder=False)``).
- ``rewind``           — speculative-decoding rollback: shrink ``lengths``
                         (stale entries beyond are overwritten/masked).
- ``cache_bytes``      — memory accounting per Fig 1.

Block-table addressing vs the §4.1.2 contiguous discipline
----------------------------------------------------------
The paper's static-cache discipline reserves ``max_len`` tokens per
sequence up front so every decode step replays one executable. Under the
serving pool that reservation is ``pad_to + max_new_cap`` tokens per slot
— reserved-but-unused memory whenever a request stops early, and Fig 1
shows KV capacity (not FLOPs) is what bounds the decode batch. The paged
variant (vLLM-style, arXiv:2407.09111) keeps the static-shape discipline
but drops the per-slot reservation:

- ONE static K/V allocation per layer, shaped ``[num_blocks, block_size,
  heads, dim]`` — physical *blocks*, not per-slot rows;
- a per-slot **block table** ``[slots, max_blocks]`` int32 mapping logical
  block ``t // block_size`` to a physical block id. The table is tiny,
  lives in host numpy, and is shipped to the device each step — the
  compiled executables never change shape as slots grow or shrink;
- physical block 0 is a reserved **garbage sink**: a freed slot's table
  rows are zeroed, so the pool-wide decode step's write for that slot
  lands in block 0 and can never corrupt a live neighbour (the paged
  analogue of the contiguous pool's "dead rows decode garbage" rule);
- freed blocks need no device-side clearing: a block is only re-readable
  after its new owner's validity mask covers the positions it rewrote
  (growth blocks are allocated exactly when the write cursor enters them).

Ops: ``append_block`` copies one block-sized chunk of a prefilled dense
row into a physical block (donated; block id and source offset are traced
so one executable serves every copy); ``free_blocks`` zeroes freed slots'
length counters (the block table itself is host state); ``set_slot_length``
installs a newly admitted slot's counter.

Chunked prefill over block tables
---------------------------------
The unchunked paged admission path still materializes a dense
``pad_to``-row per prompt (``engine.prefill``) and then copies it into
blocks via ``append_block`` — a whole prefill program run BETWEEN decode
steps, stalling every resident request. Chunked prefill
(core/prefill.py + ``engine.mixed_step``) removes both the stall and the
dense row:

- admission enqueues a *chunk cursor*; each pool-wide mixed step carries
  up to ``prefill_budget`` prompt tokens alongside every live decode
  token — ONE compiled executable, so admission rides the step instead
  of freezing it;
- the chunk's K/V goes from the layer's projections straight into the
  slot's physical blocks (``models/attention.paged_write_chunk``): per
  lane ``j`` the logical position is ``lengths[slot] + j``, mapping
  through the same block table as decode writes — no ``pad_to`` row, no
  ``append_block`` copy, no shape change;
- lanes past a slot's ``t_new`` (a final partial chunk's padding, idle
  rows) are routed to the reserved sink block 0, exactly like freed
  slots' garbage decode writes;
- the mixed step PINS every row's ``lengths`` counter from the
  scheduler's host state (decode kv length / chunk cursor / 0 for free
  rows) inside its own executable before writing, then advances it by
  ``t_new``: the plain decode step's every-row increment — which drifts
  free and mid-prefill rows' counters — can never misplace a chunk. A
  half-prefilled slot is indistinguishable from a short finished prompt
  to every validity mask; preempting it just frees its blocks and drops
  the cursor — replay restarts at chunk zero, token-identically.

Decoding profiles in the pool
-----------------------------
Multi-stream decoding profiles (core/profiles.py: an n-beam group, a
contrastive cond/uncond pair) occupy a *slot group* of ``n_streams``
slots, and the block table turns their two expensive cache operations
into index manipulation (vLLM's PagedAttention insight):

- **common-prefix sharing**: every beam prefills the same prompt, so
  admission prefills ONE stream and the others ``share`` its blocks —
  block refcounts go up, zero device copies. The contiguous pool's
  fallback is a ``write_slot`` row copy per extra stream.
- **beam reorder as table permutation**: the paper's Obs #4
  KV_Cache_Reorder — re-binding each beam to its surviving parent's
  cache every step — becomes ``BlockPool.permute_group``: child tables
  point at the parent's physical blocks (refcounted), and NO device KV
  gather runs. ``reorder_donated`` below stays the contiguous pool's
  (and the batch engines') fallback.
- **copy-on-write**: a shared block must be unshared before anyone
  writes into it. The next decode write only ever lands in the block
  holding position ``kv_len``, so ``ensure_writable`` copies exactly
  that block (``copy_block``, one block-sized donated device copy) for
  all but the last owner; full common-prefix blocks stay shared for the
  group's whole lifetime because writes never revisit them.

Speculative windows over block tables
-------------------------------------
Speculative decoding (core/profiles.py ``SpeculativeProfile`` ->
core/scheduler.py ``_step_speculative``) makes a slot's kv length move
by a VARIABLE stride: each step writes an (n_draft + 1)-lane window —
``layerskip.draft_window`` drafts into the REAL pool cache,
``engine.verify_step`` rescores and overwrites the same lanes through
``paged_write_chunk``/``write_window`` — then commits only the prefix
the full model accepted. Both halves of the rollback are host-side:

- **contiguous pool**: ``rewind`` below — swap in the committed
  ``lengths`` array. The rejected lanes' K/V stays in the buffer but
  beyond every validity mask, and the next window overwrites it in
  place. No device program runs.
- **paged pool**: ``BlockPool.truncate`` — pop the block-table suffix
  past the block the NEXT write (logical position ``kv_len``) lands in,
  mirroring ``ensure``'s growth convention so accept-then-truncate
  composes with the next step's growth. Released blocks return to the
  free-list (shared blocks just drop a reference); the kept tail
  block's rejected lanes are masked by the validity window and
  rewritten one position at a time on reuse, so — like eviction — NO
  zeroing or copy program runs. Rollback costs a table edit plus the
  ``rewind`` dict swap, never cache traffic, and allocates zero new KV
  device buffers (tests/test_paged.py locks down free-list conservation
  and dense-mirror read identity, partial-block tail included).

A preempted speculative slot needs no special casing: replay re-prefills
and re-decodes under the same per-(request, stream, token-index) keys,
and since every committed token was sampled from full-model logits, the
replayed stream is bit-identical whether or not (and where in a window)
the preemption hit.

Cross-request prefix sharing
----------------------------
The same §4.1.2 indirection that lets one GROUP's streams share prefix
blocks (above) lets DIFFERENT requests share them: a block-table row is
just a map from logical block index to physical block, so any row may
point at any block, including one another request's prompt produced.
core/prefix_cache.py keys a radix trie by full-block spans of prompt
TOKEN ids — under deterministic prefill, identical token spans under
identical ancestors imply bit-identical block contents, so the span hash
IS a content address for the K/V block. On admission the scheduler
copies the matched chain's physical ids into the new row's leading
entries (``BlockPool.adopt``: plus one pool refcount per block, and the
device ``lengths`` entry is pinned to the matched token count so no
write can land below it) and prefill starts at the first uncached token.
Finished prompts hand their full blocks to the trie by refcount handoff
(``cache_ref`` before the eviction decref — the block never visits the
free-list), giving the pool a third block state: free / owned / cached.
Cached blocks with no slot reference are reclaimed LRU-leaf-first under
out-of-blocks pressure, BEFORE preemption. None of this adds a device
program: adoption is a table edit + one ``set_slot_length``, insertion
and reclaim are pure host bookkeeping, and reserved KV bytes do not
change — reuse, not growth.

Sharded pools
-------------
Under tensor-parallel serving (distributed/tp_pool.py) the SAME block
pool is laid out across a ``("model",)`` device mesh: each K/V leaf is
sharded over its HEAD axis (``sharding.cache_specs_tp``; seq-axis
fallback when heads don't divide), so every physical block exists as
1/TP-width shards, one per device, and per-device reserved KV bytes drop
to ~1/TP of the single-device pool. Nothing in this module changes to
make that work, by construction:

- every cross-cutting op here (``reorder``, ``rewind``, ``append_block``,
  ``copy_block``, chunked/window writes) indexes the batch, sequence and
  block axes only — never the head axis — so under GSPMD each device
  runs the identical program on its own head shard;
- the block table, refcounts, free-list, trie and ``lengths`` pinning
  are HOST state (or replicated device state, for ``lengths``): one
  authoritative copy drives all shards, which is why preemption replay,
  CoW, speculative truncate and prefix-cache adoption compose with TP
  with zero new code paths;
- the garbage-sink convention (block 0) and validity masks are
  positional, so they shard along for free.

The invariant ``tests/test_tp.py`` holds it to: sharded serving
is TOKEN-identical to single-device serving at any temperature — the
row-sharded psum moves logits by at most an ulp, which argmax and top-p
sampling survive.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp


def reorder(cache: Any, beam_idx: jnp.ndarray) -> Any:
    """Gather every cache leaf along the batch axis: cache[b] <- cache[beam_idx[b]]."""
    return jax.tree.map(lambda x: jnp.take(x, beam_idx, axis=0), cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def reorder_donated(cache: Any, beam_idx: jnp.ndarray) -> Any:
    """In-place-style reorder: donation lets XLA alias the cache buffers
    (paper §4.1.2: "keep the memory pointer of each cache")."""
    return reorder(cache, beam_idx)


# repro-lint: disable=DN001 — DELIBERATELY undonated: this is the
# paper's `index_select` baseline arm for the Obs #4 A/B (reorder_donated
# above is the optimized form); donating here would erase the comparison
@jax.jit
def reorder_realloc(cache: Any, beam_idx: jnp.ndarray) -> Any:
    """Unoptimized reorder: no donation — every call allocates a fresh
    cache (the paper's `index_select` baseline for Obs #4)."""
    return reorder(cache, beam_idx)


def rewind(cache: Any, new_lengths: jnp.ndarray) -> Any:
    """Roll the cache back to ``new_lengths`` tokens (speculative reject)."""
    return {**cache, "lengths": new_lengths}


# --------------------------------------------------------------------------
# Slot-pool row ops (continuous batching)
# --------------------------------------------------------------------------
# Like ``reorder``, these treat every cache leaf's axis 0 as the sequence-
# slot axis (true for all families; scan_layers stacking is the documented
# exception and is not used by the serving pool).

@functools.partial(jax.jit, donate_argnums=(0,))
def write_slot(pool: Any, row: Any, slot: jnp.ndarray) -> Any:
    """Scatter a single-sequence cache (leaves [1, ...]) into sequence slot
    ``slot`` of a pooled cache (leaves [B, ...]). Donated: XLA updates the
    pool's buffers in place — refilling a slot never reallocates the pool
    (the §4.1.2 "keep the memory pointer" discipline applied to admission).
    ``slot`` is traced, so one compiled executable serves every slot."""
    from repro.models import attention as A

    return jax.tree.map(lambda p, r: A.write_slot_row(p, r, slot), pool, row)


@functools.partial(jax.jit, donate_argnums=(0,))
def reset_slots(pool: Any, mask: jnp.ndarray) -> Any:
    """Evict the slots marked in ``mask`` [B] by zeroing their ``lengths``
    (stale K/V beyond the length counter is already masked by the decode
    validity mask, so buffers need no clearing). Donated in-place update.
    Note: subsequent pool-wide decode steps re-increment every row's
    counter, so a freed slot's ``lengths`` drifts until it is re-assigned —
    liveness belongs to the SlotPool's host free-list, not this counter."""
    return {**pool, "lengths": jnp.where(mask, 0, pool["lengths"])}


# --------------------------------------------------------------------------
# Paged block-pool ops (see module docstring: block-table addressing)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0,))
def append_block(pool_layers: Any, row_layers: Any, block: jnp.ndarray,
                 start: jnp.ndarray) -> Any:
    """Copy one block-sized chunk of a prefilled dense row into physical
    block ``block`` of a paged pool. ``pool_layers`` leaves are
    [num_blocks, block_size, ...], ``row_layers`` leaves [1, S_row, ...];
    the chunk is ``row[0, start : start + block_size]``. Donated, and both
    ``block`` and ``start`` are traced, so ONE compiled executable serves
    every block copy of every admission (the §4.1.2 discipline at block
    granularity). The row is zero-padded to a block multiple (a static pad,
    so still one executable) before slicing: a clamped tail slice would
    silently shift the last block's tokens when ``max_len`` is not a block
    multiple."""

    def copy(p: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
        bs = p.shape[1]
        row = r[0]
        tail = (-row.shape[0]) % bs
        if tail:
            row = jnp.pad(row, [(0, tail)] + [(0, 0)] * (row.ndim - 1))
        chunk = jax.lax.dynamic_slice_in_dim(row, start, bs, axis=0)
        return jax.lax.dynamic_update_slice(
            p, chunk[None].astype(p.dtype), (block,) + (0,) * (p.ndim - 1)
        )

    return jax.tree.map(copy, pool_layers, row_layers)


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_block(pool_layers: Any, src: jnp.ndarray, dst: jnp.ndarray) -> Any:
    """Copy physical block ``src`` over physical block ``dst`` in every K/V
    leaf ([num_blocks, block_size, ...]) — the copy-on-write unshare for a
    block two slot-group streams would otherwise both write (see module
    docstring: "Decoding profiles in the pool"). Donated, with ``src`` and
    ``dst`` traced: ONE compiled executable serves every CoW copy, and the
    pool's buffers are updated in place — no new KV device buffer is ever
    allocated by a beam reorder."""

    def copy(p: jnp.ndarray) -> jnp.ndarray:
        blk = jax.lax.dynamic_slice(
            p, (src,) + (0,) * (p.ndim - 1), (1,) + p.shape[1:]
        )
        return jax.lax.dynamic_update_slice(
            p, blk, (dst,) + (0,) * (p.ndim - 1)
        )

    return jax.tree.map(copy, pool_layers)


def free_blocks(pool: Any, mask: jnp.ndarray) -> Any:
    """Paged-mode eviction: zero the freed slots' ``lengths``. The block
    table and block free-list are host state (BlockPool), and the physical
    blocks themselves need no clearing — stale K/V is unreachable until a
    new owner's validity mask covers the positions it rewrote."""
    return reset_slots(pool, mask)


@functools.partial(jax.jit, donate_argnums=(0,))
def set_slot_length(pool: Any, slot: jnp.ndarray, length: jnp.ndarray) -> Any:
    """Install a newly admitted slot's token counter (paged admission: the
    K/V rows arrive via append_block; only ``lengths`` needs the scatter).
    Donated; ``slot``/``length`` are traced — one executable for all slots."""
    return {**pool, "lengths": pool["lengths"].at[slot].set(length)}


def cache_bytes(cache: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


def cache_token_bytes(cache: Any) -> float:
    """Bytes per cached token per sequence (KV-cache 'rate')."""
    leaves = [x for x in jax.tree.leaves(cache) if x.ndim >= 2]
    if not leaves:
        return 0.0
    b = leaves[0].shape[0]
    seq_leaves = [x for x in leaves if x.ndim >= 3]
    s = max((x.shape[1] for x in seq_leaves), default=1)
    return cache_bytes(cache) / (b * s)
