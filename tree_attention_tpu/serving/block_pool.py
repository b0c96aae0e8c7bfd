"""Host-side allocator for the unified paged-KV block pool.

The paged layout (:class:`~tree_attention_tpu.models.decode.PagedKVCache`,
PagedAttention — arXiv:2309.06180) keeps ONE device pool of ``N`` blocks
under every slot AND the radix prefix cache; this module is the host-side
ledger that makes that sharing safe. Ownership is single-writer:

- a **free** block belongs to the allocator's free list;
- a **private** block belongs to exactly one slot (its decode/prefill
  tail — rows only that slot writes);
- a **cached** block belongs to exactly one radix-tree node
  (:class:`~tree_attention_tpu.serving.prefix_cache.PagedPrefixIndex`),
  published there by the slot that prefilled it — ownership moves, the
  bytes do not. Any number of slots may *read* a cached block through
  their tables; the node's pin count (``refs``) tracks them, and the
  tree only evicts refcount-0 leaves.

**Reservation-based admission** is what turns "over-subscribing the pool"
into a clean scheduling decision instead of a shape error deep inside a
jitted gather: an admission reserves its worst-case block count up front
(``ceil((prompt + max_new) / block)`` minus the blocks a prefix hit
already shares) against ``available() = free + evictable - reserved``,
where *evictable* counts cached blocks in fully-unpinned subtrees. If the
reservation does not fit, the request simply WAITS in the queue — the
engine defers admission until retires/evictions free blocks — and a
request that could never fit (needs more than the whole pool) fails
``serve()``'s validation with a clear message. Every later
:meth:`alloc` is backed by a prior reservation, so it cannot fail: when
the free list is empty the evictor (the radix tree's LRU refcount-0-leaf
eviction) is guaranteed to find a victim.

Pure host integers — no device state — so the property tests can hammer
hundreds of random admit/retire/hit/evict interleavings per second.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from tree_attention_tpu import obs
from tree_attention_tpu.utils.logging import get_logger

log = get_logger("serving.blocks")

_BLOCKS_USED = obs.gauge(
    "serving_kv_blocks_used",
    "unified KV pool blocks currently owned by a slot or the prefix tree",
)
_BLOCKS_FREE = obs.gauge(
    "serving_kv_blocks_free",
    "unified KV pool blocks on the free list",
)
# Per-shard views of the same ledger (sequence-sharded pool, ISSUE 18):
# the aggregate gauges above keep their unlabeled contract; these expose
# the shard split so /metrics shows placement imbalance directly.
_BLOCKS_USED_SHARD = obs.gauge(
    "serving_kv_blocks_used_shard",
    "KV pool blocks owned per mesh shard (sequence-sharded pool)",
    labels=("shard",),
)
_BLOCKS_FREE_SHARD = obs.gauge(
    "serving_kv_blocks_free_shard",
    "KV pool blocks free per mesh shard (sequence-sharded pool)",
    labels=("shard",),
)

# The window layers' pool (a model with sliding-window layers): blocks a slot
# or the prefix tree holds for them, and blocks given back behind a window.
_WINDOW_BLOCKS = obs.gauge(
    "serving_kv_window_blocks",
    "window-layer pool blocks by state: held (mapped in a slot's window "
    "table), cached (kept by the prefix tree), free",
    labels=("state",),
)
_WINDOW_FREED = obs.counter(
    "serving_kv_window_blocks_freed_total",
    "window-layer blocks slots gave back because they fell behind the "
    "window of every row still to be computed",
)

# Block ownership states (the debug ledger's vocabulary). A _DEMOTED
# block is owned by the host tier's staging queue: the radix tree evicted
# it toward host RAM (ISSUE 13), the D2H copy has not run yet, and the
# block must not be reused until the flush lands it on the host and calls
# :meth:`BlockAllocator.free_demoted`. A _SHARED block (ISSUE 15) is a
# copy-on-write fork's full ancestor: refcounted by the slots whose
# tables map it (cached-style shared ownership, but owned by SLOTS, not
# the radix tree), append-only by construction (every owner only writes
# PAST it), freed when the last owner retires.
_FREE, _PRIVATE, _CACHED, _DEMOTED, _SHARED = 0, 1, 2, 3, 4


class BlockAllocator:
    """Free list + reservation accounting over ``blocks`` pool blocks.

    The radix tree registers itself via :meth:`set_evictor`; without one
    (prefix cache off) *evictable* is always 0 and the allocator is a
    plain reserve-then-take free list.
    """

    def __init__(self, blocks: int):
        if blocks < 1:
            raise ValueError(f"block pool needs >= 1 block, got {blocks}")
        self.blocks = blocks
        # Pop from the end -> ascending ids early on (cosmetic, and it
        # makes allocator traces readable).
        self._free: List[int] = list(range(blocks - 1, -1, -1))
        self._state = [_FREE] * blocks  # the double-free/leak ledger
        self.reserved = 0
        # Availability generation: bumped whenever availability can have
        # GROWN (frees, unreserves; the engine also bumps on retire,
        # whose pin releases grow evictability without touching the free
        # list). A deferred admission latches the generation it failed
        # at and skips the O(prompt) re-match + O(tree) evictability
        # recount until the counter moves — pool state can't have
        # improved in between.
        self.gen = 0
        # Lifetime count of blocks handed between slot tables via
        # :meth:`transfer_private` (disaggregation accounting).
        self.transferred = 0
        # Copy-on-write fork accounting (ISSUE 15): per-block owner
        # refcounts of _SHARED blocks, and the lifetime count of
        # share edges taken (each fork_shared bid is one edge).
        self._shared_refs: Dict[int, int] = {}
        self.fork_shares = 0
        self._evict_one: Optional[Callable[[], bool]] = None
        self._evictable: Optional[Callable[[], int]] = None
        # Demotion staging (ISSUE 13): with a host tier under the pool,
        # eviction DEMOTES blocks (state _DEMOTED) instead of freeing
        # them, and the flusher runs the batched D2H gather that finally
        # frees them. ``demote_batch`` is how many leaves one dry alloc
        # demotes before flushing — the batch that makes "one jitted
        # gather per demotion batch" a real amortisation instead of a
        # per-block sync.
        self._flush_demotions: Optional[Callable[[], int]] = None
        self.demote_batch = 8

    # -- the free list (subclass seam) ------------------------------------
    #
    # Every free-list touch goes through these two hooks so a subclass can
    # swap the backing structure (ShardedBlockAllocator keeps one list per
    # mesh shard) without re-deriving any of the ownership transitions or
    # the reservation-soundness argument above.

    def _push_free(self, bid: int) -> None:
        self._free.append(bid)

    def _pop_free(self) -> int:
        return self._free.pop()

    # -- introspection ----------------------------------------------------

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used(self) -> int:
        return self.blocks - self.free_count

    def evictable(self) -> int:
        return self._evictable() if self._evictable is not None else 0

    def available(self) -> int:
        """Blocks an admission may still reserve: free + evictable-now,
        minus what earlier admissions already promised themselves."""
        return self.free_count + self.evictable() - self.reserved

    def publish_gauges(self) -> None:
        if obs.REGISTRY.enabled:
            _BLOCKS_USED.set(self.used)
            _BLOCKS_FREE.set(self.free_count)

    # -- the evictor hook (the radix tree) --------------------------------

    def set_evictor(
        self, evict_one: Callable[[], bool], evictable: Callable[[], int]
    ) -> None:
        """``evict_one()`` must free one refcount-0 cached leaf into this
        allocator (returning False only when none exists); ``evictable()``
        counts blocks reachable that way."""
        self._evict_one = evict_one
        self._evictable = evictable

    def set_demote_flusher(self, flush: Callable[[], int]) -> None:
        """``flush()`` must complete every pending demotion's D2H copy
        and :meth:`free_demoted` the device blocks, returning how many it
        freed. The engine registers this when KV tiering is on; alloc()
        calls it only when a backed reservation finds the free list dry
        (the common flush point is the engine's end-of-tick staging)."""
        self._flush_demotions = flush

    # -- reservations -----------------------------------------------------

    def reserve(self, n: int) -> bool:
        """Promise ``n`` future :meth:`alloc` calls; False if the pool
        cannot honor them (the engine defers the admission)."""
        if n < 0:
            raise ValueError(f"cannot reserve {n} blocks")
        if n > self.available():
            return False
        self.reserved += n
        return True

    def unreserve(self, n: int) -> None:
        """Return unused reservations (early EOS, retire)."""
        self.reserved -= n
        self.gen += 1
        assert self.reserved >= 0, "block reservation underflow"

    # -- allocation / ownership transitions -------------------------------

    def alloc(self) -> int:
        """One private block, consuming one reservation. Never fails:
        reservations are only granted against free + evictable blocks,
        and pins (which shrink evictability) are themselves reserved."""
        assert self.reserved > 0, "alloc without a backing reservation"
        self.reserved -= 1
        while not self.free_count:
            # Load-bearing calls — NOT inside an assert (python -O strips
            # assert statements, and the eviction must still run). With a
            # host tier, evict_one() DEMOTES (the block parks in state
            # _DEMOTED, not on the free list), so a dry alloc demotes a
            # small batch of leaves and flushes the staged D2H once —
            # one jitted gather per batch, not one sync per block.
            n = 0
            while not self.free_count and n < self.demote_batch:
                if self._evict_one is None or not self._evict_one():
                    break
                n += 1
            if not self.free_count and self._flush_demotions is not None \
                    and self._flush_demotions() > 0:
                continue
            if not self.free_count:
                raise AssertionError(
                    "allocator invariant broken: a backed reservation "
                    "found neither a free block nor an evictable leaf"
                )
        bid = self._pop_free()
        assert self._state[bid] == _FREE, f"block {bid} double-allocated"
        self._state[bid] = _PRIVATE
        return bid

    def publish(self, bid: int) -> None:
        """Ownership transfer private slot -> radix node (zero bytes
        moved — the whole point of the paged layout)."""
        assert self._state[bid] == _PRIVATE, (
            f"block {bid} published while not privately owned"
        )
        self._state[bid] = _CACHED

    def free_private(self, bid: int) -> None:
        """A retiring slot returns a block it still owns."""
        assert self._state[bid] == _PRIVATE, (
            f"block {bid} freed while not privately owned"
        )
        self._state[bid] = _FREE
        self._push_free(bid)
        self.gen += 1

    def unmap_private(self, bid: int) -> None:
        """A slot unmaps a block whose tokens were ROLLED BACK (rejected
        speculation) but keeps its worst-case claim: the block returns to
        the free list AND the reservation it consumed is restored, so the
        slot's later re-allocation cannot fail. Net availability is
        unchanged (+1 free, +1 reserved), hence no generation bump — a
        deferred admission could not be admitted by this."""
        assert self._state[bid] == _PRIVATE, (
            f"block {bid} unmapped while not privately owned"
        )
        self._state[bid] = _FREE
        self._push_free(bid)
        self.reserved += 1

    # -- copy-on-write fork sharing (ISSUE 15) ----------------------------

    @property
    def shared_count(self) -> int:
        """_SHARED blocks currently alive (each counted once, whatever
        its refcount) — a drained engine must read 0 here."""
        return len(self._shared_refs)

    def shared_refs(self, bid: int) -> int:
        """Owner refcount of a shared block (0 when not shared)."""
        return self._shared_refs.get(bid, 0)

    def fork_shared(self, bids: Iterable[int]) -> List[int]:
        """A fork shares full ancestor blocks between parent and child:
        each ``bid`` must be privately owned (first fork — becomes
        ``_SHARED`` with two owners) or already shared (another sibling
        forks the same history — one more owner). The bytes never move
        and never change: shared blocks are full, and every owner only
        appends PAST them, so refcounting is the whole safety story —
        exactly vLLM's copy-on-write fork over PagedAttention block
        tables (arXiv:2309.06180). Returns the bids as the child's
        shared-ownership set; the caller must ledger it (and the
        parent's) so BOTH retires release — the ``ledger-leak`` lint
        pass tracks this acquire site."""
        out: List[int] = []
        for bid in bids:
            if self._state[bid] == _PRIVATE:
                self._state[bid] = _SHARED
                self._shared_refs[bid] = 2
            elif self._state[bid] == _SHARED:
                self._shared_refs[bid] += 1
            else:
                raise AssertionError(
                    f"block {bid} fork-shared while neither private nor "
                    f"shared (state {self._state[bid]}) — sharing a "
                    f"free/cached block would double-own it"
                )
            self.fork_shares += 1
            out.append(bid)
        return out

    def release_shared(self, bid: int) -> None:
        """One owner of a shared block retires. The last release frees
        the block (and grows availability — generation bump); earlier
        ones only drop the refcount."""
        refs = self._shared_refs.get(bid)
        assert refs is not None and self._state[bid] == _SHARED, (
            f"block {bid} shared-released while not shared"
        )
        if refs > 1:
            self._shared_refs[bid] = refs - 1
            return
        del self._shared_refs[bid]
        self._state[bid] = _FREE
        self._push_free(bid)
        self.gen += 1

    def transfer_private(self, bids: Iterable[int]) -> int:
        """Audited ownership handoff of private blocks between slot
        tables (disaggregated serving: a prefill worker's finished slot
        hands its block set to a decode worker, which adopts them into
        its own table — zero KV bytes moved; DistServe, arXiv:2401.09670).

        The ledger state does not change — each block stays ``_PRIVATE``,
        owned by exactly one slot before AND after (the callers move the
        slot-side bookkeeping: table row, private set, and the unspent
        reservation, which stays counted in :attr:`reserved` throughout).
        Net availability is therefore untouched — no generation bump, and
        the reservation-soundness invariant (every future alloc backed by
        free + evictable blocks) holds across the handoff by construction.
        The audit is the point: transferring a block that is *not*
        privately owned (double handoff, a cached block still owned by
        the radix tree, a freed block) is the ownership bug this ledger
        exists to catch, and raises here instead of corrupting the pool.
        Returns the number of blocks transferred."""
        n = 0
        for bid in bids:
            if self._state[bid] != _PRIVATE:
                raise AssertionError(
                    f"block {bid} transferred while not privately owned "
                    f"(state {self._state[bid]}) — handoff of a cached/"
                    f"free block would double-own it"
                )
            n += 1
        self.transferred += n
        return n

    def free_cached(self, bid: int) -> None:
        """The radix tree evicts a refcount-0 leaf's block."""
        assert self._state[bid] == _CACHED, (
            f"block {bid} evicted while not tree-owned"
        )
        self._state[bid] = _FREE
        self._push_free(bid)
        self.gen += 1

    # -- the host tier's transitions (ISSUE 13) ---------------------------

    def demote_cached(self, bid: int) -> None:
        """The radix tree demotes a refcount-0 leaf toward the host tier:
        the block leaves the tree's ownership but is NOT yet free — its
        bytes must survive on the device until the staged D2H gather
        copies them out (``free_demoted``). Not counted available, so the
        reservation-soundness audit holds through the staging window."""
        assert self._state[bid] == _CACHED, (
            f"block {bid} demoted while not tree-owned"
        )
        self._state[bid] = _DEMOTED

    def undemote(self, bid: int) -> None:
        """Cancel a pending demotion: a prefix hit matched the demoted
        node before its D2H copy ran, so the block's device bytes are
        still canonical — hand ownership straight back to the tree (zero
        copies, zero allocations)."""
        assert self._state[bid] == _DEMOTED, (
            f"block {bid} un-demoted while not staged (state "
            f"{self._state[bid]})"
        )
        self._state[bid] = _CACHED

    def free_demoted(self, bid: int) -> None:
        """The staged D2H copy landed on the host: the device block is
        finally reusable."""
        assert self._state[bid] == _DEMOTED, (
            f"block {bid} flushed while not staged for demotion"
        )
        self._state[bid] = _FREE
        self._push_free(bid)
        self.gen += 1


class ShardedBlockAllocator(BlockAllocator):
    """The sequence-sharded pool's ledger (ISSUE 18): ``blocks`` global
    block ids range-partitioned over ``shards`` mesh shards — shard ``s``
    owns ids ``[s*Nl, (s+1)*Nl)`` with ``Nl = blocks // shards``, the SAME
    rule the device pool uses to map a global table entry to a local slice
    row, so the host ledger and the device placement can never disagree.

    One free list per shard; :meth:`alloc` pops from the RICHEST shard so
    a growing slot's blocks interleave across shards and every shard
    carries ~1/W of each slot's keys (balanced flash partials, balanced
    pool pressure). Everything else — ownership states, eviction,
    demotion, CoW sharing — is inherited untouched.

    Reservations stay GLOBAL, which keeps them sound: any block can serve
    any slot through the table indirection (placement only decides which
    pool slice the bytes land in), so ``available()`` over the pooled free
    count is exactly the guarantee :meth:`alloc` needs. Per-shard
    reservations would be strictly weaker bookkeeping for zero safety.
    """

    def __init__(self, blocks: int, shards: int):
        if shards < 1:
            raise ValueError(f"need >= 1 shard, got {shards}")
        if blocks % shards:
            raise ValueError(
                f"pool of {blocks} blocks does not split over {shards} "
                f"shards — round the pool up first"
            )
        self.shards = shards
        self.shard_blocks = blocks // shards
        super().__init__(blocks)
        nl = self.shard_blocks
        self._free_by_shard: List[List[int]] = [
            list(range((s + 1) * nl - 1, s * nl - 1, -1))
            for s in range(shards)
        ]
        self._free = []  # unused; the per-shard lists are the free list

    def shard_of(self, bid: int) -> int:
        return bid // self.shard_blocks

    def _push_free(self, bid: int) -> None:
        self._free_by_shard[bid // self.shard_blocks].append(bid)

    def _pop_free(self) -> int:
        rich = max(
            range(self.shards), key=lambda s: len(self._free_by_shard[s])
        )
        return self._free_by_shard[rich].pop()

    @property
    def free_count(self) -> int:
        return sum(len(f) for f in self._free_by_shard)

    def free_per_shard(self) -> List[int]:
        return [len(f) for f in self._free_by_shard]

    def used_per_shard(self) -> List[int]:
        return [self.shard_blocks - len(f) for f in self._free_by_shard]

    def publish_gauges(self) -> None:
        super().publish_gauges()
        if obs.REGISTRY.enabled:
            for s, nfree in enumerate(self.free_per_shard()):
                _BLOCKS_FREE_SHARD.labels(shard=s).set(nfree)
                _BLOCKS_USED_SHARD.labels(shard=s).set(
                    self.shard_blocks - nfree
                )


class WindowBlocks:
    """The sliding-window layers' blocks: their pool's ledger (a second
    :class:`BlockAllocator`: same refcounts, same fork and publish rules),
    their table, and what each slot holds of them.

    A window layer's row at ``t`` sees positions ``(t - window, t]``, so of
    a slot's logical blocks only those that hold a position some row still
    to be computed can see need a physical block. Before every dispatch
    that writes rows ``[t0, end)`` of a slot, :meth:`advance` gives back
    every block whose last token lies at or under ``t0 - window`` (its
    table entry goes back to 0: it names no block of the slot's) and maps
    the blocks the rows fall in. What a slot holds is therefore bounded by
    :attr:`bound` = ``ceil((window + chunk) / block) + 1`` whatever its
    length (``ceil(window / block) + 1`` in decode).

    The give-back rule is data (``rule``). ``"sliding"``, above: a block
    goes back when its last token lies at or under ``t0 - window``.
    ``"aligned"`` (an EVA layer's exact rows; ``models/decode.py``
    ``PagedWindowCache``): a row at ``t`` sees ``[(t // window) * window,
    t]``, so a block goes back when it lies under ``(t0 // window) *
    window``, the window of the FIRST row still to be computed: a chunk
    whose rows straddle a boundary keeps the old window until its tick has
    been dispatched, and the next dispatch gives the old window's ``window
    / block`` blocks back at once. The bound holds as it is: the old window
    and the rows past it are fewer than ``window + chunk`` tokens.

    **Reservation.** Every admission reserves the constant :attr:`bound`
    and every block a slot maps that it does not own alone (a fork's shared
    ancestor, a block the prefix tree keeps) is charged to it as one it
    allocated would be: a slot's mapped blocks plus its unspent reservation
    are :attr:`bound`, always. The pool holds ``slots x (bound + 1)``
    blocks, so by counting alone the reservations in force never exceed the
    free blocks plus the tree's unmapped ones (each mapped block is charged
    to at least one slot): an admission never waits for window blocks and
    a long request never runs the pool dry. The blocks over ``slots x
    bound`` are what the prefix tree may keep for later hits.

    Pure host integers, like the allocator's."""

    PRIVATE, SHARED = "private", "shared"

    def __init__(self, *, slots: int, table_width: int, block: int,
                 window: int, chunk: int, rule: str = "sliding"):
        if window < 1 or rule not in ("sliding", "aligned"):
            raise ValueError(
                f"a window of {window} tokens under the {rule!r} rule "
                f"('sliding' or 'aligned')")
        self.block, self.window, self.rule = block, window, rule
        self.bound = -(-(window + chunk) // block) + 1
        # The published blocks a hit needs at its boundary: those that
        # hold the ``window - 1`` positions under it.
        self.hit_blocks = -(-(window - 1) // block)
        self.blocks = slots * (self.bound + 1)
        self.alloc = BlockAllocator(self.blocks)
        self.table = np.zeros((slots, table_width), np.int32)
        self.dirty = False
        # slot -> logical block -> (pool block, owner): PRIVATE, SHARED or
        # the prefix node that keeps it.
        self._held: List[Dict[int, Tuple[int, Any]]] = [
            {} for _ in range(slots)]
        self._reserve = [0] * slots
        self.freed = 0        # lifetime blocks given back behind a window
        self.peak_slot = 0    # the most one slot ever held at once

    # -- numbers ------------------------------------------------------------

    def held(self, slot: Optional[int] = None) -> int:
        """Window-table entries mapped: one slot's, or summed over slots."""
        if slot is not None:
            return len(self._held[slot])
        return sum(len(h) for h in self._held)

    def private(self) -> int:
        return sum(1 for h in self._held for _, o in h.values()
                   if o is self.PRIVATE)

    def reserved(self, slot: int) -> int:
        return self._reserve[slot]

    def publish_gauges(self, cached: int = 0) -> None:
        if obs.REGISTRY.enabled:
            _WINDOW_BLOCKS.labels(state="held").set(self.held())
            _WINDOW_BLOCKS.labels(state="cached").set(cached)
            _WINDOW_BLOCKS.labels(state="free").set(self.alloc.free_count)

    # -- a slot's life ------------------------------------------------------

    def reserve(self) -> bool:
        """One admission's constant; False defers it (cannot happen at
        the pool's own size, see the class docstring)."""
        return self.alloc.reserve(self.bound)

    def cancel(self) -> None:
        """Return a reservation no slot took (a deferred admission)."""
        self.alloc.unreserve(self.bound)

    def admit(self, slot: int) -> None:
        assert not self._held[slot] and not self._reserve[slot], (
            f"slot {slot} admitted over window blocks it still holds")
        self._reserve[slot] = self.bound

    def _charge(self, slot: int) -> None:
        # A mapped block the slot did not allocate counts against its
        # constant like one it did.
        assert self._reserve[slot] > 0, (
            f"slot {slot} maps more window blocks than its bound")
        self._reserve[slot] -= 1
        self.alloc.unreserve(1)

    def _refund(self, slot: int) -> None:
        self._reserve[slot] += 1
        self.alloc.reserved += 1

    def _set(self, slot: int, j: int, bid: int, owner: Any) -> None:
        self._held[slot][j] = (bid, owner)
        self.table[slot, j] = bid
        self.dirty = True
        self.peak_slot = max(self.peak_slot, len(self._held[slot]))

    def hit(self, slot: int, at: List[Tuple[int, Any]]) -> None:
        """Map the prefix nodes' window blocks at a hit's boundary:
        ``(logical block, node)`` pairs whose ``wrefs`` the caller has
        taken (``PagedPrefixIndex.pin_window``)."""
        for j, node in at:
            self._charge(slot)
            self._set(slot, j, node.wblock, node)

    def advance(self, slot: int, t0: int, end: int) -> int:
        """Before a dispatch that writes rows ``[t0, end)`` of ``slot``:
        give back what lies behind the window of row ``t0`` (and so of
        every later row), map the blocks the rows fall in. Returns how
        many blocks were given back."""
        held = self._held[slot]
        # The lowest position row ``t0`` sees, by the rule.
        low = t0 // self.window * self.window if self.rule == "aligned" \
            else t0 - self.window + 1
        behind = [j for j in held if (j + 1) * self.block - 1 < low]
        for j in behind:
            self._drop(slot, j, refund=True)
        self.freed += len(behind)
        if behind and obs.REGISTRY.enabled:
            _WINDOW_FREED.inc(len(behind))
        for j in range(t0 // self.block, (end - 1) // self.block + 1):
            if j not in held:
                assert self._reserve[slot] > 0, (
                    f"slot {slot} outgrew its window-block bound "
                    f"{self.bound}")
                self._reserve[slot] -= 1
                self._set(slot, j, self.alloc.alloc(), self.PRIVATE)
        return len(behind)

    def _drop(self, slot: int, j: int, refund: bool) -> None:
        bid, owner = self._held[slot].pop(j)
        self.table[slot, j] = 0   # names no block of the slot's any more
        self.dirty = True
        if owner is self.PRIVATE:
            if refund:
                self.alloc.unmap_private(bid)   # free, and reserved again
                self._reserve[slot] += 1
            else:
                self.alloc.free_private(bid)
            return
        if owner is self.SHARED:
            self.alloc.release_shared(bid)
        else:
            owner.wrefs -= 1
            assert owner.wrefs >= 0, "prefix node window-ref underflow"
        if refund:
            self._refund(slot)

    def publish(self, slot: int, j: int, node: Any, index: Any) -> bool:
        """Hand the block ``slot`` privately holds at logical ``j`` to
        prefix ``node`` (which keeps none yet): ownership moves, the slot
        goes on reading it, charged as before."""
        got = self._held[slot].get(j)
        if got is None or got[1] is not self.PRIVATE or node.wblock >= 0:
            return False
        index.adopt_window(node, got[0])
        self._held[slot][j] = (got[0], node)
        return True

    def fork(self, parent: int, child: int, nshare: int,
             partial: bool) -> Tuple[int, int]:
        """A fork's window half: ``child`` (admitted) shares every block
        ``parent`` holds under logical ``nshare`` by reference, as a full
        layer's ancestors are shared, and takes a block of its own for the
        partial one at ``nshare``. Returns ``(source, destination)`` of
        the one device copy (0, 0: none)."""
        for j, (bid, owner) in list(self._held[parent].items()):
            if j >= nshare:
                continue
            if owner is self.PRIVATE or owner is self.SHARED:
                self.alloc.fork_shared([bid])
                self._held[parent][j] = (bid, self.SHARED)
                owner = self.SHARED
            else:
                owner.wrefs += 1
            self._charge(child)
            self._set(child, j, bid, owner)
        if not partial or nshare not in self._held[parent]:
            return 0, 0
        src = self._held[parent][nshare][0]
        self._reserve[child] -= 1
        dst = self.alloc.alloc()
        self._set(child, nshare, dst, self.PRIVATE)
        return src, dst

    def free_slot(self, slot: int) -> None:
        """Everything ``slot`` holds goes back; its table row is reset."""
        for j in list(self._held[slot]):
            self._drop(slot, j, refund=False)
        if self._reserve[slot]:
            self.alloc.unreserve(self._reserve[slot])
            self._reserve[slot] = 0
        self.table[slot, :] = 0
        self.dirty = True
