"""Radix prefix KV cache: shared-prompt reuse across serving requests.

Production request streams are dominated by shared prompt prefixes —
system prompts, few-shot templates, multi-turn histories — yet a plain
slot server re-runs full prefill for every admission, paying Tree-
Attention prefill compute for tokens whose KV rows already sit on the
device. RadixAttention (Zheng et al., *SGLang*, arXiv:2312.07104) showed
that a radix tree over prompt token sequences, mapping prefixes to cached
KV blocks, removes that duplicate prefill. This module is that idea
fitted to the slot engine's contracts:

- **Host-side radix tree** at ``block``-token granularity (power of two,
  bucket-friendly): each node owns ONE pool block — the KV rows of one
  ``block``-token span — keyed by that span's token tuple under its
  parent. A path from the root spells a prompt prefix; matching is a walk.
- **Blocks of the ONE paged pool**: a node's block is a block of the pool
  every slot already reads through its block table, so a hit is a
  host-side table update and a publish is an ownership transfer — no
  device bytes move either way (:class:`PagedPrefixIndex`).
- **Ref-counted LRU eviction**: a node is pinned (``refs > 0``) from the
  admission that matched or published it until that request retires;
  eviction only ever takes a refcount-0 *leaf* (evicting an interior node
  would orphan its children's prefix), least-recently-used first. The
  tree can therefore never over-commit and never frees a block a request
  still depends on — the property test in
  ``tests/test_serving_prefix.py`` hammers exactly this.

Matches are capped at ``len(prompt) - 1`` tokens (rounded down to the
block size): the suffix must keep at least one token, because sampling
the first output token needs at least one forward row.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from tree_attention_tpu import obs
from tree_attention_tpu.serving.block_pool import BlockAllocator
from tree_attention_tpu.utils.logging import get_logger

log = get_logger("serving.prefix")

# Prefix-reuse observability (ISSUE 5). Hit/miss/reuse counters are
# host-loop truths recorded at admission; the occupancy gauge tracks the
# pool allocator. All guarded: allocation-free when the registry is off.
_HITS = obs.counter(
    "serving_prefix_hits_total",
    "admissions that matched a cached prompt prefix",
)
_MISSES = obs.counter(
    "serving_prefix_misses_total",
    "admissions that found no cached prefix (cold prefill)",
)
_TOKENS_REUSED = obs.counter(
    "serving_prefix_tokens_reused_total",
    "prompt tokens whose prefill was replaced by a prefix hit",
)
_POOL_USED = obs.gauge(
    "serving_prefix_pool_blocks_used",
    "prefix pool blocks currently holding a cached KV span",
)


def _block_key(toks: List[int], j: int, block: int) -> Tuple[int, ...]:
    """The radix key of block ``j``: that span's token tuple. Callers on
    the admission hot path convert the prompt with ONE ``tolist()`` and
    slice here at C speed — per-element ``int()`` over numpy scalars
    measured slower than a device gather of the matched blocks would be,
    which would have made the host the bottleneck of a copy-free hit."""
    return tuple(toks[j * block:(j + 1) * block])


# Node tiers (ISSUE 13): a DEVICE node's ``block_id`` names a device
# pool block; a HOST node's names a row of the host tier
# (:class:`~tree_attention_tpu.serving.host_pool.HostBlockPool`) —
# demotion flips the bit down, a prefix-hit restore flips it back.
TIER_DEVICE, TIER_HOST = 0, 1


class _Node:
    """One radix node: a ``block``-token span owning one pool block
    (device tier) or one host-tier row (demoted)."""

    __slots__ = ("key", "parent", "children", "block_id", "refs",
                 "last_use", "tier", "wblock", "wrefs")

    def __init__(self, key: Tuple[int, ...], parent: Optional["_Node"],
                 block_id: int):
        self.key = key
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.block_id = block_id
        self.refs = 0
        self.last_use = 0
        self.tier = TIER_DEVICE
        # A model with sliding-window layers: the window-pool block that
        # holds this span's rows for them (-1: given back, or never kept)
        # and the slots whose window tables map it.
        self.wblock = -1
        self.wrefs = 0


class PagedPrefixIndex:
    """Radix prefix index over the UNIFIED paged pool — reference in place.

    A host radix tree at ``block``-token granularity with the pin /
    LRU-leaf discipline of the module docstring, whose nodes reference
    blocks of the ONE pool every slot already reads through its block
    table (:class:`~tree_attention_tpu.models.decode.PagedKVCache`), so
    both halves of prefix reuse move ZERO device bytes:

    - a **hit** pins the matched path and hands the engine its block ids;
      the engine writes them into the slot's table row — a host-side
      integer update;
    - a **publish** ADOPTS the prefilling slot's private blocks
      (:meth:`adopt`): ownership moves to the tree via the allocator's
      ledger, the KV bytes stay exactly where the prefill wrote them.

    ``max_cached`` bounds how many blocks the tree may retain (the
    deprecated ``prefix_pool_blocks`` view of the world — useful for
    tests and for bounding cold-cache memory); ``None`` lets retention
    grow to whatever the pool's eviction pressure allows. The index
    registers itself as the allocator's evictor, so slot allocations
    under a full free list recycle LRU refcount-0 leaves automatically.

    **Sequence-sharded pools (ISSUE 18)** need no changes here: radix
    keys are host-side token tuples and node payloads are GLOBAL block
    ids — which mesh shard physically holds a block's pool row is an
    allocator detail (``ShardedBlockAllocator.shard_of``), invisible to
    matching, pinning, adoption, and eviction. A hit under
    ``kv_shard="seq"`` is the same host-side table update; the decode
    merge finds the reused rows wherever they live.

    **Layers whose block counts differ** (a model with sliding-window
    layers, ``window_alloc`` given): a node may also keep the WINDOW-pool
    block of its span (``wblock``), adopted from the slot that published
    it while that slot still held it, i.e. for the last blocks of a
    published prompt only. A hit must restore every kind's state, so it
    ends at the deepest boundary whose last ``window_need`` nodes still
    keep theirs (:meth:`window_depth`), else shallower, else nowhere. A
    kept window block no slot maps (``wrefs`` 0) is what the window
    allocator's evictor frees, least recently used first, whatever the
    node's place in the tree; the node stays, and hits past it fall back.
    """

    def __init__(self, *, block: int, alloc: BlockAllocator,
                 max_cached: Optional[int] = None,
                 host_pool: Optional[Any] = None,
                 window_alloc: Optional[BlockAllocator] = None,
                 window_need: int = 0):
        if block < 1 or block & (block - 1):
            raise ValueError(f"prefix block must be a power of two, "
                             f"got {block}")
        self.block = block
        self._root = _Node((), None, -1)
        self._clock = 0
        # Run/lifetime stats (host truths; the engine snapshots + diffs
        # these per serve() run for its report).
        self.hits = 0
        self.misses = 0
        self.tokens_reused = 0
        self.evictions = 0
        self.alloc = alloc
        self.max_cached = max_cached
        self._cached = 0  # DEVICE blocks the tree currently owns
        self._host_cached = 0  # demoted nodes (host-tier rows)
        # KV tiering (ISSUE 13): with a host pool attached, eviction
        # DEMOTES the LRU victim's block into it (the node survives with
        # its tier bit flipped) instead of freeing, and a later match on
        # the demoted path restores it — see host_pool.py's module
        # docstring for the block's full journey.
        self.host = host_pool
        alloc.set_evictor(self.evict_one, self.evictable_blocks)
        self.walloc = window_alloc
        self.window_need = window_need
        self._wcached = 0     # window blocks the tree keeps
        if window_alloc is not None:
            if host_pool is not None:
                raise ValueError(
                    "a host tier under window-layer blocks is not built")
            window_alloc.set_evictor(
                self._evict_window_one, self._window_evictable)

    # -- stats (the engine snapshots + diffs these per run) ---------------

    @property
    def blocks_used(self) -> int:
        return self._cached

    def stats(self) -> Dict[str, Any]:
        out = {
            "hits": self.hits,
            "misses": self.misses,
            "tokens_reused": self.tokens_reused,
            "evictions": self.evictions,
            "pool_blocks_used": self._cached,
            "pool_blocks": (self.max_cached if self.max_cached is not None
                            else self.alloc.blocks),
        }
        if self.walloc is not None:
            out["window_blocks_used"] = self._wcached
        if self.host is not None:
            out.update(self.host.stats())
        return out

    # -- the radix walk / pin / LRU machinery ---------------------------

    def _touch(self, node: _Node) -> None:
        self._clock += 1
        node.last_use = self._clock

    def _pinned_walk(self, prompt: np.ndarray) -> List[_Node]:
        """Pin + LRU-touch the longest cached path over the prompt's
        matchable blocks — capped at ``len(prompt) - 1`` tokens, because
        sampling the first output token needs at least one forward row."""
        max_blocks = (len(prompt) - 1) // self.block
        toks = prompt.tolist()  # ONE C-speed convert; see _block_key
        node = self._root
        path: List[_Node] = []
        for j in range(max_blocks):
            child = node.children.get(_block_key(toks, j, self.block))
            if child is None:
                break
            child.refs += 1
            self._touch(child)
            path.append(child)
            node = child
        return path

    def record_match(self, matched: int) -> None:
        """Count one admission's match outcome (stats + guarded
        counters). Separate from the walk so a caller that may DEFER the
        admission (the paged engine's reservation check) records only
        admissions that actually proceed."""
        if matched:
            self.hits += 1
            self.tokens_reused += matched
            if obs.REGISTRY.enabled:
                _HITS.inc()
                _TOKENS_REUSED.inc(matched)
        else:
            self.misses += 1
            if obs.REGISTRY.enabled:
                _MISSES.inc()

    def release(self, nodes: List[_Node]) -> None:
        for n in nodes:
            n.refs -= 1
            assert n.refs >= 0, "prefix node ref underflow"

    def repin(self, nodes: List[_Node]) -> List[_Node]:
        """Take one MORE pin on each node of an already-pinned path — the
        copy-on-write fork's radix arc (ISSUE 15): a forked sibling
        shares its parent's matched/published ancestor blocks, so it
        holds its own pins on the same nodes and releases them through
        its own retire, exactly like a second admission that matched the
        same path (without re-walking: the parent's pins prove the path
        is alive). Returns the nodes as the child's pinned set; the
        caller must ledger it — the ``ledger-leak`` lint pass tracks
        this acquire site."""
        for n in nodes:
            assert n.refs > 0, "repin of an unpinned prefix node"
            n.refs += 1
            self._touch(n)
        return list(nodes)

    def total_pins(self) -> int:
        """Sum of every node's refcount — the pin-balance truth. A
        drained engine (every request retired, however it exited) must
        read 0 here: admit-time pins are released at retire on EVERY
        outcome arc, cancellation and deadline expiry included (the
        chaos-harness contract, ISSUE 10)."""
        total = 0
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            total += n.refs
        return total

    def _lru_scan(self, victim) -> Optional[_Node]:
        """The min-``last_use`` node satisfying ``victim(node)`` over the
        whole tree, or None — the ONE traversal every LRU-victim rule
        (classic leaf eviction, device-tier demotion, host-tier drop)
        parameterizes."""
        best: Optional[_Node] = None
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if not victim(n):
                continue
            if best is None or n.last_use < best.last_use:
                best = n
        return best

    # -- layers whose block counts differ ---------------------------------

    @property
    def window_blocks_used(self) -> int:
        return self._wcached

    def window_depth(self, path: List[_Node]) -> int:
        """The deepest prefix of a matched ``path``, in blocks, at which
        the window layers' state can be restored too: its last
        ``window_need`` nodes all keep their window block. 0: no hit."""
        if self.walloc is None:
            return len(path)
        for d in range(len(path), 0, -1):
            if all(n.wblock >= 0
                   for n in path[max(0, d - self.window_need):d]):
                return d
        return 0

    def window_nodes(self, path: List[_Node]) -> List[Tuple[int, _Node]]:
        """``(logical block, node)`` of the nodes a hit at the end of
        ``path`` maps window blocks from."""
        lo = max(0, len(path) - self.window_need)
        return list(enumerate(path))[lo:]

    def pin_window(self, nodes: List[_Node]) -> None:
        """One more slot maps each node's window block."""
        for n in nodes:
            assert n.wblock >= 0, "window pin of a node that keeps none"
            n.wrefs += 1

    def unpin_window(self, nodes: List[_Node]) -> None:
        for n in nodes:
            n.wrefs -= 1
            assert n.wrefs >= 0, "prefix node window-ref underflow"

    def adopt_window(self, node: _Node, bid: int) -> None:
        """``node`` keeps window-pool block ``bid`` from now on, handed
        over by the slot that wrote it (which goes on mapping it)."""
        assert node.wblock < 0 and self.walloc is not None
        self.walloc.publish(bid)
        node.wblock, node.wrefs = bid, 1
        self._wcached += 1

    def _window_victim(self) -> Optional[_Node]:
        return self._lru_scan(lambda n: n.wblock >= 0 and not n.wrefs)

    def _evict_window_one(self) -> bool:
        victim = self._window_victim()
        if victim is None:
            return False
        self._free_window(victim)
        return True

    def _free_window(self, node: _Node) -> None:
        self.walloc.free_cached(node.wblock)
        node.wblock = -1
        self._wcached -= 1

    def _window_evictable(self) -> int:
        n = 0
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            n += node.wblock >= 0 and not node.wrefs
        return n

    # -- match / pin ------------------------------------------------------

    def match(self, prompt: np.ndarray,
              record: bool = True) -> Tuple[int, List[_Node]]:
        """Longest cached prefix in whole blocks (capped so one suffix
        token remains), path ref-pinned and LRU-touched; the caller holds
        the pins admit→retire and reads KV through ``node.block_id`` —
        no copy, no staging, zero device bytes. ``record=False`` defers
        the hit/miss stats to :meth:`record_match`: the engine matches
        BEFORE it knows whether the admission's block reservation fits,
        and a deferred admission re-matches later (double-counting the
        monotonic counters would corrupt the reuse accounting)."""
        path = self._pinned_walk(prompt)
        matched = len(path) * self.block
        if record:
            self.record_match(matched)
        return matched, path

    # -- publish by adoption ----------------------------------------------

    def adopt(self, prompt: np.ndarray, phys: Dict[int, int],
              held: List[_Node]) -> Tuple[List[_Node], List[int]]:
        """Publish a completed prompt by HANDING OVER the slot's blocks.

        ``phys`` maps the prompt's logical block index ``j`` to the
        physical pool block the slot privately owns there; ``held`` is
        the request's admit-pinned matched path (its pins CARRY OVER —
        adopt neither re-pins nor releases them). Walking past the held
        prefix: a missing node adopts ``phys[j]`` (ownership moves to
        the tree, refs=1 held by this request until retire); a node
        another request published since our admit is walked THROUGH with
        only a call-scoped guard pin — the slot keeps reading its own
        private copy (identical bytes, freed at retire), and a
        PERSISTENT pin on a refcount-0 node here could convert a block
        some admission's reservation is backed by from evictable to
        pinned, stranding that reservation (the allocator's one
        soundness invariant). The guard pin exists because the budget
        eviction below picks LRU refcount-0 LEAVES — without it, the
        very leaf the walk is standing on could be evicted mid-adopt,
        and the new child would attach under a detached parent (an
        orphaned subtree whose block leaks). Dropped before returning,
        so availability accounting is untouched. Adoption stops early
        when the retention budget is pinned full — partial paths are
        valid prefixes. Returns ``(path, adopted_logical)``: the pinned nodes
        this request now holds (held + created) and which logical
        blocks changed owner.
        """
        nb_full = len(prompt) // self.block
        toks = prompt.tolist()
        node = held[-1] if held else self._root
        path: List[_Node] = list(held)
        adopted: List[int] = []
        guard: List[_Node] = []  # call-scoped pins on walked-through nodes
        for j in range(len(held), nb_full):
            key = _block_key(toks, j, self.block)
            child = node.children.get(key)
            if child is None:
                bid = phys.get(j)
                if bid is None:
                    break  # the slot holds no private block here
                if self.max_cached is not None \
                        and self._cached >= self.max_cached:
                    if not self.evict_one():
                        log.debug("prefix index pinned full; publish "
                                  "stops at block %d/%d", j, nb_full)
                        break
                child = _Node(key, node, bid)
                child.refs = 1
                self.alloc.publish(bid)
                self._cached += 1
                adopted.append(j)
                node.children[key] = child
                path.append(child)
            else:
                child.refs += 1
                guard.append(child)
            self._touch(child)
            node = child
        self.release(guard)
        if obs.REGISTRY.enabled:
            _POOL_USED.set(self._cached)
        return path, adopted

    # -- eviction / demotion (the allocator's hook) -----------------------

    def _lru_device_victim(self) -> Optional[_Node]:
        """The LRU refcount-0 DEVICE-tier node with no device-tier
        children, or None when every device block is pinned. Without a
        host tier this is exactly the classic refcount-0 leaf (host
        nodes never exist); with one, a device node whose children were
        all demoted already is a valid victim — demoting it keeps the
        node (and its host subtree's prefix) intact."""
        return self._lru_scan(
            lambda n: n.tier == TIER_DEVICE and not n.refs
            and not any(c.tier == TIER_DEVICE
                        for c in n.children.values())
        )

    def _drop_host_lru(self) -> bool:
        """The host tier's own LRU eviction: delete the least-recently-
        used refcount-0 host-tier LEAF from the tree (the ``dropped``
        arc — same leaf-only discipline as device eviction, so no
        prefix is ever orphaned). A still-pending demotion's device
        block frees directly: its copy never ran and never will."""
        best = self._lru_scan(
            lambda n: n.tier == TIER_HOST and not n.refs
            and not n.children
        )
        if best is None:
            return False
        del best.parent.children[best.key]
        bid = self.host.drop(best.block_id)
        if bid is not None:
            self.alloc.free_demoted(bid)
        self._host_cached -= 1
        return True

    def evict_one(self) -> bool:
        """Recycle one LRU refcount-0 device victim: DEMOTE it into the
        host tier when one is attached (the node survives — a later
        match restores it), plain-evict otherwise (or when the host tier
        is pinned full even after dropping its own LRU). False when
        every device block is pinned."""
        victim = self._lru_device_victim()
        if victim is None:
            return False
        if self.host is not None:
            row = self.host.alloc()
            while row is None and self._drop_host_lru():
                row = self.host.alloc()
            if row is not None:
                self.alloc.demote_cached(victim.block_id)
                self.host.enqueue(row, victim.block_id)
                victim.tier = TIER_HOST
                victim.block_id = row
                self._cached -= 1
                self._host_cached += 1
                self.evictions += 1
                if obs.REGISTRY.enabled:
                    _POOL_USED.set(self._cached)
                return True
            log.debug("host tier pinned full; falling back to eviction")
        # Classic eviction: the prefix is forgotten. A demoted-tier
        # victim never reaches here (victims are device-tier), so the
        # only children it could orphan are host nodes — and a device
        # victim with host children only falls through when the host
        # tier could not take it, in which case its host subtree must
        # drop with it (leaf-first, so it is already empty: _drop_host_lru
        # failing means every host leaf is pinned, which pins this path).
        if victim.children:
            return False
        del victim.parent.children[victim.key]
        if victim.wblock >= 0:
            # Unpinned, so no slot maps its window block either.
            assert not victim.wrefs, "evicting a node a window table maps"
            self._free_window(victim)
        self.alloc.free_cached(victim.block_id)
        self._cached -= 1
        self.evictions += 1
        if obs.REGISTRY.enabled:
            _POOL_USED.set(self._cached)
        return True

    def evictable_blocks(self) -> int:
        """DEVICE blocks in fully-unpinned subtrees — exactly what
        repeated :meth:`evict_one` calls can reach (device-leaf-first
        eviction drains an unpinned subtree's device blocks completely;
        a pinned descendant protects every ancestor on its path).
        Host-tier nodes hold no device block and count 0."""

        def walk(node: _Node) -> Tuple[bool, int, int]:
            has_pin = node.refs > 0
            dev_blocks = 1 if node.tier == TIER_DEVICE else 0
            kid_evictable = 0
            for c in node.children.values():
                p, b, e = walk(c)
                has_pin |= p
                dev_blocks += b
                kid_evictable += e
            if has_pin:
                return True, dev_blocks, kid_evictable
            return False, dev_blocks, dev_blocks

        return sum(walk(c)[2] for c in self._root.children.values())

    # -- restore (the engine's hit path, ISSUE 13) ------------------------

    def demoted_in(self, nodes: List[_Node]) -> List[_Node]:
        """The host-tier nodes of a matched (pinned) path, path order."""
        return [n for n in nodes if n.tier == TIER_HOST]

    def restore_nodes(
        self, nodes: List[_Node], alloc_device: Any
    ) -> Tuple[List[int], List[int]]:
        """Bring a pinned path's demoted nodes back to the device tier.

        Two arcs per node: a still-PENDING demotion cancels (the device
        bytes never left — the block hands straight back to the tree,
        zero copies, zero allocations); a flushed one takes a fresh
        device block from ``alloc_device()`` (the admission's
        reservation backs it) and joins the batched H2D scatter the
        caller dispatches. Returns ``(host_rows, new_bids)`` — equal-
        length lists of the rows to copy and their destination blocks;
        the caller reads the rows (:meth:`HostBlockPool.read`), scatters,
        then releases them. Tier bits and ownership flip here, so the
        tree's view is consistent the moment this returns."""
        rows: List[int] = []
        bids: List[int] = []
        for n in nodes:
            assert n.tier == TIER_HOST and n.refs > 0, \
                "restore of an unpinned or device-tier node"
            row = n.block_id
            bid = self.host.cancel_pending(row)
            if bid is not None:
                self.alloc.undemote(bid)
            else:
                bid = alloc_device()
                self.alloc.publish(bid)  # private -> tree-owned
                rows.append(row)
                bids.append(bid)
            n.block_id = bid
            n.tier = TIER_DEVICE
            self._cached += 1
            self._host_cached -= 1
        if obs.REGISTRY.enabled:
            _POOL_USED.set(self._cached)
        return rows, bids
