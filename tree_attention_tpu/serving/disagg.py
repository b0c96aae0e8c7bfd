"""Disaggregated prefill/decode serving: split-phase engine pools with a
zero-copy KV handoff (ISSUE 12).

Prefill is compute-bound and bursty; decode is memory-bound and steady.
Fusing them in one engine is why the Sarathi chunk budget exists at all —
and even chunked admission puts ``Tq > 1`` rows into decode ticks during
admission storms, so decode inter-token latency (TBT) p99 degrades with
prefill load. DistServe (arXiv:2401.09670) and Splitwise (arXiv:2311.18677)
split the two phases onto separate pools, removing that interference class
entirely. This module is the in-process shape of that split:

- a **prefill worker**: a :class:`~tree_attention_tpu.serving.engine
  .SlotServer` that runs admission + chunked prefill ONLY — its slots go
  ``free -> prefill -> await -> handoff``, never ``live``, and its ticks
  never carry a decode row;
- a **decode worker**: a second ``SlotServer`` whose ticks are pure
  ``Tq=1`` decode (or speculative-verify) programs — no admission, no
  chunks; its slots are fed exclusively by adoption from the handoff
  queue;
- **one shared block pool**: both workers are constructed over a single
  :class:`~tree_attention_tpu.serving.block_pool.BlockAllocator` (and one
  :class:`~tree_attention_tpu.serving.prefix_cache.PagedPrefixIndex` when
  the radix cache is on), and :class:`DisaggServer` rebinds both caches
  to ONE set of device pool arrays, relaying the (functionally updated)
  pool between the workers after every dispatch. A handoff therefore
  moves **zero KV bytes**: the finished prefill's blocks change owner in
  the allocator-audited ledger (:meth:`BlockAllocator.transfer_private`),
  the decode worker writes the same physical ids into its own table row,
  and the unspent worst-case reservation moves with the request — it is
  *transferred*, not re-reserved, so admission soundness holds across the
  handoff with no window in which a third request could steal the blocks.
  (Under int8 the per-BLOCK scale scalars — ISSUE 13 — are POOL state and
  relay with the KV arrays; the handoff itself moves no scale metadata.)

**The handoff queue is the prefill slot itself.** A request whose final
chunk completed parks in its prefill slot in state ``handoff`` until a
decode slot frees up; adoption then transfers every resource in one host
step. This buys two things: natural backpressure (a saturated decode pool
stalls prefill admissions instead of growing an unbounded queue), and the
one-retire-path contract — cancel/deadline while *queued for handoff* is
just :meth:`SlotServer._retire` on the prefill worker, the same code path
as every other exit arc, releasing blocks, pins, and reservations exactly
once on whichever worker owns the request at that moment.

**CPU-proxy caveat (honest accounting).** In-process, both workers run on
ONE device and the tick loop serializes them, so a wall-clock decode gap
would absorb the prefill worker's tick time — noise a two-device
deployment does not pay. The loop therefore *attributes* time per worker:
after each prefill tick, every live decode slot's last-token clock is
shifted forward by the prefill section's wall time, so recorded TBT is
the decode worker's own cost — what a dedicated decode device would
serve. The serialized totals are still reported
(``ServeReport.handoff["prefill_tick_s"/"decode_tick_s"]``) so nothing
hides; absolute seconds are CPU-proxy numbers either way, the structure
(decode ticks never widen with prefill load) is what transfers.

Threading contract: like ``SlotServer``, the ONLY thread-safe seams are
:meth:`cancel` and :meth:`request_drain` (mailboxes under ``self._lock``,
an RLock, swept at tick start) plus a live ``RequestSource``'s submit
side; everything else — both engines' state, the handoff queue, the
shared allocator — is touched only by the serve-loop thread.
``DisaggServer`` exposes the same ``serve``/``cancel``/``request_drain``/
``slots``/``slo``/``leak_report`` surface as ``SlotServer``, so the HTTP
ingress, the fleet supervisor, and the chaos harness stack on top
unchanged (the CLI's ``--serve-disagg``, composable with
``--serve-http``).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from tree_attention_tpu import obs
from tree_attention_tpu.obs.flight import FLIGHT, STARTUP
from tree_attention_tpu.models.transformer import Params, TransformerConfig
from tree_attention_tpu.serving.block_pool import (
    BlockAllocator,
    ShardedBlockAllocator,
)
from tree_attention_tpu.serving.engine import (
    OUTCOME_BUDGET,
    OUTCOME_CANCELLED,
    OUTCOME_DEADLINE,
    OUTCOME_EOS,
    OUTCOME_ERROR,
    OUTCOME_SHED,
    Request,
    RequestSource,
    ServeReport,
    SlotServer,
    StaticRequestSource,
    _SLOTS_OCCUPIED,
    _TBT,
    _TOKENS,
    _TTFT,
    serving_params,
)
from tree_attention_tpu.serving.speculation import Drafter, PackedSpec
from tree_attention_tpu.utils.logging import get_logger

log = get_logger("serving.disagg")

# Handoff observability (ISSUE 12): counts are host-loop truths recorded
# at the adoption step; the queue gauge tracks prefill slots parked in
# state "handoff". All guarded: allocation-free when the registry is off.
_HANDOFFS = obs.counter(
    "serving_handoff_total",
    "requests handed off prefill->decode (pure ownership transfer, "
    "zero KV bytes moved in-process)",
)
_HANDOFF_QUEUE = obs.gauge(
    "serving_handoff_queue",
    "requests parked in prefill slots awaiting decode-pool adoption",
)


class DisaggServer:
    """Two ``SlotServer`` workers over one block pool, one tick loop.

    Args (the shared ones mean exactly what they mean on
    :class:`SlotServer`; both workers are built from the same params/cfg):

      prefill_slots: batch size of the prefill worker — how many prompts
        may be in (chunked) prefill or parked for handoff at once.
      decode_slots: batch size of the decode worker — the max concurrent
        decoding requests (the fused engine's ``slots`` analog for
        steady-state concurrency).
      kv_blocks: TOTAL shared pool capacity in blocks (both workers and
        the prefix tree draw from it). Default:
        ``(prefill_slots + decode_slots) * ceil(cache_len / kv_block)``
        — the fused engine's default at equal total slots, so fused vs
        disaggregated comparisons are equal-bytes by construction.
      speculate / draft_k / drafter: speculative decoding on the DECODE
        pool (the prefill worker never speculates — it has nothing to
        draft against).
      prefix_cache: shared radix reuse across the pair — the prefill
        worker matches/adopts against ONE :class:`PagedPrefixIndex`, the
        decode worker inherits each request's pins at handoff and
        releases them at retire. int8 serving shares too (ISSUE 13):
        blocks carry per-BLOCK scales in the pool, so a published int8
        block is self-contained on either worker.
      host_blocks: KV tiering across the pair (ISSUE 13) — capacity of
        the host-RAM demotion tier under the SHARED pool (0 = off).
        The tier belongs to the shared radix tree: the prefill worker
        (the matching side) runs the restores and the staged demotion
        flushes; the relayed pool arrays keep both workers' views of a
        restored block identical. Requires ``prefix_cache=True``.
    """

    def __init__(
        self,
        params: Params,
        cfg: TransformerConfig,
        *,
        prefill_slots: int,
        decode_slots: int,
        cache_len: int,
        mesh: Optional[Mesh] = None,
        quantize: bool = False,
        quant_kernel: str = "q8q",
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int = 0,
        prefill_chunk: int = 256,
        prefill_budget: Optional[int] = None,
        slo_ttft: float = 1.0,
        slo_tbt: float = 0.2,
        slo_window: int = 1024,
        prefix_cache: bool = False,
        prefix_block: int = 64,
        prefix_pool_blocks: Optional[int] = None,
        kv_block: Optional[int] = None,
        kv_blocks: Optional[int] = None,
        speculate: bool = False,
        draft_k: int = 4,
        drafter: Union[str, Drafter, None] = None,
        host_blocks: int = 0,
        kv_shard: str = "replicated",
    ):
        if prefill_slots < 1 or decode_slots < 1:
            raise ValueError(
                f"disaggregation needs >= 1 slot per pool, got "
                f"prefill_slots={prefill_slots} decode_slots={decode_slots}"
            )
        if kv_block is None:
            kv_block = prefix_block if prefix_cache else 64
        self.prefill_slots = prefill_slots
        self.decode_slots = decode_slots
        self.slots = prefill_slots + decode_slots  # the ingress contract
        self.cache_len = cache_len
        self.cfg = cfg
        # Re-laid once for the pair: each worker's own call then passes
        # the served tree through.
        self.params = params = serving_params(params)
        self.quantize = quantize
        self.kv_block = kv_block
        npb = -(-cache_len // kv_block)
        self.kv_blocks = (
            self.slots * npb if kv_blocks is None else kv_blocks
        )
        # ONE ledger for both workers: every reservation, allocation, and
        # ownership transition — including the handoff's transfer — runs
        # through this allocator, so the soundness audit covers the pair.
        # Under kv_shard="seq" (ISSUE 18) the ledger is the sharded
        # variant — the handoff still moves zero KV bytes because block
        # ownership is a host-side notion regardless of which mesh shard
        # physically holds a block's pool row.
        if kv_shard not in ("replicated", "seq"):
            raise ValueError(
                f"kv_shard must be 'replicated' or 'seq', got {kv_shard!r}"
            )
        self.kv_shard = kv_shard
        if kv_shard == "seq":
            from tree_attention_tpu.parallel.mesh import AXIS_SEQ

            w = max(mesh.shape.get(AXIS_SEQ, 1), 1) if mesh is not None else 1
            self.kv_blocks = -(-self.kv_blocks // w) * w
            self.pool = ShardedBlockAllocator(self.kv_blocks, w)
        else:
            self.pool = BlockAllocator(self.kv_blocks)
        if host_blocks < 0:
            raise ValueError(f"host_blocks must be >= 0, got {host_blocks}")
        if host_blocks and not prefix_cache:
            raise ValueError(
                "host_blocks KV tiering requires prefix_cache=True "
                "(demotion is what radix eviction becomes; with no "
                "radix tree nothing ever demotes)"
            )
        self.host_blocks = host_blocks
        self.host_pool = None
        if host_blocks:
            from tree_attention_tpu.serving.host_pool import HostBlockPool

            self.host_pool = HostBlockPool(
                host_blocks,
                n_layers=cfg.cache_layers,
                n_kv_heads=cfg.n_kv_heads,
                block=kv_block,
                d_head=cfg.d_head,
                dtype=np.int8 if quantize else np.dtype(
                    jnp.dtype(cfg.dtype).name),
                quantized=quantize,
            )
        self.prefix_index = None
        if prefix_cache:
            from tree_attention_tpu.serving.prefix_cache import (
                PagedPrefixIndex,
            )

            self.prefix_index = PagedPrefixIndex(
                block=kv_block, alloc=self.pool,
                max_cached=prefix_pool_blocks,
                host_pool=self.host_pool,
            )
        common = dict(
            cache_len=cache_len, mesh=mesh, quantize=quantize,
            quant_kernel=quant_kernel, temperature=temperature,
            top_k=top_k, slo_ttft=slo_ttft, slo_tbt=slo_tbt,
            slo_window=slo_window, kv_block=kv_block,
            kv_shard=kv_shard,
            block_pool=self.pool, prefix_index=self.prefix_index,
        )
        self.prefill = SlotServer(
            params, cfg, slots=prefill_slots, seed=seed,
            prefill_chunk=prefill_chunk,
            # No decode row rides a prefill worker's tick, so there is no
            # inter-token gap for its budget to protect: every prefilling
            # slot advances a chunk a tick unless the caller bounds it.
            prefill_budget=(prefill_slots * prefill_chunk
                            if prefill_budget is None else prefill_budget),
            **common,
        )
        self.decode = SlotServer(
            params, cfg, slots=decode_slots, seed=seed + 1,
            prefill_chunk=prefill_chunk,
            speculate=speculate, draft_k=draft_k, drafter=drafter,
            **common,
        )
        # ONE SLO monitor for the pair: TTFT is observed on the prefill
        # worker, TBT on the decode worker, retires on whichever worker
        # owns the request — a split monitor would halve every window.
        self.slo = self.prefill.slo
        self.decode.slo = self.slo
        # ONE set of device pool arrays: the decode worker's freshly
        # allocated (all-zero, identical) pools are dropped in favor of
        # the prefill worker's, and every dispatch below relays the
        # updated arrays to the other worker — the rebinding that makes
        # "zero KV bytes moved" literal rather than aspirational.
        self.decode.cache = dataclasses.replace(
            self.decode.cache, k=self.prefill.cache.k,
            v=self.prefill.cache.v,
        )
        if quantize:
            # Per-BLOCK scales are POOL state (ISSUE 13), shared exactly
            # like the KV pools: drop the decode worker's fresh scale
            # arrays for the prefill worker's, and the per-dispatch
            # relay below carries them — the handoff itself moves no
            # scale metadata at all (it used to copy the per-slot frozen
            # rows; per-block scales travel with their blocks for free).
            self.decode.cache = dataclasses.replace(
                self.decode.cache,
                k_scale=self.prefill.cache.k_scale,
                v_scale=self.prefill.cache.v_scale,
            )
        if self.host_pool is not None:
            # KV tiering across the pair (ISSUE 13): the tier belongs to
            # the SHARED tree, so the workers were built with
            # host_blocks=0 and the pair wires the prefill worker — the
            # matching side, where restores happen — as the tier's
            # engine: its _paged_hit restores demoted paths, its
            # _flush_demotions runs the staged D2H batches (registered
            # as the shared allocator's flusher so a dry reservation on
            # EITHER worker can force one; the relayed pool arrays make
            # prefill.cache the live pool whichever worker dispatched
            # last). The loop relays after restores and flushes at end
            # of tick, mirroring SlotServer.serve.
            self.prefill.attach_host_tier(self.host_pool)
        # Fork families (ISSUE 15) need sibling slots on the SAME engine
        # as the parent's prefill — which disaggregation splits across
        # the handoff — so n/best_of > 1 requests are rejected at
        # validation; mid-generation fork(uid) still works, applied on
        # the decode worker (live slots only exist there).
        self.prefill._fork_ok = False
        # Token-tree sibling decode (ISSUE 20) is a fused-engine feature:
        # the decode worker's tick loop serves chain verify rows only, so
        # a mid-generation fork must take the sibling-slot path, never the
        # in-slot tree conversion.
        self.decode._tree_sampling = False
        # Thread-safe control mailboxes — the ingress's seams. RLock: the
        # drain flag is flipped from SIGTERM handlers (the ingress's
        # install_drain_signals contract), which may interrupt a handler
        # thread already holding the lock.
        self._lock = threading.RLock()
        self._cancel_uids: set = set()
        self._draining = False
        self._fork_uids: List[int] = []
        # Lifetime handoff stats (public, loop-thread only; serve() diffs
        # them per run for ServeReport.handoff).
        self.handoffs = 0

    # -- ingress-facing control (thread-safe) ------------------------------

    def cancel(self, uid: int) -> None:
        """Cancel request ``uid`` (any thread). Applied at the next tick
        sweep on whichever worker owns it — queued, prefilling, parked
        for handoff, or decoding; unknown uids are a no-op."""
        with self._lock:
            self._cancel_uids.add(uid)

    def fork(self, uid: int) -> None:
        """Branch live request ``uid`` mid-generation (any thread,
        ISSUE 15) — applied by the control sweep on the DECODE worker,
        where live slots exist; the branch shares the request's full
        ancestor blocks in the pair's ONE pool and retires through the
        decode worker's one retire path."""
        with self._lock:
            self._fork_uids.append(uid)

    def _take_forks(self) -> List[int]:
        with self._lock:
            out = self._fork_uids
            self._fork_uids = []
            return out

    @property
    def _fork_carry(self) -> Dict[int, int]:
        """The decode worker's deferred-fork carry (the sweep's retry
        state lives where the forks apply)."""
        return self.decode._fork_carry

    def _apply_forks(self, forks: List[int], tick: int, pending) -> None:
        """Mirror of the fused engine's fork arc, applied on the decode
        worker. A request still QUEUED, prefilling, or parked for
        handoff lives on the prefill side — the decode worker cannot
        see it, so those uids ride the retry carry (exactly the fused
        engine's not-yet-live race) instead of aging out as unknown.
        A fork's tail-block copy donates the SHARED pool arrays, so a
        sweep that forked anything relays them to the prefill worker
        before its next dispatch."""
        upstream = list(pending) + [
            rq for rq in self.prefill._slot_req if rq is not None
        ]
        forked0 = self.decode._forks_life
        self.decode._apply_forks(forks, tick, upstream)
        if self.decode._forks_life != forked0:
            self._relay_pool(self.decode, self.prefill)

    def request_drain(self) -> None:
        """Begin graceful drain (any thread): stop admitting, shed the
        queue, finish everything in flight — handoffs included — then
        return from :meth:`serve`."""
        with self._lock:
            self._draining = True

    @property
    def draining(self) -> bool:
        with self._lock:
            return self._draining

    @property
    def all_slots_free(self) -> bool:
        return self.prefill.all_slots_free and self.decode.all_slots_free

    def _take_control(self) -> Tuple[set, bool]:
        with self._lock:
            cancels = self._cancel_uids
            self._cancel_uids = set()
            return cancels, self._draining

    def prefix_stats(self) -> Dict[str, Any]:
        return ({} if self.prefix_index is None
                else dict(self.prefix_index.stats()))

    def leak_report(self) -> Dict[str, int]:
        """The pair's no-leak invariant: after a drained run the shared
        pool must hold no slot-private blocks ON EITHER WORKER, no
        unspent reservations, and no pinned radix nodes — a handoff that
        dropped or double-counted a block shows up here."""
        out = {
            "blocks_private": (
                sum(len(s) for s in self.prefill._slot_private)
                + sum(len(s) for s in self.decode._slot_private)
            ),
            "blocks_used": self.pool.used,
            "blocks_reserved": self.pool.reserved,
            "blocks_shared": self.pool.shared_count,
            "blocks_cached": 0,
            "pins": 0,
        }
        if self.prefix_index is not None:
            out["blocks_cached"] = self.prefix_index.blocks_used
            out["pins"] = self.prefix_index.total_pins()
        if self.host_pool is not None:
            # Host-tier occupancy is legitimate retained cache (like
            # blocks_cached), surfaced for the harness's accounting.
            out["host_blocks_used"] = self.host_pool.used
        return out

    def slots_snapshot(self) -> List[Dict[str, Any]]:
        """The pair's ``/slots`` view (ISSUE 16): both workers' rows,
        labeled — a parked handoff shows as prefill state ``handoff``
        until a decode row adopts it."""
        out: List[Dict[str, Any]] = []
        for worker, eng in (("prefill", self.prefill),
                            ("decode", self.decode)):
            for row in eng.slots_snapshot():
                row["worker"] = worker
                out.append(row)
        return out

    # -- the zero-copy handoff ---------------------------------------------

    def _relay_pool(self, src: SlotServer, dst: SlotServer) -> None:
        """Rebind ``dst``'s cache to ``src``'s just-produced pool arrays.

        Every dispatch donates its cache, so after a worker steps, the
        OTHER worker's cache still references the pre-step (possibly
        consumed) pool buffers; this host-side pointer swap — no device
        work — restores the single-pool invariant before the next
        dispatch. Tables and lengths are per-worker and untouched; the
        per-BLOCK scale arrays (ISSUE 13) are POOL state like the KV
        arrays and relay with them under int8."""
        new = dict(k=src.cache.k, v=src.cache.v)
        if self.quantize:
            new.update(k_scale=src.cache.k_scale,
                       v_scale=src.cache.v_scale)
        dst.cache = dataclasses.replace(dst.cache, **new)

    def _adopt(self, p: int, d: int, tick: int,
               pending_reset: Dict[int, int]) -> None:
        """Move one parked request from prefill slot ``p`` to decode slot
        ``d`` — the handoff proper. Pure ownership transfer: the
        allocator audits that every transferred block is privately owned
        (:meth:`BlockAllocator.transfer_private`), the table row / private
        set / unspent reservation / radix pins / sampling state move to
        the decode worker's ledgers, and the prefill slot is scrubbed
        WITHOUT freeing anything — the request now retires (on any arc)
        through the decode worker's one retire path."""
        pf, dc = self.prefill, self.decode
        req = pf._slot_req[p]
        plen = len(req.prompt)
        bids = pf._slot_private[p]
        nb = pf._slot_nblocks[p]
        self.pool.transfer_private(bids)
        dc._host_table[d, :nb] = pf._host_table[p, :nb]
        dc._host_table[d, nb:] = 0
        dc._slot_nblocks[d] = nb
        dc._slot_private[d] = bids
        dc._slot_reserve[d] = pf._slot_reserve[p]
        dc._table_dirty = True
        # The request's pinned radix path (admit-time hit + published
        # blocks) — the pins carry over and release at decode retire.
        dc._slot_nodes[d] = pf._slot_nodes[p]
        dc._slot_req[d] = req
        dc._slot_tokens[d] = pf._slot_tokens[p]  # [first token]
        dc._slot_admit[d] = pf._slot_admit[p]
        dc._slot_wait[d] = pf._slot_wait[p]
        dc._slot_ttft[d] = pf._slot_ttft[p]
        dc._slot_max_tbt[d] = pf._slot_max_tbt[p]
        dc._slot_prefix_hit[d] = pf._slot_prefix_hit[p]
        dc._prompt_np[d] = pf._prompt_np[p]
        dc._last_tok_t[d] = pf._last_tok_t[p]
        # Sampling state moves with the request (ISSUE 15): the PRNG key
        # row (reproducibility is fold_in(key, stream-index) — the
        # handoff must not re-derive from the decode worker's base),
        # per-slot temperature/top-k, the branch index, and the running
        # cumulative logprob.
        dc._keys = dc._keys.at[d].set(pf._keys[p])
        dc._temp_np[d] = pf._temp_np[p]
        dc._topk_np[d] = pf._topk_np[p]
        dc._slot_index[d] = pf._slot_index[p]
        dc._slot_cum_lp[d] = pf._slot_cum_lp[p]
        dc._slot_shared[d] = set()
        dc._slot_clen[d] = plen  # committed rows = the prompt; the first
        # token is the pending tip (the spec rollback ledger starts here)
        first = dc._slot_tokens[d][-1]
        # _tok_host may be a read-only view of the device fetch — copy
        # before installing the adopted slot's parked token ((S,) int32).
        th = np.array(dc._tok_host)
        th[d] = first
        dc._tok_host = th
        if dc._speculate:
            dc._hist_buf[d, :plen] = dc._prompt_np[d]
            dc._hist_buf[d, plen] = first
            dc._hist_len[d] = plen + 1
        dc._slot_state[d] = "live"
        # The request's admit->retire span follows the request.
        dc._slot_span[d] = pf._slot_span[p]
        # The decode worker's device cache still carries a STALE length
        # for slot d (its prefill happened in the other worker's length
        # vector) — the slot's first decode dispatch resets it to plen.
        pending_reset[d] = plen
        # Scrub the prefill slot WITHOUT releasing resources — they just
        # changed owner. No allocator generation bump either: nothing
        # became available, so a deferred admission must keep waiting.
        pf._slot_req[p] = None
        pf._slot_tokens[p] = []
        pf._slot_state[p] = "free"
        pf._prompt_np[p] = None
        pf._slot_nodes[p] = []
        pf._slot_private[p] = set()
        pf._slot_reserve[p] = 0
        pf._host_table[p, :] = 0
        pf._slot_nblocks[p] = 0
        pf._table_dirty = True
        pf._slot_span[p] = None
        self.handoffs += 1
        if obs.REGISTRY.enabled:
            _HANDOFFS.inc()
        if obs.TRACER.active:
            obs.instant("handoff", cat="serving", args={
                "rid": req.uid, "tick": tick, "from_slot": p,
                "to_slot": d, "blocks": nb, "kv_bytes_moved": 0,
            })
            if req.trace is not None:
                # Step point of the request's cross-process flow at the
                # prefill→decode adoption (ISSUE 16): the trace context
                # rides the Request object across the handoff.
                obs.flow("t", obs.flow_id(req.trace[0]))
        if obs.REQLOG.enabled:
            # Close the ledger's handoff segment (parked → adopted).
            obs.REQLOG.resume(req.uid)

    # -- the split tick loop ----------------------------------------------

    def serve(self, requests: Union[Sequence[Request], RequestSource],
              max_ticks: Optional[int] = None) -> ServeReport:
        """Run both workers' tick loops, interleaved, until the source
        drains — the same contract as :meth:`SlotServer.serve` (static
        trace or live source, control sweep at tick start, ``max_ticks``
        bounds runaway loops), with each loop iteration running at most
        one prefill-worker tick and one decode-worker tick.

        MAINTENANCE NOTE: the ingest/control-sweep/admission sections and
        the two dispatch bodies below deliberately MIRROR
        ``SlotServer.serve`` (specialized: no decode rows in the prefill
        tick, no chunk rows in the decode tick) rather than extracting
        shared helpers from the fused engine's hot loop. A behavioral fix
        to the fused engine's sweep ordering, cancel-carry TTL, deferral
        latch, or verify-tick packing must be ported here by hand — the
        token-parity gate catches data-plane drift but NOT control-plane
        drift (cancel/deadline race semantics). Grep anchor:
        engine.py's serve() carries the same section comments."""
        pf, dc = self.prefill, self.decode
        # The two workers' caches are views of ONE donated pool array
        # set: a dispatch through either consumes the other's view until
        # _relay_pool rebinds it (machine-checked by the donation-safety
        # lint pass through this declaration).
        # lint: donated-alias[pf.cache, dc.cache]
        live = isinstance(requests, RequestSource)
        if live:
            source: RequestSource = requests
        else:
            for r in requests:
                pf._validate(r)
            source = StaticRequestSource(requests)
            with self._lock:
                # Same reset rule as the fused engine: a stale mailbox
                # must not cancel a fresh synthetic trace; live sources
                # keep pre-loop drains/cancels.
                self._cancel_uids.clear()
                self._draining = False
        pending: deque = deque()
        cancel_carry: Dict[int, int] = {}
        results: Any = deque(maxlen=4096) if live else []
        visible_wall: Dict[int, float] = {}
        tbt: Any = deque(maxlen=1 << 16) if live else []
        # Loop-local run state (deliberately NOT instance attributes: the
        # serve loop is single-threaded and this state dies with the run).
        handoff_fifo: List[int] = []  # prefill slots parked in "handoff"
        pending_reset: Dict[int, int] = {}  # decode slot -> adopted length
        tok_dirty = False  # decode token vector needs a host->device push
        tick = 0
        decode_ticks = 0
        occupancy = 0
        tokens = 0
        queue_peak = 0
        prefill_s = 0.0  # serialized wall time per worker (the CPU-proxy
        decode_s = 0.0   # attribution record — see the module docstring)
        handoffs0 = self.handoffs
        transferred0 = self.pool.transferred
        peak_used = self.pool.used
        prefix0 = (self.prefix_index.stats()
                   if self.prefix_index is not None else None)
        host0 = (self.host_pool.stats()
                 if self.host_pool is not None else None)
        hit_bytes0 = pf._hit_bytes_moved
        spec0 = (dc._spec_proposed, dc._spec_accepted, dc._spec_ticks,
                 dc._spec_verifies)
        pf._defer_gen = -1  # a stale latch must not defer a fresh run
        t0 = time.monotonic()
        # The start-up record's span of this call (obs/flight.py), as
        # ``SlotServer.serve`` keeps one: open until the report is built.
        run_span = STARTUP.begin("serve", t0)

        try:
            while True:
                if max_ticks is not None and tick >= max_ticks:
                    raise RuntimeError(
                        f"DisaggServer.serve() exceeded max_ticks="
                        f"{max_ticks} with {len(pending)} pending and "
                        f"{len(handoff_fifo)} queued-for-handoff "
                        f"request(s)"
                    )
                now = time.monotonic()
                pf._tick_prefix_hits = 0
                pf._tick_prefix_reused = 0
                pf._tick_restored = 0
                # Robustness-arc counters mirror the fused engine's (the
                # prefill worker holds the pair's sweep stats; the flight
                # record below surfaces them like SlotServer.serve does).
                pf._tick_cancelled = 0
                pf._tick_deadline = 0
                pf._tick_shed = 0

                # Ingest newly visible requests (live invalids finish
                # with outcome 'error'; static traces validated up front).
                # lint: mirror[ingest] begin
                for r in source.poll(tick):
                    vis = r.visible_at if r.visible_at is not None else now
                    try:
                        pf._validate(r)
                    except ValueError as e:
                        log.warning("rejecting request %s: %s", r.uid, e)
                        pf._finish_unadmitted(
                            r, tick, OUTCOME_ERROR, results, vis, now
                        )
                        continue
                    pending.append(r)
                    visible_wall[r.uid] = vis
                    if obs.TRACER.active:
                        obs.instant("request_queued", cat="serving",
                                    args={"rid": r.uid, "tick": tick})
                # lint: mirror[ingest] end

                # Control sweep — the fused engine's ordering (cancel
                # beats deadline beats drain-shed), applied across BOTH
                # workers; a request parked for handoff is a prefill-slot
                # occupant and retires through that worker's one retire
                # path like every other arc.
                cancels, draining = self._take_control()
                cancels |= set(cancel_carry)
                if cancels:
                    # lint: mirror[cancel-queued] begin
                    matched = set()
                    for r in [r for r in pending if r.uid in cancels]:
                        pending.remove(r)
                        matched.add(r.uid)
                        pf._tick_cancelled += 1
                        pf._finish_unadmitted(
                            r, tick, OUTCOME_CANCELLED, results,
                            visible_wall.pop(r.uid, now), now,
                        )
                    # lint: mirror[cancel-queued] end
                    for eng in (pf, dc):
                        for i, rq in enumerate(eng._slot_req):
                            if rq is not None and rq.uid in cancels:
                                matched.add(rq.uid)
                                pf._tick_cancelled += 1
                                eng._retire(i, tick, OUTCOME_CANCELLED,
                                            results)
                    # lint: mirror[cancel-carry] begin
                    for uid in cancels - matched:
                        if uid not in cancel_carry:
                            cancel_carry[uid] = 2
                        else:
                            cancel_carry[uid] -= 1
                            if cancel_carry[uid] <= 0:
                                del cancel_carry[uid]
                    for uid in matched:
                        cancel_carry.pop(uid, None)
                    # lint: mirror[cancel-carry] end
                # lint: mirror[deadline-queued] begin
                for r in [r for r in pending
                          if r.deadline_s is not None
                          and now >= r.deadline_s]:
                    pending.remove(r)
                    pf._tick_deadline += 1
                    pf._finish_unadmitted(
                        r, tick, OUTCOME_DEADLINE, results,
                        visible_wall.pop(r.uid, now), now,
                    )
                # lint: mirror[deadline-queued] end
                for eng in (pf, dc):
                    for i, rq in enumerate(eng._slot_req):
                        if (rq is not None and rq.deadline_s is not None
                                and now >= rq.deadline_s):
                            pf._tick_deadline += 1
                            eng._retire(i, tick, OUTCOME_DEADLINE, results)
                # The sweep may have retired parked requests out of their
                # slots — drop them from the handoff FIFO.
                handoff_fifo = [p for p in handoff_fifo
                                if pf._slot_state[p] == "handoff"]
                if draining:
                    # lint: mirror[drain-shed] begin
                    source.close()
                    while pending:
                        r = pending.popleft()
                        pf._tick_shed += 1
                        pf._finish_unadmitted(
                            r, tick, OUTCOME_SHED, results,
                            visible_wall.pop(r.uid, now), now,
                        )
                    # lint: mirror[drain-shed] end

                # Copy-on-write fork arc (ISSUE 15): mailboxed
                # fork(uid)s branch live requests onto free slots
                # (deferred ones retry from the carry for a few sweeps).
                # lint: mirror[fork] begin
                forks = self._take_forks()
                if forks or self._fork_carry:
                    self._apply_forks(forks, tick, pending)
                # lint: mirror[fork] end

                # Adopt: oldest parked request per free decode slot —
                # the zero-copy handoff step.
                free_d = dc._free_slots()
                while handoff_fifo and free_d:
                    p = handoff_fifo.pop(0)
                    d = free_d.pop(0)
                    self._adopt(p, d, tick, pending_reset)
                    tok_dirty = True

                # Admit: oldest visible request per free PREFILL slot
                # (worst-case reservation against the shared pool; the
                # generation latch and FIFO-no-skip rules are the fused
                # engine's).
                free = pf._free_slots()
                while free and pending:
                    if pf._staged_prefill and pf._prefill_fifo:
                        break
                    if pf._defer_gen == self.pool.gen:
                        break
                    resv = pf._paged_reserve(pending[0])
                    if resv is None:
                        pf._defer_gen = self.pool.gen
                        break
                    req = pending.popleft()
                    slot = free.pop(0)
                    pf._admit(req, slot, tick,
                              visible_wall.pop(req.uid, now), resv)
                if self.host_pool is not None and pf._tick_restored:
                    # A hit on a demoted path just scattered restored
                    # blocks into the (donated) pool arrays — relay so
                    # the decode worker's next dispatch sees them.
                    self._relay_pool(pf, dc)
                queue_depth = len(pending)
                if len(handoff_fifo) > queue_peak:
                    queue_peak = len(handoff_fifo)
                if obs.REGISTRY.enabled:
                    _HANDOFF_QUEUE.set(len(handoff_fifo))

                busy = bool(
                    pending or handoff_fifo
                    or not pf.all_slots_free or not dc.all_slots_free
                )
                if not busy:
                    # Idle handling stays BEFORE the tick body (the
                    # executed-ticks == recorded-ticks invariant).
                    if FLIGHT.enabled:
                        rec = None
                        # lint: mirror[sweep-only] begin
                        if (pf._tick_cancelled or pf._tick_deadline
                                or pf._tick_shed):
                            # The sweep retired work and left the tick
                            # idle; without this record the counters are
                            # zeroed at the next tick top and the storm
                            # vanishes from the black box.
                            rec = {
                                "tick": tick,
                                "sweep_only": True,
                                "occupancy": 0,
                                "queue_depth": queue_depth,
                                "pending": len(pending),
                                "cancelled": pf._tick_cancelled,
                                "deadline_expired": pf._tick_deadline,
                                "shed": pf._tick_shed,
                                "draining": draining,
                            }
                        # lint: mirror[sweep-only] end
                        if rec is not None:
                            rec["worker"] = "prefill"
                            FLIGHT.record(rec)
                    # lint: mirror[idle] begin
                    if source.exhausted or draining:
                        break
                    nxt = source.next_arrival()
                    if nxt is not None:
                        tick = max(tick + 1, nxt)
                    else:
                        if FLIGHT.enabled:
                            FLIGHT.mark_idle()
                        source.wait(0.05)
                    continue
                    # lint: mirror[idle] end

                # ---- prefill-worker tick: chunks only, no decode rows.
                tp0 = time.monotonic()
                plan = pf._plan_chunks()
                chunk_tokens = sum(n for _, n, _ in plan)
                pf_span = obs.span(
                    "disagg:prefill_tick", cat="serving",
                    args=None if not obs.TRACER.active else {
                        "tick": tick,
                        "prefilling": len(pf._prefill_fifo),
                        "chunk_tokens": chunk_tokens,
                        "handoff_queue": len(handoff_fifo),
                        "queue_depth": queue_depth,
                    },
                )
                with pf_span:
                    if pf._staged_prefill and plan:
                        # int8: staged exact chunks; the final chunk
                        # quantizes + inserts through the slot's table.
                        for slot, n, last in plan:
                            pf._run_staged_chunk(slot, n, last)
                        self._relay_pool(pf, dc)
                    elif plan:
                        # The packed tick with no decode row live: the
                        # chunk group and S inert rows.
                        tq = pf._chunk_bucket(max(n for _, n, _ in plan))
                        reset = np.zeros((pf.slots,), bool)
                        reset_val = np.zeros((pf.slots,), np.int32)
                        emit = np.zeros((pf.slots,), bool)
                        chunk_tok, chunk_slot, chunk_n = \
                            pf._pack_chunk_group(plan, tq, reset,
                                                 reset_val, emit)
                        sidx = np.zeros((pf.slots,), np.int32)
                        pf._sync_table()
                        pf.tok, pf._lp, _, _, pf.cache = pf._packed(
                            pf.params, jnp.asarray(chunk_tok),
                            jnp.asarray(chunk_slot), jnp.asarray(chunk_n),
                            jnp.asarray(sidx), jnp.asarray(sidx),
                            jnp.asarray(reset),
                            jnp.asarray(reset_val), jnp.asarray(emit),
                            pf.cache, pf._keys,
                            jnp.asarray(pf._temp_np),
                            jnp.asarray(pf._topk_np),
                            jnp.asarray(sidx), pf._lp,
                        )
                        self._relay_pool(pf, dc)
                        if pf._prefix is not None:
                            for slot, n, last in plan:
                                if last:
                                    pf._publish_prefix(slot)
                    awaits = [i for i, st in enumerate(pf._slot_state)
                              if st == "await"]
                    if awaits:
                        # lint: allow[host-sync] the prefill worker's one per-tick fetch (final-chunk first tokens + logprobs)
                        pf._tok_host = np.asarray(pf.tok)
                        # lint: allow[host-sync] rides the same sync point (first-token logprobs)
                        pf._lp_host = np.asarray(pf._lp)
                        now2 = time.monotonic()
                        for i in awaits:
                            req = pf._slot_req[i]
                            first = int(pf._tok_host[i])
                            pf._slot_tokens[i] = [first]
                            pf._slot_cum_lp[i] = float(pf._lp_host[i])
                            pf._push_token(req, first)
                            _, vis = pf._slot_admit[i]
                            pf._slot_ttft[i] = max(now2 - vis, 0.0)
                            pf._last_tok_t[i] = now2
                            tokens += 1
                            self.slo.observe_ttft(pf._slot_ttft[i])
                            if obs.REGISTRY.enabled:
                                _TOKENS.inc()
                                _TTFT.observe(pf._slot_ttft[i])
                            if obs.TRACER.active:
                                obs.instant(
                                    "first_token", cat="serving", args={
                                        "rid": req.uid, "slot": i,
                                        "tick": tick,
                                        "ttft_s": round(
                                            pf._slot_ttft[i], 6),
                                    })
                            if obs.REQLOG.enabled:
                                obs.REQLOG.first_token(req.uid, now=now2)
                            if req.eos_id is not None \
                                    and first == req.eos_id:
                                pf._retire(i, tick, OUTCOME_EOS, results)
                            elif req.max_new_tokens <= 1:
                                pf._retire(i, tick, OUTCOME_BUDGET,
                                           results)
                            else:
                                pf._slot_state[i] = "handoff"
                                handoff_fifo.append(i)
                                if len(handoff_fifo) > queue_peak:
                                    queue_peak = len(handoff_fifo)
                                if obs.TRACER.active:
                                    obs.instant(
                                        "handoff_queued", cat="serving",
                                        args={"rid": req.uid, "slot": i,
                                              "tick": tick})
                                if obs.REQLOG.enabled:
                                    # Open the ledger's handoff segment:
                                    # parked until a decode slot adopts.
                                    obs.REQLOG.park(req.uid)
                dt_pf = time.monotonic() - tp0
                prefill_s += dt_pf
                # CPU-proxy attribution: the serialized prefill section
                # must not count against decode-pool inter-token gaps —
                # shift every live decode slot's last-token clock past it
                # (see the module docstring; the serialized totals stay
                # in ServeReport.handoff).
                for i, st in enumerate(dc._slot_state):
                    if st == "live":
                        dc._last_tok_t[i] += dt_pf
                if FLIGHT.enabled:
                    FLIGHT.record({
                        "worker": "prefill",
                        "tick": tick,
                        "t_s": round(now - t0, 6),
                        "states": list(pf._slot_state),
                        "chunk_plan": [[s, int(n), bool(last)]
                                       for s, n, last in plan],
                        "chunk_tokens": chunk_tokens,
                        "handoff_queue": len(handoff_fifo),
                        "pending": len(pending),
                        "queue_depth": queue_depth,
                        "prefix_hits": pf._tick_prefix_hits,
                        "prefix_reused": pf._tick_prefix_reused,
                        # Robustness arcs this tick (the fused engine's
                        # black-box keys — a storm reads the same way).
                        "cancelled": pf._tick_cancelled,
                        "deadline_expired": pf._tick_deadline,
                        "shed": pf._tick_shed,
                        **({"restored_blocks": pf._tick_restored,
                            "host_blocks_used": self.host_pool.used}
                           if self.host_pool is not None else {}),
                        "draining": draining,
                    })

                # ---- decode-worker tick: Tq=1 / speculative verify only.
                td0 = time.monotonic()
                live_idx = [i for i, st in enumerate(dc._slot_state)
                            if st == "live"]
                tokens_this_tick = 0
                if obs.REGISTRY.enabled:
                    _SLOTS_OCCUPIED.set(len(live_idx))
                dc_span = obs.span(
                    "disagg:decode_tick", cat="serving",
                    args=None if not obs.TRACER.active else {
                        "tick": tick, "occupancy": len(live_idx),
                    },
                )
                with dc_span:
                    if live_idx and dc._speculate:
                        spec_plan: Dict[int, PackedSpec] = {}
                        for i in live_idx:
                            spec_plan[i] = dc._draft_slot(i)
                        rows_max = max(p.rows for p in spec_plan.values())
                        tq = (dc._spec_bucket(rows_max) if rows_max > 1
                              else 1)
                        mat = np.zeros((dc.slots, tq), np.int32)
                        n_vec = np.zeros((dc.slots,), np.int32)
                        reset = np.zeros((dc.slots,), bool)
                        reset_val = np.zeros((dc.slots,), np.int32)
                        emit = np.zeros((dc.slots,), bool)
                        use_dev0 = np.zeros((dc.slots,), bool)
                        # Per-ROW key-chain operands (ISSUE 20): decode-
                        # worker verify rows always ride the slot's own
                        # spec chain (branch < 0), stream index = emitted
                        # count + row depth — same fill as the fused
                        # engine's spec tick.
                        sidx = np.asarray(
                            [len(t) for t in dc._slot_tokens], np.int32
                        )
                        branch_m = np.full((dc.slots, tq), -1, np.int32)
                        ridx_m = sidx[:, None] + np.tile(
                            np.arange(tq, dtype=np.int32),
                            (dc.slots, 1),
                        )
                        need_tree = False
                        for i, pack in spec_plan.items():
                            r = pack.rows
                            dc._ensure_blocks(i, dc._slot_clen[i] + r)
                            mat[i, :r] = pack.row_tokens
                            n_vec[i] = r
                            # reset_val IS both the spec rollback and the
                            # adoption length fix (clen == plen there).
                            reset[i] = True
                            reset_val[i] = dc._slot_clen[i]
                            ridx_m[i, :r] = sidx[i] + pack.depth
                            if not np.array_equal(
                                pack.depth, np.arange(r, dtype=np.int32)
                            ):
                                need_tree = True
                        pending_reset.clear()
                        dc._sync_table()
                        if tok_dirty:
                            dc.tok = jnp.asarray(dc._tok_host)
                            tok_dirty = False
                        args = (
                            dc.params, jnp.asarray(mat), dc.tok,
                            jnp.asarray(use_dev0), jnp.asarray(n_vec),
                            jnp.asarray(reset), jnp.asarray(reset_val),
                            jnp.asarray(emit),
                        )
                        extra = (
                            dc._keys, jnp.asarray(dc._temp_np),
                            jnp.asarray(dc._topk_np),
                            jnp.asarray(sidx), dc._lp,
                            jnp.asarray(dc._salt_np),
                            jnp.asarray(branch_m), jnp.asarray(ridx_m),
                        )
                        if need_tree:
                            depth_m = np.tile(
                                np.arange(tq, dtype=np.int32),
                                (dc.slots, 1),
                            )
                            bits_m = np.broadcast_to(
                                np.tril(np.ones((tq, tq), bool)),
                                (dc.slots, tq, tq),
                            ).copy()
                            for i, pack in spec_plan.items():
                                r = pack.rows
                                depth_m[i, :r] = pack.depth
                                bits_m[i, :r, :r] = pack.anc
                            dc.tok, dc._lp, fused_dev, _, dc.cache = \
                                dc._spec_tree(
                                    *args, jnp.asarray(depth_m),
                                    jnp.asarray(bits_m), dc.cache,
                                    *extra,
                                )
                        else:
                            dc.tok, dc._lp, fused_dev, _, dc.cache = \
                                dc._spec_lin(
                                    *args, dc.cache, *extra,
                                )
                        # lint: allow[host-sync] the decode worker's one per-tick fetch (fused token/logprob vectors + every verify-row draw)
                        fused_host = np.asarray(fused_dev)
                        dc._tok_host = fused_host[:, 0, 0]
                        dc._lp_host = np.ascontiguousarray(
                            fused_host[:, 0, 1]
                        ).view(np.float32)
                        alltok_host = fused_host[:, 1:, 0]
                        alllp_host = np.ascontiguousarray(
                            fused_host[:, 1:, 1]
                        ).view(np.float32)
                        now2 = time.monotonic()
                        decode_ticks += 1
                        occupancy += len(live_idx)
                        n_new = dc._spec_commit_all(
                            spec_plan, alltok_host, alllp_host, tq, now2,
                            tick, results, tbt,
                        )
                        tokens += n_new
                        tokens_this_tick += n_new
                        # The commit may have dispatched a compaction —
                        # relay after, not before.
                        self._relay_pool(dc, pf)
                    elif live_idx:
                        n_vec = np.zeros((dc.slots,), np.int32)
                        emit = np.zeros((dc.slots,), bool)
                        reset = np.zeros((dc.slots,), bool)
                        reset_val = np.zeros((dc.slots,), np.int32)
                        n_vec[live_idx] = 1
                        emit[live_idx] = True
                        for i, plen in pending_reset.items():
                            # The one decode dispatch where the device
                            # learns an adopted slot's length.
                            if dc._slot_state[i] == "live":
                                reset[i] = True
                                reset_val[i] = plen
                        pending_reset.clear()
                        for i in list(dc._live_reset):
                            # A forked child's device length learns the
                            # fork point at its first consuming tick
                            # (mirrors the fused engine's fork resets).
                            if dc._slot_state[i] == "live":
                                reset[i] = True
                                reset_val[i] = dc._live_reset.pop(i)
                        for i in live_idx:
                            dc._ensure_blocks(
                                i, len(dc._slot_req[i].prompt)
                                + len(dc._slot_tokens[i])
                            )
                        sidx = np.asarray(
                            [len(t) for t in dc._slot_tokens], np.int32
                        )
                        dc._sync_table()
                        if tok_dirty:
                            dc.tok = jnp.asarray(dc._tok_host)
                            tok_dirty = False
                        dc.tok, dc._lp, fused_dev, _, dc.cache = dc._mixed(
                            dc.params, dc.tok[:, None],
                            jnp.asarray(n_vec), jnp.asarray(reset),
                            jnp.asarray(reset_val), jnp.asarray(emit),
                            dc.cache, dc._keys,
                            jnp.asarray(dc._temp_np),
                            jnp.asarray(dc._topk_np),
                            jnp.asarray(sidx), dc._lp,
                        )
                        self._relay_pool(dc, pf)
                        # lint: allow[host-sync] the decode worker's one per-tick fetch (token vector + bitcast logprobs, one fused array)
                        fh = np.asarray(fused_dev)
                        dc._tok_host = fh[:, 0]
                        dc._lp_host = np.ascontiguousarray(
                            fh[:, 1]
                        ).view(np.float32)
                        now2 = time.monotonic()
                        decode_ticks += 1
                        occupancy += len(live_idx)
                        for i in live_idx:
                            req = dc._slot_req[i]
                            tok_i = int(dc._tok_host[i])
                            # Every live decode slot has a first token
                            # already (handoff adoption, or the fork's
                            # family pass) — always an inter-token gap.
                            dc._slot_tokens[i].append(tok_i)
                            dc._slot_cum_lp[i] += float(dc._lp_host[i])
                            dc._push_token(req, tok_i, dc._slot_index[i])
                            tokens += 1
                            tokens_this_tick += 1
                            gap = max(now2 - dc._last_tok_t[i], 0.0)
                            tbt.append(gap)
                            dc._last_tok_t[i] = now2
                            if gap > dc._slot_max_tbt[i]:
                                dc._slot_max_tbt[i] = gap
                            self.slo.observe_tbt(gap)
                            if obs.REGISTRY.enabled:
                                _TOKENS.inc()
                                _TBT.observe(gap)
                            if (req.fork_at is not None
                                    and dc._slot_index[i] == 0
                                    and len(dc._slot_tokens[i])
                                    == req.fork_at):
                                # Replayable mid-generation branch: the
                                # request forks itself through the
                                # pair's mailbox (applied on this
                                # worker at the next sweep).
                                self.fork(req.uid)
                            if req.eos_id is not None \
                                    and tok_i == req.eos_id:
                                dc._retire(i, tick, OUTCOME_EOS, results)
                            elif (len(dc._slot_tokens[i])
                                    >= req.max_new_tokens):
                                dc._retire(i, tick, OUTCOME_BUDGET,
                                           results)
                decode_s += time.monotonic() - td0
                if self.pool.used > peak_used:
                    peak_used = self.pool.used
                self.pool.publish_gauges()
                if self.host_pool is not None:
                    # The pair's staged D2H flush point (mirrors the
                    # fused engine's end-of-tick flush): both workers
                    # have dispatched, the relayed pool arrays are
                    # current, and the fetch overlaps the loop's idle
                    # gap toward the next tick's host work.
                    pf._flush_demotions()
                    self.host_pool.publish_gauge()
                if FLIGHT.enabled:
                    FLIGHT.record({
                        "worker": "decode",
                        "tick": tick,
                        "t_s": round(now - t0, 6),
                        "occupancy": len(live_idx),
                        "states": list(dc._slot_state),
                        "tokens_emitted": tokens_this_tick,
                        "handoff_queue": len(handoff_fifo),
                        "kv_blocks_used": self.pool.used,
                        "kv_blocks_free": self.pool.free_count,
                        "draining": draining,
                    })
                self.slo.maybe_export(now)
                tick += 1
        except BaseException as e:
            STARTUP.end(run_span, ticks=tick)
            FLIGHT.dump_if_armed(f"disagg_error:{type(e).__name__}")
            if obs.TRACER.active:
                obs.instant("engine_error", cat="serving", args={
                    "error": type(e).__name__, "tick": tick,
                })
            raise

        if self.host_pool is not None:
            # A drained run leaves no demotion staged: the ledger's
            # _DEMOTED blocks would otherwise read as leaked capacity.
            pf._flush_demotions()
            self.host_pool.publish_gauge()
        if FLIGHT.enabled:
            FLIGHT.mark_idle()
        with self._lock:
            self._cancel_uids.clear()
            self._draining = False
        t_end = time.monotonic()
        wall = t_end - t0
        self.slo.export_gauges()
        slo_snap = self.slo.snapshot()
        prefix_snap: Dict[str, Any] = {}
        if self.prefix_index is not None:
            p1 = self.prefix_index.stats()
            reused = p1["tokens_reused"] - prefix0["tokens_reused"]
            prompt_tokens = sum(r.prompt_len for r in results)
            prefix_snap = {
                "hits": p1["hits"] - prefix0["hits"],
                "misses": p1["misses"] - prefix0["misses"],
                "tokens_reused": reused,
                "reused_ratio": round(reused / prompt_tokens, 4)
                if prompt_tokens else 0.0,
                "evictions": p1["evictions"] - prefix0["evictions"],
                "pool_blocks_used": p1["pool_blocks_used"],
                "pool_blocks": p1["pool_blocks"],
                # Reference-in-place for exact blocks; int8 hits count
                # their dequant gather into staging (ISSUE 13).
                "hit_bytes_moved": pf._hit_bytes_moved - hit_bytes0,
            }
        kv_snap = {
            "layout": "paged",
            "block": self.kv_block,
            "pool_blocks": self.kv_blocks,
            "blocks_used": self.pool.used,
            "blocks_free": self.pool.free_count,
            "peak_blocks_used": peak_used,
        }
        if self.host_pool is not None:
            h1 = self.host_pool.stats()
            kv_snap.update({
                "host_blocks": h1["host_blocks"],
                "host_blocks_used": h1["host_blocks_used"],
                "demotions": h1["demotions"] - host0["demotions"],
                "restores": h1["restores"] - host0["restores"],
                "host_drops": h1["host_drops"] - host0["host_drops"],
            })
        handoff_snap = {
            "handoffs": self.handoffs - handoffs0,
            "blocks_transferred": self.pool.transferred - transferred0,
            "queue_peak": queue_peak,
            "kv_bytes_moved": 0,  # the in-process contract, audited by
            # transfer_private: ownership moves, the bytes do not
            "prefill_tick_s": round(prefill_s, 4),
            "decode_tick_s": round(decode_s, 4),
        }
        spec_snap: Dict[str, Any] = {}
        if dc._speculate:
            prop = dc._spec_proposed - spec0[0]
            acc = dc._spec_accepted - spec0[1]
            spec_snap = {
                "drafter": type(dc._drafter).__name__,
                "draft_k": dc.draft_k,
                "proposed": prop,
                "accepted": acc,
                "acceptance_rate": round(acc / prop, 4) if prop else 0.0,
                "verify_ticks": dc._spec_ticks - spec0[2],
                "tokens_per_verify": round(
                    1.0 + acc / (dc._spec_verifies - spec0[3]), 4
                ) if dc._spec_verifies - spec0[3] else 0.0,
            }
        log.info(
            "disagg served %d request(s): %d tokens, %d handoff(s), "
            "%d decode tick(s), %.1f tok/s, mean decode occupancy "
            "%.2f/%d",
            len(results), tokens, self.handoffs - handoffs0,
            decode_ticks, tokens / wall if wall > 0 else 0.0,
            occupancy / max(decode_ticks, 1), dc.slots,
        )
        run_span[3].update(
            ticks=tick, prompt_tokens=sum(r.prompt_len for r in results),
            tokens_generated=tokens)
        report = ServeReport(
            results=sorted(results, key=lambda r: r.uid),
            ticks=tick,
            wall_s=wall,
            tokens_generated=tokens,
            mean_occupancy=occupancy / max(decode_ticks, 1),
            tbt_s=list(tbt),
            slo=slo_snap,
            prefix=prefix_snap,
            kv=kv_snap,
            spec=spec_snap,
            handoff=handoff_snap,
            requests=obs.aggregate_ledgers(
                [r.ledger for r in results if r.ledger is not None]
            ) or {},
            startup=STARTUP.snapshot(),
        )
        STARTUP.end(run_span, t_end)
        return report
